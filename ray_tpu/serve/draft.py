"""Drafters for speculative decoding in the :class:`~.engine.DecodeEngine`
(ISSUE 9).

A drafter proposes ``draft_k`` candidate tokens per active slot at every
chunk boundary; the target model then verifies all of them in ONE
batched forward
(:func:`~ray_tpu.models.gpt_decode.verify_chunk_slots_paged`) and commits the accepted prefix plus its own correction/bonus token.
Because acceptance is exact (greedy match at temperature 0, lossless
rejection sampling above it), a drafter can NEVER change the committed
stream — only how many target forwards it takes to produce it — so the
protocol is deliberately tiny and entirely advisory.

Contract every drafter must keep (the engine's replay machinery leans
on it):

- **Determinism**: proposals must be a pure function of the slot's
  committed history (prompt + delivered tokens). Crash-resume replays
  the stream on another replica by re-running the same deterministic
  generation; a stateful or randomized drafter would change the
  accepted lengths — harmless for token identity, but it would shift
  the temperature>0 PRNG chain and break bit-exact replay.
- **Per-slot isolation**: no state shared across slots (a slot's
  proposals must not depend on which other requests are resident).
- **Driver-thread only**: every method is called from the engine's
  driver thread, between device dispatches — no locking, and device
  drafters may dispatch freely (rtlint RT102 ``owner=driver``).

Two implementations ship:

- :class:`NGramDrafter` — a host-side n-gram table per slot, built from
  the prompt and committed tokens (prompt-lookup decoding). Zero device
  cost and zero compiled programs; wins whenever the output is locally
  repetitive (templated/structured text, code, the loops greedy
  decoding falls into).
- :class:`ModelDrafter` — a small GPT (typically sharing the target's
  embedding, see :func:`tied_drafter_params`) decoding greedily into
  its own dense cache that mirrors the engine's slots. Wins when a
  trained/distilled draft model actually approximates the target;
  costs ``len(prompt_buckets) + 2`` extra compiled programs (its own
  prefill per bucket, a k-step draft chunk, and a 1-token ingest).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class Drafter:
    """Protocol for speculative-decoding proposal sources.

    Lifecycle per slot: :meth:`admit` when the engine prefills a prompt
    into it, :meth:`propose` + :meth:`observe` once per verify round
    while the lane runs, :meth:`free` when the lane ends for any reason
    (EOS, max_new, deadline, abandonment, failure). :meth:`configure`
    is called once by the engine before any traffic (and again after a
    supervisor driver restart, via :meth:`reset`)."""

    name = "base"

    def configure(self, *, slots: int, max_len: int,
                  prompt_buckets: Sequence[int], draft_k: int):
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.prompt_buckets = tuple(prompt_buckets)
        self.draft_k = int(draft_k)

    def admit(self, slot: int, prompt: np.ndarray, first_token: int):
        """A prompt was prefilled into ``slot``; ``first_token`` is the
        target's fused first sample (already delivered)."""

    def propose(self, active: np.ndarray, last: np.ndarray) -> np.ndarray:
        """``[slots, draft_k]`` int32 proposals; rows of inactive slots
        are ignored. ``last`` is each slot's last delivered token."""
        raise NotImplementedError

    def observe(self, slot: int, tokens: np.ndarray, accepted: int):
        """``tokens`` were committed to ``slot`` this round (the
        accepted drafts plus the target's correction/bonus);
        ``accepted`` of this drafter's proposals were accepted, or
        ``-1`` when the round ran the plain chunk path (adaptive
        speculation parked this slot, so nothing was proposed —
        only drafters with an :meth:`estimate` ever see ``-1``).
        Called only for lanes that keep running — ended lanes get
        :meth:`free` instead."""

    def estimate(self, slot: int) -> Optional[float]:
        """Expected accepted proposals for a verify round on ``slot``
        right now, or None for "no self-assessment" — the engine then
        always speculates the slot (``None`` is treated as +inf
        against ``spec_threshold``). Must be a deterministic function
        of the slot's committed history: the engine's per-slot
        speculate-or-chunk decision feeds the PRNG consumption
        pattern, so crash-resume replay depends on it."""
        return None

    def free(self, slot: int):
        """The lane in ``slot`` ended; drop its state."""

    def reset(self):
        """Drop ALL per-slot state (supervisor driver restart: the
        engine pool was rebuilt from scratch and every lane failed)."""


class NGramDrafter(Drafter):
    """Host-side prompt-lookup drafter: per slot, an n-gram table from
    the prompt + committed tokens maps each trailing context of length
    ``min_n..max_n`` to its observed continuations; proposals extend
    the history with the MOST FREQUENT continuation of the longest
    matching context (ties break to the smallest token id — the whole
    proposal is deterministic). With no match the last token repeats
    (self-loops are the most common attractor). Zero device cost: the
    engine's compiled-program set stays ``len(prompt_buckets) + 1 + 1``.
    """

    name = "ngram"

    #: EMA smoothing for the per-slot hit self-assessment.
    EMA_ALPHA = 0.5

    def __init__(self, max_n: int = 4, min_n: int = 1):
        if not 1 <= min_n <= max_n:
            raise ValueError(f"need 1 <= min_n <= max_n, got "
                             f"[{min_n}, {max_n}]")
        self.max_n = int(max_n)
        self.min_n = int(min_n)
        self._hist: Dict[int, List[int]] = {}
        #: slot -> {(n, ctx tuple) -> {token -> count}}
        self._tab: Dict[int, Dict[Tuple, Dict[int, int]]] = {}
        #: slot -> EMA of per-round would-have-hit counts (the
        #: adaptive-speculation signal; deterministic from history).
        self._ema: Dict[int, float] = {}

    def _index(self, slot: int, start: int):
        """Count the continuations introduced by hist[start:]."""
        h = self._hist[slot]
        tab = self._tab[slot]
        for t in range(max(start, self.min_n), len(h)):
            for n in range(self.min_n, min(self.max_n, t) + 1):
                key = (n, tuple(h[t - n:t]))
                bucket = tab.setdefault(key, {})
                bucket[h[t]] = bucket.get(h[t], 0) + 1

    def admit(self, slot: int, prompt: np.ndarray, first_token: int):
        self._hist[slot] = [int(t) for t in prompt] + [int(first_token)]
        self._tab[slot] = {}
        self._ema[slot] = 0.0
        self._index(slot, 0)

    def _propose_one(self, slot: int, k: int) -> List[int]:
        """k deterministic proposals extending slot's history: most
        frequent continuation of the longest matching context, ties to
        the smallest token, last-token self-loop as fallback."""
        out: List[int] = []
        tail = list(self._hist[slot][-self.max_n:])
        extra: Dict[Tuple, Dict[int, int]] = {}
        tab = self._tab[slot]
        empty: Dict[int, int] = {}
        for _ in range(k):
            nxt = None
            for n in range(min(self.max_n, len(tail)),
                           self.min_n - 1, -1):
                key = (n, tuple(tail[-n:]))
                base = tab.get(key, empty)
                ext = extra.get(key, empty)
                if not base and not ext:
                    continue
                # Max by (count, -token) over base+ext WITHOUT copying
                # base (this is the propose hot loop): ext tokens get
                # their combined count, pure-base tokens their own.
                best = None
                for tok, c in base.items():
                    if tok not in ext:
                        cand = (c, -tok)
                        if best is None or cand > best:
                            best = cand
                for tok, c in ext.items():
                    cand = (c + base.get(tok, 0), -tok)
                    if best is None or cand > best:
                        best = cand
                nxt = -best[1]
                break
            if nxt is None:
                nxt = tail[-1]
            out.append(nxt)
            # Count the hypothetical extension too, so a proposal that
            # starts a repeat immediately reinforces itself.
            for n in range(self.min_n, min(self.max_n, len(tail)) + 1):
                key = (n, tuple(tail[-n:]))
                b = extra.setdefault(key, {})
                b[nxt] = b.get(nxt, 0) + 1
            tail.append(nxt)
            tail = tail[-self.max_n:]
        return out

    def observe(self, slot: int, tokens: np.ndarray, accepted: int):
        h = self._hist.get(slot)
        if h is None:
            return
        # Self-assessment BEFORE indexing the new tokens: how many of
        # this round's committed tokens would this table have proposed?
        # Verify rounds already measured it — ``accepted`` IS that
        # count; chunk rounds (accepted == -1, nothing was proposed)
        # replay the proposal against the committed row. Either way the
        # EMA is a pure function of the committed history, which
        # adaptive mode leans on for deterministic replay.
        if accepted >= 0:
            hit = accepted
        else:
            hyp = self._propose_one(slot, min(self.draft_k, len(tokens)))
            hit = 0
            for want, got in zip(tokens, hyp):
                if int(want) != got:
                    break
                hit += 1
        self._ema[slot] = ((1.0 - self.EMA_ALPHA) * self._ema[slot]
                           + self.EMA_ALPHA * hit)
        start = len(h)
        h.extend(int(t) for t in tokens)
        self._index(slot, start)

    def estimate(self, slot: int) -> Optional[float]:
        return self._ema.get(slot, 0.0)

    def propose(self, active: np.ndarray, last: np.ndarray) -> np.ndarray:
        out = np.zeros((self.slots, self.draft_k), np.int32)
        for i in range(self.slots):
            if not active[i] or i not in self._hist:
                continue
            out[i, :] = self._propose_one(i, self.draft_k)
        return out

    def free(self, slot: int):
        self._hist.pop(slot, None)
        self._tab.pop(slot, None)
        self._ema.pop(slot, None)

    def reset(self):
        self._hist.clear()
        self._tab.clear()
        self._ema.clear()


class ModelDrafter(Drafter):
    """Device drafter: a small GPT decoding greedily into its OWN
    dense cache whose slots mirror the engine's 1:1 (same ``max_len``,
    same prompt buckets, so positions track the target exactly). The
    cache is the page pool with one ``max_len`` page per slot and the
    fixed table ``pt[s] = [s]``: the engine's programs, no allocator,
    no prefix cache.

    Per verify round the drafter runs one fused k-step greedy chunk
    (:func:`~ray_tpu.models.gpt_decode.decode_chunk_slots_paged` of its
    own model) to propose, and after the verify it rolls its write cursor
    back past rejected positions — host-authoritative ``pos`` is
    re-uploaded wholesale each round, and garbage K/V beyond it is
    overwritten before it is ever attended (the engine's standard
    exactness argument). A fully-accepted round leaves exactly one
    committed token (``d_k``) without K/V in the drafter cache; it is
    ingested lazily by a 1-token chunk before the next proposal, so the
    drafter's compiled-program set is bounded at
    ``len(prompt_buckets) + 2`` for any traffic."""

    name = "model"

    def __init__(self, params, cfg):
        self.params = params
        self.cfg = cfg

    # The drafter's own program set (rtflow RT109): its prefill per
    # prompt bucket + the k-step draft chunk + the 1-token lazy ingest.
    # rtlint: program-budget: len(prompt_buckets) + 2
    def configure(self, *, slots: int, max_len: int,
                  prompt_buckets: Sequence[int], draft_k: int):
        super().configure(slots=slots, max_len=max_len,
                          prompt_buckets=prompt_buckets, draft_k=draft_k)
        if max_len > self.cfg.max_seq:
            raise ValueError(
                f"drafter max_seq {self.cfg.max_seq} cannot mirror "
                f"engine max_len {max_len}")
        from ..models import gpt_decode

        self._gd = gpt_decode
        self._pt = np.arange(self.slots, dtype=np.int32)[:, None]
        self._prefill = gpt_decode.jit_prefill_into_slot_paged(
            self.cfg, self.max_len, 0.0)
        self._step = gpt_decode.jit_decode_chunk_slots_paged(
            self.cfg, self.draft_k, self.max_len, 0.0, -1)
        self._ingest = gpt_decode.jit_decode_chunk_slots_paged(
            self.cfg, 1, self.max_len, 0.0, -1)
        self.reset()

    def reset(self):
        self._cache = self._gd.init_paged_cache(
            self.cfg, self.slots, self.slots, self.max_len)
        self._pos = np.zeros((self.slots,), np.int32)
        self._pending = np.full((self.slots,), -1, np.int64)
        self._rngs = np.zeros((self.slots, 2), np.uint32)

    # entry=driver: admission is the engine driver's first touch of a
    # slot — rtsan re-registers the drafter's owner thread here, so a
    # supervisor-restarted engine (new driver thread, drafter reset)
    # rebinds on its first admission instead of tripping RS103.
    # rtlint: owner=driver entry=driver
    def admit(self, slot: int, prompt: np.ndarray, first_token: int):
        import jax

        S = int(prompt.shape[0])
        bucket = next(b for b in self.prompt_buckets if b >= S)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :S] = prompt
        # The fused first-token sample is the TARGET's job; the
        # drafter's is discarded — only the prompt K/V matters here.
        _tok, cache, _key = self._prefill(
            self.params, self._cache, padded, np.int32(S), np.int32(0),
            self._pt[slot], np.int32(self._gd.PT_SENTINEL),
            np.int32(slot), jax.random.PRNGKey(0))
        self._cache = cache
        self._pos[slot] = S
        self._pending[slot] = -1

    # rtlint: owner=driver
    def propose(self, active: np.ndarray, last: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        # Host-authoritative write cursor: rejected draft positions
        # were rolled back in observe(), so upload pos wholesale (tiny
        # [slots] int32 against the draft forward). A private host copy:
        # the transfer queues behind the admission's prefill, and
        # ``_pos`` advances below before it has run.
        self._cache["pos"] = jnp.asarray(self._pos.copy())
        pend = active & (self._pending >= 0)
        if pend.any():
            ptok = np.where(pend, self._pending, 0).astype(np.int32)
            _t, cache, _d, _r = self._ingest(
                self.params, self._cache, ptok, self._rngs, pend,
                self._pt)
            self._cache = cache
            self._pos[pend] += 1
            self._pending[pend] = -1
        toks, cache, _done, _rngs = self._step(
            self.params, self._cache, np.asarray(last, np.int32),
            self._rngs, active, self._pt)
        self._cache = cache
        self._pos[active] += self.draft_k
        # The drafted tokens must reach the host: the verify dispatch
        # feeds them back as its device inputs.
        # rtlint: sync-ok=proposals proposals feed the verify dispatch
        return np.asarray(toks)

    def observe(self, slot: int, tokens: np.ndarray, accepted: int):
        k = self.draft_k
        if accepted < 0:
            # Chunk-round observe: cannot happen — this drafter has no
            # estimate(), so the engine always speculates its slots.
            raise RuntimeError(
                "ModelDrafter saw a chunk-round observe; its KV cache "
                "cannot ingest unproposed tokens")
        if accepted >= k:
            # Every proposal accepted: the draft chunk wrote K/V for
            # [last, d_1..d_{k-1}] — all committed — but d_k's K/V is
            # missing. Ingest it lazily before the next proposal.
            self._pending[slot] = int(tokens[k - 1])
        else:
            # Roll the cursor back past the rejected positions: valid
            # K/V runs through [last, d_1..d_a] at pos0..pos0+a.
            self._pos[slot] += accepted + 1 - k
            self._pending[slot] = -1

    def free(self, slot: int):
        self._pos[slot] = 0
        self._pending[slot] = -1


def tied_drafter_params(target_params, target_cfg, *, n_layer: int = 1,
                        seed: int = 0):
    """Build ``(params, cfg)`` for a :class:`ModelDrafter` that SHARES
    the target's embedding and position tables (the same arrays — zero
    extra HBM for the dominant parameter block) over a fresh
    ``n_layer``-deep trunk. Deterministic for a given seed, so every
    replica builds the identical drafter — required for bit-exact
    crash-resume replay with ``spec_decode="model"``."""
    import dataclasses

    import jax

    from ..models import gpt

    dcfg = dataclasses.replace(target_cfg, n_layer=int(n_layer))
    params = gpt.init_params(jax.random.PRNGKey(int(seed)), dcfg)
    params["embed"] = target_params["embed"]
    params["pos_embed"] = target_params["pos_embed"]
    return params, dcfg


def make_drafter(spec, params=None, cfg=None) -> Optional[Drafter]:
    """Resolve the engine/config-plane ``spec_decode`` knob:

    - ``None``/``False`` → no drafter (speculative decoding off);
    - ``True`` / ``"ngram"`` → a fresh :class:`NGramDrafter`;
    - ``"model"`` → a :class:`ModelDrafter` over
      :func:`tied_drafter_params` of the engine's own weights;
    - a :class:`Drafter` instance → used as-is.
    """
    if spec is None or spec is False:
        return None
    if isinstance(spec, Drafter):
        return spec
    if spec is True or spec == "ngram":
        return NGramDrafter()
    if spec == "model":
        if params is None or cfg is None:
            raise ValueError(
                "spec_decode='model' needs the engine's params/cfg to "
                "build the tied-embedding drafter")
        return ModelDrafter(*tied_drafter_params(params, cfg))
    raise ValueError(
        f"spec_decode must be False, True, 'ngram', 'model', or a "
        f"Drafter instance, got {spec!r}")

"""The serving FRAME and what it asks of a model: its DESCRIPTION.

:class:`~ray_tpu.serve.engine.DecodeEngine` serves any decoder whose
config object answers ``cfg.decode_programs()`` with a module (or
namespace) of the paged slot-pool programs: the engine binds no model
module by name. Seven decoders answer today: the GPT-2 block
(:mod:`ray_tpu.models.gpt_decode`: a page holds keys and values per
head), the latent-attention expert decoder
(:mod:`ray_tpu.models.mla_moe`: a page holds one 576-wide latent a
token in a row of 640 lanes, no head axis), the hybrid
linear-attention expert decoder (:mod:`ray_tpu.models.kda_moe`: one
layer in four keeps grouped keys and values in pages, the others a
fixed recurrent state and a short convolution's tail PER SLOT), the
shortcut-connected expert decoder (:mod:`ray_tpu.models.scmoe`: TWO
latent attentions a layer, so the latent entry counts ``2 * n_layer``
layers of the one pool; the attention is ``mla_moe``'s, imported under
its public names), the parallel hybrid decoder
(:mod:`ray_tpu.models.ssm_hybrid`: a state-space state and a
convolution's tail per slot BESIDE rotary grouped keys and values in
pages, in EVERY layer; decode's attention is ``kda_moe``'s, through its
public entry) and the state-space expert decoder
(:mod:`ray_tpu.models.ssm_moe`: Mamba-2 layers and NoPE grouped-query
attention layers IN TURN, by index, so a layer keeps a state per slot
OR pages; the mixer is ``ssm_hybrid``'s and the attention ``kda_moe``'s,
both imported under public names; its expert layer is routed by the
THIRD router, :func:`ray_tpu.models.moe.route_topk_softmax`, the top k
logits and a softmax over the chosen ones, and its head is its
table) and the sparse latent-attention expert decoder
(:mod:`ray_tpu.models.dsa_moe`: ``mla_moe``'s block whose page holds a
SECOND per-token entry, an indexer's key, under the same page ids, and
whose decode step attends over the ``index_topk`` cached tokens the
indexer scores highest; the attention and its kernel are ``mla_moe``'s,
imported under public names, the kernel with the selection as a mask).

**A description provides** what only the model knows:

``cache_spec(cfg, kv_dtype) -> CacheSpec``
    what one token leaves in a page and what a sequence keeps in its
    slot, per layer that keeps it (below). The ONE place the pool's
    shapes come from: :func:`init_paged_pool`,
    :func:`CacheSpec.bytes_per_page`, :func:`CacheSpec.bytes_per_slot`,
    the engine's handoff shape checks and ``stats()``'s
    ``kv_bytes_per_token`` / ``state_bytes_per_slot`` all read it. It
    refuses a ``kv_dtype`` the model has not (:func:`check_kv_dtype`).
``prefill_into_slot_paged(params, cache, tokens, length, hist_len,
pt_row, cow_src, slot, rng, *, cfg, page_size, temperature, kv_dtype)``
    one prompt suffix into its pages (and its slot), with the first
    token's sample: ``(token, cache', rng')``.
``prefill_group_into_slots_paged(params, cache, tokens, length,
hist_len, pt_row, cow_src, slot, rng, *, cfg, page_size, temperature,
kv_dtype)``
    the same for the ``G`` prompts admitted at ONE chunk boundary, in
    one launch: ``tokens`` a tuple of ``G`` arrays ``[1, bucket_g]``
    (each suffix in its OWN bucket: no prompt is padded to another's)
    and every other operand with a leading ``G`` (``pt_row`` ``[G,
    max_pages]``, ``rng`` ``[G, 2]``); ``(tokens [G], cache', rngs [G,
    2])``. The prompts' rows lie end to end (:class:`PromptRows`): what
    is per ROW (embedding, norms, projections, the FFNs and the expert
    layer, the head over the last rows) runs over all of them as one
    set of matrix products, so a weight is read once a launch and an
    expert sees the rows of every prompt; what is per PROMPT (the
    suffix's attention and its read of the cached prefix, a recurrence
    from its zero state, the slot written, the sample with the
    request's own key) takes the prompt's own rows, one prompt after
    another, each row's arithmetic the single prefill's. The prompts'
    pages and slots are disjoint and no prompt reads a page another
    writes (the engine closes a group before such a request).
``_slot_decode_step_paged(params, cache, token, active, pt, cfg,
page_size, kv_dtype, attn_kernel)``
    ONE masked decode step over the slot pool: ``(logits, cache')``,
    and a third value, its int32 counters, where ``STEP_COUNTERS`` names
    any. The module binds the frame's chunk program to it
    (``decode_chunk_slots_paged = functools.partial(
    serving.decode_chunk_slots_paged, step=_slot_decode_step_paged,
    counters=len(STEP_COUNTERS))``).
``max_positions(cfg)``
    the longest sequence the model can place (a learned table's rows;
    a rotary model's declared reach).
``KV_DTYPES``, ``ATTN_KERNELS``
    the pool storage types it has, and the NAMES of its decode
    attention paths: what an ``attn_kernel`` knob may say. A name is
    the model's own and says nothing of how the path is built:
    ``gpt_decode`` has two (``"gather"`` plain XLA, ``"pallas"`` the
    fused kernel); ``mla_moe`` has ONE decode attention under one
    name, ``"gather"``, which is a Pallas kernel over each lane's live
    latent pages wherever Mosaic can address a page and plain XLA over
    the gathered pages where it cannot (the model chooses, by shape);
    ``kda_moe`` has one too, ``"gather"`` (its GQA layers' attention:
    a Pallas kernel over each lane's live pages of keys and values
    wherever Mosaic can address a page and a head, plain XLA over the
    gathered pages where it cannot), and chooses its recurrence's
    kernel by shape the same way.
``decode_attention_fused(cfg, page_size, attn_kernel) -> bool``
    whether the chunk program built with these knobs holds a fused
    kernel of its decode step's sequence mixing, WHICHEVER that is:
    ``gpt_decode`` answers by the knob (``attn_kernel == "pallas"``),
    ``mla_moe`` (and ``scmoe``, whose attention it is) for its
    attention over latent pages, by shape, ``kda_moe`` for TWO kernels,
    the recurrence on its per-slot state and its GQA layers' attention
    over pages, each taken by its own shapes: either one makes the
    answer true, ``ssm_hybrid`` for two as well, that attention's
    kernel and its own recurrence's on the per-slot state-space state,
    each by its own shapes, and ``ssm_moe`` for the same two, both
    imported, one a layer by the layer's kind. The engine asks it for
    ``warm_up()["attn_kernel_mode"]`` (``"compiled"`` / ``"interpret"``,
    read off the lowered program, or ``None`` without a kernel) and for
    ``stats()["attn_kernel_dispatches"]``; every description answers.
``UNSUPPORTED``
    ``{engine capability: reason}`` for what this model does not get
    (``"int8"``, ``"tp"``, ``"spec_decode"``, ``"roles"``,
    ``"prefix_cache"``): the engine raises the reason at construction.
    A model that lists ``"prefix_cache"`` gets no prefix cache unless
    asked, and the reason when asked.
``STEP_COUNTERS``
    names of the int32 counters its step returns, which the chunk
    program sums over its steps into a fifth output (``()``: four
    outputs); the engine adds them into ``stats()`` under those names.
``jit_verify_chunk_slots_paged``, ``jit_export_slot_kv_paged``,
``jit_import_slot_kv_paged``
    speculative verify and the KV handoff, or absent (GPT alone).
``check_tp``, ``shard_params``, ``shard_cache``, ``shard_program``
    a model WITH tensor-parallel programs (GPT alone) states its own
    placement: ``check_tp(cfg, tp)`` answers the mesh (``None`` for
    ``tp == 1``), ``shard_params`` / ``shard_cache`` place the weights
    and the pool on it, and ``shard_program(fn, mesh, n_out, ...)``
    wraps a program of ``(params, cache, *rest)`` for it. That wrapper
    is ALL a mesh adds to the frame's two factories.

**The frame provides**, once, for every description (a module takes
them under its own names with :func:`bind`, so the engine, the
benchmark and the tests call ``<module>.jit_prefill_into_slot_paged(
cfg, ...)`` as before):

- the chunk program (:func:`decode_chunk_slots_paged`): ONE
  ``lax.scan`` of a description's step with per-slot sampling
  (:func:`sample_slots`), the EOS mask-and-carry and the counters;
- the two factories every model has
  (:func:`jit_prefill_into_slot_paged`,
  :func:`jit_decode_chunk_slots_paged`): the knobs checked against the
  description (``KV_DTYPES``, ``ATTN_KERNELS``, ``check_tp``), the
  program named for a trace (:func:`program`: the XLA modules are
  ``jit_prefill_into_slot_paged`` and ``jit_decode_chunk_slots_paged``
  whatever the model), the pool donated, one jit wrapper a key
  (:func:`knob_cache`; the key holds the description);
- what follows from the spec: :func:`init_paged_cache`,
  :func:`kv_bytes_per_page`, and the :func:`check_tp` /
  :func:`shard_params` of a model that lists ``"tp"`` under
  ``UNSUPPORTED``;
- sampling (:func:`sample`, :func:`sample_slots`) and the paged pool's
  addressing that more than one model uses: :data:`PT_SENTINEL`,
  :func:`init_paged_pool`, :func:`flat`, :func:`at_layer`,
  :func:`live_length`, :func:`live_lanes`, and a prefill's read of its
  cached prefix (:func:`hist_blocks`, :func:`attend_history`).

No module under ``ray_tpu/models`` imports or reads an underscore name
of another (``tests/test_models_frame.py`` holds it; the training
step's ``_mm``, ``_rmsnorm``, ``_project_vocab`` of ``models/gpt.py``
are the listed exemption). Block math the expert decoders share
(``rmsnorm``, ``embed``, ``head``, ``block_ffn``) lives beside the
expert layer and its three routers in :mod:`ray_tpu.models.moe`.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

#: Page-table padding value. Positive and far beyond any real pool size,
#: so a sentinel is out-of-bounds for scatter (write DROPPED, never
#: clamped into someone else's page) while reads clip it to a real page
#: whose garbage the attention mask hides. Never use a negative
#: sentinel: traced negative indices WRAP in jnp indexing.
PT_SENTINEL = 2 ** 30


@dataclasses.dataclass(frozen=True)
class CacheEntry:
    """One array of the pool: ``per`` ``"token"`` (a row for each of a
    page's positions: ``[L, n_pages, page_size, *shape]``), ``"page"``
    (one row a page, e.g. a quantisation scale: ``[L, n_pages,
    *shape]``) or ``"slot"`` (what a SEQUENCE keeps whatever its
    length, e.g. a recurrent state: ``[L, slots, *shape]``; it belongs
    to the slot, not to a page: no page hash shares it and every
    prefill into the slot rebuilds it). ``n_layer``: how many layers
    keep this entry, where that is not the spec's ``n_layer`` (a model
    whose layers are of two kinds)."""
    name: str
    per: str
    shape: Tuple[int, ...]
    dtype: Any
    n_layer: Optional[int] = None

    def _bytes(self, rows: int) -> int:
        for s in self.shape:
            rows *= s
        return rows * jnp.dtype(self.dtype).itemsize

    def bytes_per_page(self, page_size: int) -> int:
        """Bytes of ONE layer's part of one page (0 for a per-slot
        entry: it lives in no page)."""
        return self._bytes({"token": page_size, "page": 1,
                            "slot": 0}[self.per])

    def bytes_per_slot(self) -> int:
        """Bytes of ONE layer's part of one slot (0 for an entry that
        lives in pages)."""
        return self._bytes(1 if self.per == "slot" else 0)


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Per layer, what a token leaves in a page and what a sequence
    keeps in its slot: the entries' trailing shapes and dtypes, and how
    many layers keep one (``n_layer``, or the entry's own count)."""
    n_layer: int
    entries: Tuple[CacheEntry, ...]

    def entry(self, name: str) -> CacheEntry:
        return next(e for e in self.entries if e.name == name)

    def layers(self, name: str) -> int:
        """How many layers keep the entry ``name``."""
        n = self.entry(name).n_layer
        return self.n_layer if n is None else n

    def bytes_per_page(self, page_size: int) -> int:
        """HBM bytes ONE physical page costs across all layers — the
        unit the engine's page budget is denominated in."""
        return sum(self.layers(e.name) * e.bytes_per_page(page_size)
                   for e in self.entries)

    def bytes_per_slot(self) -> int:
        """HBM bytes ONE slot's per-slot entries cost across all layers
        (0 for a model that keeps everything in pages)."""
        return sum(self.layers(e.name) * e.bytes_per_slot()
                   for e in self.entries)

    def token_shape(self, name: str, tokens: int) -> Tuple[int, ...]:
        """``[L, tokens, *shape]``: a per-token entry laid out over a
        contiguous run of tokens (the handoff's ship order)."""
        return (self.layers(name), tokens) + self.entry(name).shape


def init_paged_pool(spec: CacheSpec, slots: int, n_pages: int,
                    page_size: int) -> Dict[str, Any]:
    """Zeroed pool arrays for ``spec`` (pages, and the per-slot entries
    beside them) plus the per-slot ``pos``."""
    cache = {}
    for e in spec.entries:
        lead = {"token": (n_pages, page_size), "page": (n_pages,),
                "slot": (slots,)}[e.per]
        cache[e.name] = jnp.zeros((spec.layers(e.name),) + lead + e.shape,
                                  e.dtype)
    cache["pos"] = jnp.zeros((slots,), jnp.int32)
    return cache


def decode_programs(cfg):
    """The description of ``cfg``'s model (module docstring)."""
    try:
        return cfg.decode_programs()
    except AttributeError:
        raise TypeError(
            f"{type(cfg).__name__} does not describe a decoder the "
            f"engine can serve: it has no decode_programs()") from None


# ------------------------------------------------- programs and sampling
def knob_cache(fn):
    """``lru_cache`` with DEFAULT-NORMALIZED keys: ``f(cfg)``,
    ``f(cfg, tp=1)`` and ``f(cfg, ..., 1)`` all land on the SAME cache
    entry. The engine threads every static knob positionally (including
    default-valued ones like ``tp=1``), while tests and external
    callers omit trailing defaults — a raw ``lru_cache`` would key
    those spellings separately, silently doubling the compiled-program
    set and breaking the recompile guards' wrapper ``is``-identity.
    512 entries: the frame's two factories hold every description's
    wrappers (51 each of seven)."""
    sig = inspect.signature(fn)
    cached = functools.lru_cache(maxsize=512)(fn)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        bound = sig.bind(*args, **kwargs)
        bound.apply_defaults()
        return cached(*bound.args, **bound.kwargs)

    wrapper.cache_info = cached.cache_info
    wrapper.cache_clear = cached.cache_clear
    return wrapper


def program(fn, name: Optional[str] = None, **knobs):
    """``fn`` with its static knobs bound, under a ``__name__`` of its
    own: ``jax.jit`` calls the XLA module ``jit_<name>``, and that is
    what a profile shows for every launch (``jit__unknown`` for a
    ``functools.partial``, ``jit_fn`` for a local closure). ``name``
    defaults to ``fn``'s; every ``jit_<x>`` factory compiles programs
    named ``jit_<x>``, whatever its model and its mesh."""
    def program(*args):
        return fn(*args, **knobs)

    program.__name__ = program.__qualname__ = name or fn.__name__
    return program


def sample(logits, temperature: float, key):
    """One sampling decision; greedy iff temperature == 0 (static)."""
    if temperature > 0.0:
        key, sub = jax.random.split(key)
        token = jax.random.categorical(
            sub, logits / temperature, axis=-1).astype(jnp.int32)
    else:
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return token, key


def sample_slots(logits, temperature: float, keys):
    """Per-slot sampling with independent PRNG lanes: each slot's key
    chain splits exactly like :func:`sample`'s, so a slot's stream is
    reproducible from its seed regardless of which other slots share the
    pool or when it was admitted."""
    if temperature > 0.0:
        split = jax.vmap(jax.random.split)(keys)   # [B, 2, 2]
        keys, subs = split[:, 0], split[:, 1]
        token = jax.vmap(lambda s, lg: jax.random.categorical(
            s, lg / temperature, axis=-1))(subs, logits).astype(jnp.int32)
    else:
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return token, keys


# ------------------------------------------------- the pool's addressing
def flat(pool: jax.Array) -> jax.Array:
    """``[L, n_pages, ...]`` viewed as ``[L * n_pages, ...]``: layer
    ``l`` addresses page ``p`` at ``l * n_pages + p`` and no layer's
    pool is sliced out of the stacked one."""
    return pool.reshape((-1,) + pool.shape[2:])


def at_layer(pages, l: int, n_pages: int):
    """Page ids of one layer in the flat pool; sentinels (and anything
    out of bounds) stay out of bounds."""
    return jnp.where((pages >= 0) & (pages < n_pages),
                     pages + l * n_pages, jnp.int32(PT_SENTINEL))


def live_length(pt, pos, active, n_pages: int, page_size: int):
    """Tokens of each lane a decode kernel reads: positions <= ``pos``
    inside the mapped prefix of the lane's table row (the engine maps a
    lane's pages from column 0 without holes); 0 for an inactive lane or
    a row of sentinels."""
    max_pages = pt.shape[1]
    mapped = jnp.min(jnp.where((pt >= 0) & (pt < n_pages),
                               jnp.int32(max_pages),
                               jnp.arange(max_pages, dtype=jnp.int32)),
                     axis=1)
    return jnp.where(active, jnp.minimum(pos.astype(jnp.int32) + 1,
                                         mapped * page_size), 0)


def live_lanes(active):
    """``active`` [B] bool -> ``(lanes int32 [B], n int32 [1])``: the
    live lanes' indices in lane order, then the last of them repeated
    (lane 0 where none is live), and their count: the scalar operands
    of a kernel whose grid runs over the live lanes of a per-slot entry
    (the recurrences of ``kda_moe`` and ``ssm_hybrid``; the same for
    every layer of a step: XLA keeps one of them)."""
    B = active.shape[0]
    n = jnp.sum(active, dtype=jnp.int32)
    lanes = jnp.nonzero(active, size=B, fill_value=0)[0].astype(jnp.int32)
    return jnp.where(jnp.arange(B) < n, lanes,
                     lanes[jnp.maximum(n - 1, 0)]), n[None]


#: Tokens of cached prefix a paged prefill reads at once
#: (:func:`attend_history`; ``256 // page_size`` pages, one page where a
#: page is larger). A prefill pays for whole blocks, so a hit costs at
#: most 255 masked keys more than it is long; larger blocks bought
#: nothing on a v5e: three latent attentions at A.X-K1's widths, bucket
#: 512, hits of 192 / 512 / 1,024 / 1,536 tokens took 11.1 / 11.9 / 13.3
#: / 14.8 ms at 256 and 12.2 / 12.2 / 13.7 / 15.3 at 512 (10.4 without
#: a hit; the ``max_len``-wide view 16.6 whatever the hit), six layers
#: of the GPT block 6.7-7.3 at either (PERF.md, PR 48).
HIST_BLOCK_TOKENS = 256


def hist_blocks(pt_row: jax.Array, n_pages: int, page_size: int):
    """How a prefill reads its cached prefix: ``(T, pages)``, the tokens
    a block holds (:data:`HIST_BLOCK_TOKENS` in whole pages, at most
    the row) and ``pages(j, layer)``, block ``j``'s pages of one layer
    in the stacked pool's flat view. The page table's row is clipped
    into the pool (sentinels name page ``n_pages - 1``: their positions
    are past any ``hist_len`` and masked) and padded to whole blocks."""
    bp = max(1, min(HIST_BLOCK_TOKENS // page_size, pt_row.shape[0]))
    ptc = jnp.pad(jnp.clip(pt_row, 0, n_pages - 1),
                  (0, -pt_row.shape[0] % bp))

    def pages(j, layer):
        return lax.dynamic_slice(ptc, (j * bp,), (bp,)) + layer * n_pages

    return bp * page_size, pages


def attend_history(lg_s, v_s, hist_len, block_tokens: int, block):
    """A prefill's attention over its own rows AND the ``hist_len``
    cached tokens before them, in ONE softmax: ``lg_s`` ``[B, H, S, S]``
    float32 are the rows' scaled scores against themselves, masked
    causally, ``v_s`` ``[B, S, H, v]`` their values. The prefix is read
    in blocks of ``block_tokens``: ``block(j) -> (scores [B, H, S, T]
    float32, scaled; values [B, T, H, v])`` of tokens ``j * T ..``,
    each block's scores masked at ``hist_len``, under loops of
    ``ceil(hist_len / T)`` trips. Two passes, so that every probability
    is the one softmax's own, divided by the whole sum BEFORE it is
    rounded to the values' dtype (a running weighted sum rounds first,
    the decode kernels' difference; it parted a hit's greedy tokens
    from the whole prefill's): the first folds the blocks' scores into
    the running (max, sum) that start at the rows' own max, the second
    adds each block's ``probs . V`` to the rows' own in float32. A hit
    differs from the view that gathered ``max_len`` keys by the order of
    float32 sums. NO trip without a hit: nothing is read, and the result
    is ``softmax(lg_s) . v_s`` to the bit. Returns ``[B, S, H, v]``
    float32."""
    T = block_tokens
    n = (hist_len + T - 1) // T

    def scores(j):
        s, v = block(j)
        return jnp.where(j * T + jnp.arange(T) < hist_len, s, -1e30), v

    def stats(j, carry):
        m, l = carry
        with jax.named_scope("prefill.history"):
            s, _ = scores(j)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            l = jnp.exp(m - m_new) * l + jnp.sum(
                jnp.exp(s - m_new), axis=-1, keepdims=True)
        return m_new, l

    m_s = jnp.max(lg_s, axis=-1, keepdims=True)
    m, l_h = lax.fori_loop(0, n, stats, (m_s, jnp.zeros_like(m_s)))
    e_s = jnp.exp(lg_s - m)
    l = jnp.sum(e_s, axis=-1, keepdims=True) + l_h

    def weigh(j, acc):
        with jax.named_scope("prefill.history"):
            s, v = scores(j)
            return acc + jnp.einsum(
                "bhqk,bkhd->bqhd", (jnp.exp(s - m) / l).astype(v.dtype), v,
                preferred_element_type=jnp.float32)

    return lax.fori_loop(0, n, weigh, jnp.einsum(
        "bhqk,bkhd->bqhd", (e_s / l).astype(v_s.dtype), v_s,
        preferred_element_type=jnp.float32))


class PromptRows:
    """The rows of the ``G`` prompts ONE launch prefills, laid end to
    end: ``tokens`` is a sequence of ``[1, S_g]`` suffixes, each in its
    own bucket, ``length`` and ``hist_len`` ``[G]`` their live rows and
    cached prefixes. ``R = sum(S_g)`` rows in all; the buckets and the
    offsets are static, so a prompt's rows are a static slice
    (:meth:`split`). ``tokens`` ``[R]``, ``positions`` ``[R]``
    (``hist_len[g] + i``), ``live`` ``[R]`` (``i < length[g]``) and
    ``last`` ``[G]`` (each prompt's last live row) are what the per-row
    parts of a group prefill need; :meth:`pages` is where each row
    lands."""

    def __init__(self, tokens, length, hist_len):
        self.sizes = tuple(t.shape[-1] for t in tokens)
        self.offs = tuple(sum(self.sizes[:g]) for g in range(len(tokens)))
        self.G, self.R = len(tokens), sum(self.sizes)
        self.tokens = jnp.concatenate([t.reshape(-1) for t in tokens])
        self.positions = jnp.concatenate(
            [hist_len[g] + jnp.arange(S) for g, S in enumerate(self.sizes)])
        self.live = jnp.concatenate(
            [jnp.arange(S) < length[g] for g, S in enumerate(self.sizes)])
        self.last = jnp.asarray(self.offs, jnp.int32) + length - 1

    def split(self, a, axis: int = 0):
        """``a``'s rows (along ``axis``) a prompt at a time."""
        return [lax.slice_in_dim(a, o, o + S, axis=axis)
                for o, S in zip(self.offs, self.sizes)]

    def pages(self, pt_row, page_size: int):
        """``(page [R], offset [R])``: the page of its prompt's table
        row (``pt_row`` ``[G, max_pages]``) and the place in it where
        each row's cache entry lands; the sentinel for a row that pads
        its bucket or lies past the table: its write is dropped."""
        max_pages = pt_row.shape[1]
        vp = self.positions // page_size
        own = jnp.concatenate([
            pt_row[g][jnp.clip(v, 0, max_pages - 1)]
            for g, v in enumerate(self.split(vp))])
        return jnp.where(self.live & (vp < max_pages), own,
                         jnp.int32(PT_SENTINEL)), self.positions % page_size


# ------------------------------------------------------ the chunk program
def decode_chunk_slots_paged(params, cache, token: jax.Array,
                             rngs: jax.Array, active: jax.Array,
                             pt: jax.Array, *, step, counters: int, cfg,
                             k: int, page_size: int,
                             temperature: float = 0.0,
                             eos_token: int = -1, kv_dtype: str = "fp",
                             attn_kernel: str = "gather", **step_knobs):
    """k fused decode steps over the slot pool in ONE program, whatever
    the model: a ``lax.scan`` of a description's ``step``
    (``_slot_decode_step_paged``; ``step_knobs`` are its own, a mesh
    axis), decoding only slots where ``active`` is set, with the page
    table held constant through the chunk (the engine maps pages
    covering ``pos + k`` before dispatching — a slot that cannot be
    covered is parked out of ``active`` instead).

    ``token`` ``[B_slots]`` is each slot's last emitted token, ``rngs``
    ``[B_slots, 2]`` its PRNG lane (:func:`sample_slots`), ``active``
    ``[B_slots]`` the chunk-static admission mask (admission happens at
    chunk boundaries, so the mask never changes inside a dispatch). The
    cache (pages, and whatever a sequence keeps in its slot) is the
    scan's carry, donated: a step updates it in place. EOS lanes
    mask-and-carry: once a lane samples ``eos_token`` (or was fed it)
    it keeps emitting it, and the ENGINE frees the slot at the chunk
    boundary, which is what turns mask-and-carry into slot reuse.
    Returns ``(tokens [B_slots, k], cache', done [B_slots], rngs')``
    and, where the description counts (``counters`` of
    ``STEP_COUNTERS``, a third value of its step), ``counts int32
    [counters]`` summed over the k steps, at no launch of their own;
    rows of inactive slots are garbage. ``kv_dtype`` / ``attn_kernel``
    are STATIC knobs baked into the compiled program, never retrace
    triggers."""
    B = token.shape[0]
    eos = jnp.asarray(eos_token, jnp.int32)
    done0 = (active & (token == eos)) if eos_token >= 0 \
        else jnp.zeros((B,), jnp.bool_)

    def body(carry, _):
        cache, tok, done, keys, *counts = carry
        logits, cache, *c = step(
            params, cache, tok, active, pt, cfg, page_size, kv_dtype,
            attn_kernel, **step_knobs)
        nxt, keys = sample_slots(logits, temperature, keys)
        if eos_token >= 0:
            nxt = jnp.where(done, eos, nxt)
            done = done | (active & (nxt == eos))
        return (cache, nxt, done, keys,
                *(n + m for n, m in zip(counts, c))), nxt

    # a description without counters carries none: four outputs
    counts0 = (jnp.zeros((counters,), jnp.int32),) if counters else ()
    (cache, _, done, rngs, *counts), toks = lax.scan(
        body, (cache, token, done0, rngs, *counts0), None, length=k)
    return (jnp.moveaxis(toks, 0, 1), cache, done, rngs, *counts)


# ----------------------------------- what follows from a description
def bind(fn, model):
    """``fn`` of this frame (one that takes ``model=``) as the
    description ``model``'s own: a module says ``init_paged_cache =
    bind(serving.init_paged_cache, sys.modules[__name__])`` and is
    called, and reads to ``inspect.signature``, as before. A cached
    factory keeps its ``cache_info`` / ``cache_clear``, which are the
    frame's: every description's wrappers of that factory."""
    bound = functools.update_wrapper(functools.partial(fn, model=model), fn)
    sig = inspect.signature(fn)
    bound.__signature__ = sig.replace(parameters=[
        p for p in sig.parameters.values() if p.name != "model"])
    return bound


def check_kv_dtype(model, kv_dtype: str):
    """Refuse a pool storage type the description has not, with its
    reason for having no quantised layout where it states one."""
    if kv_dtype not in model.KV_DTYPES:
        reason = model.UNSUPPORTED.get("int8")
        raise ValueError(
            f"kv_dtype must be one of {model.KV_DTYPES}, got {kv_dtype!r}"
            + (f": {reason}" if reason else ""))


def kv_bytes_per_page(cfg, page_size: int, kv_dtype: str = "fp", *,
                      model) -> int:
    """HBM bytes ONE physical page costs across all layers — the unit
    the engine's page budget is denominated in
    (:meth:`CacheSpec.bytes_per_page` of the description's spec)."""
    return model.cache_spec(cfg, kv_dtype).bytes_per_page(page_size)


def init_paged_cache(cfg, slots: int, n_pages: int, page_size: int,
                     kv_dtype: str = "fp", tp: int = 1, *, model):
    """The description's pool, zeroed (:func:`init_paged_pool` of its
    spec: pages, per-slot entries and ``pos``), placed on its mesh
    where it has one."""
    mesh = model.check_tp(cfg, tp)
    cache = init_paged_pool(model.cache_spec(cfg, kv_dtype), slots,
                            n_pages, page_size)
    return cache if mesh is None else model.shard_cache(cache, mesh)


def check_tp(cfg, tp: int, *, model):
    """The (cfg, tp) validation of a model that lists ``"tp"`` under
    ``UNSUPPORTED``: no mesh, and its reason past one device."""
    if int(tp) > 1:
        raise ValueError(f"tp={tp}: " + model.UNSUPPORTED["tp"])
    return None


def shard_params(params, cfg, tp: int, *, model):
    """The weights' placement of a model without a mesh: as they are."""
    model.check_tp(cfg, tp)
    return params


# rtlint: program-budget: 1
def jit_program(model, fn, name: str, mesh, n_out: int, *,
                cache_out: int = 1, check_vma: bool = True, **knobs):
    """``fn(params, cache, ...)`` of the description ``model`` jitted
    with its static ``knobs`` bound, named ``name`` for a trace
    (:func:`program`) and the pool donated; with a mesh, the same
    function under the description's ``shard_program`` (``n_out``
    values, the cache at ``cache_out``), which is all a mesh adds. One
    jit wrapper a call: the calling factory's cache and budget count
    the programs."""
    if mesh is not None:
        fn, knobs = model.shard_program(
            functools.partial(fn, **knobs, tp_axis="tp"), mesh, n_out,
            cache_out, check_vma), {}
    return jax.jit(program(fn, name, **knobs), donate_argnums=(1,))


def _checked_mesh(model, cfg, kv_dtype, tp, attn_kernel=None):
    """The factories' validation of their knobs against the
    description; returns its mesh (``None`` without one)."""
    check_kv_dtype(model, kv_dtype)
    if attn_kernel is not None and attn_kernel not in model.ATTN_KERNELS:
        raise ValueError(
            f"attn_kernel must be one of {model.ATTN_KERNELS}, got "
            f"{attn_kernel!r}")
    return model.check_tp(cfg, tp)


# rtlint: program-budget: len(prompt_buckets) + len(prompt_buckets) * len(prompt_buckets)
@knob_cache
def jit_prefill_into_slot_paged(cfg, page_size: int,
                                temperature: float = 0.0,
                                kv_dtype: str = "fp", tp: int = 1, *,
                                model):
    """Jitted ``model.prefill_into_slot_paged`` and, called with a
    TUPLE of prompts (``tokens`` ``([1, bucket_g], ...)``, a ``length``
    each), the group's ``model.prefill_group_into_slots_paged`` under
    the same name: one compiled program per SUFFIX bucket, and one per
    group's buckets (the engine hands a pair over widest first: a pair
    of buckets is one program whichever came first), per (model, cfg,
    page_size, temperature, kv_dtype, tp) key; one prompt, with scalar
    operands, is the program it always was — prefix-hit depth (``hist_len``),
    page-table contents, and COW source are all traced, so
    shared-prefix admission never retraces. ``kv_dtype`` is an engine-level static baked into the
    same program set (it changes the pool layout, not the program
    COUNT). Cached on the static knobs so every engine for the same
    knobs shares one wrapper (and its trace cache). The pool cache is
    donated: the engine holds the only reference and immediately
    rebinds the returned cache, so on TPU the update is in-place
    instead of a full-pool copy (CPU ignores donation). With a mesh
    (``tp > 1`` of a description that has one) the same inner function
    runs under its ``shard_program``."""
    mesh = _checked_mesh(model, cfg, kv_dtype, tp)

    def prefill(params, cache, tokens, *rest, **knobs):
        fn = model.prefill_group_into_slots_paged \
            if isinstance(tokens, (tuple, list)) \
            else model.prefill_into_slot_paged
        return fn(params, cache, tokens, *rest, **knobs)

    return jit_program(
        model, prefill, "prefill_into_slot_paged", mesh, 3, cfg=cfg,
        page_size=page_size, temperature=temperature, kv_dtype=kv_dtype)


# rtlint: program-budget: 1
@knob_cache
def jit_decode_chunk_slots_paged(cfg, k: int, page_size: int,
                                 temperature: float = 0.0,
                                 eos_token: int = -1,
                                 kv_dtype: str = "fp",
                                 attn_kernel: str = "gather",
                                 tp: int = 1, *, model):
    """Jitted ``model.decode_chunk_slots_paged`` (the frame's chunk
    program around the description's step): ONE program per (model,
    pool shape, k, page_size, tp) — the page table is data, and the
    ``kv_dtype``/``attn_kernel`` knobs are engine-level statics that
    select WHICH one program is built, never additional ones. Pool
    donated."""
    mesh = _checked_mesh(model, cfg, kv_dtype, tp, attn_kernel)
    return jit_program(
        model, model.decode_chunk_slots_paged, "decode_chunk_slots_paged",
        mesh, 4 + bool(model.STEP_COUNTERS),
        # pallas_call's out_shape carries no vma annotation, which
        # strict shard_map rejects (and the interpreter's own slicing
        # trips the same check on the CPU): a program that holds a
        # kernel runs unchecked, like the flash kernel's.
        check_vma=not model.decode_attention_fused(cfg, page_size,
                                                   attn_kernel),
        cfg=cfg, k=k, page_size=page_size, temperature=temperature,
        eos_token=eos_token, kv_dtype=kv_dtype, attn_kernel=attn_kernel)

"""The comparison that decides ``correct`` against the plain reference,
run inside the process that holds the chip, outside the measured window.

Serving is held to the reference twice. ``serve_check`` compares LOGITS
of the served arithmetic (the one tolerance that tells a cruder number
format from bfloat16) and, in the same pass of the reference, takes
the answers that came out of the engine through the handle (chunk
program, page tables, prefix cache, eviction) and asks of EVERY
distinct one whether each token was a best token within a margin
(``served_verdict``): a page of someone else's keys, or a step that is
no longer the scanned one, yields tokens the reference ranks far below
its best. Two answers to one request may differ where the reference
itself cannot tell their tokens apart (a whole prefill and a
prefix-cache hit are two arithmetic paths in bfloat16); each still has
to pass. A control from the same pass, the first answer against another
prompt's logits, has to FAIL, so the check cannot go blind.

What the reference itself cannot decide is left out, by the harness and
on the reference's word alone. Where a model makes a discrete choice
(the experts it holds among a layer's top k), a bfloat16 program and
the float32 reference choose differently wherever the selection's edge
is a near-tie, and the two logit vectors then differ by a whole
expert's part. An architecture module MAY therefore provide
``decidable(cfg, conf)`` (the contract is in ``architectures/gpt2.py``):
a function of the reference's parameters and the token rows that says,
for every row and position, whether every such choice clears its edge
by the configuration's ``correct.tie_eps``. The logit vectors and the
served tokens at positions it calls undecidable are not compared; how
many were compared and how many left out is in every entry
(``compared``, ``left_out``); and a run is not ``correct`` unless a
vector after prefill and one after decode were compared, at least the
share ``correct.min_compared`` (default 1: all) of the vectors and of
each answer's tokens were, and the control still fails. The program has
no say in any of this: ``served_logits`` returns what it computed.

Nothing here knows a model: the served arithmetic and the reference are
the architecture module's (``perf_harness.load_architecture``).
"""
from __future__ import annotations

import math
import time


def needed(n: int, share: float) -> int:
    """How many of ``n`` have to be compared: the share, rounded up,
    and never none."""
    return max(1, math.ceil(share * n - 1e-9))


def serve_check(arch, engine, cfg, conf: dict, seed: int, n_prompt: int,
                n_steps: int, served=None) -> dict:
    """``correct.rows`` (default 2) seeded sequences through the served
    arithmetic (``arch.served_logits``) against the reference's full
    forward pass: the logits right after prefill and after ``n_steps``
    cached decode steps, teacher-forced on the sequence's own tokens.

    ``served``: optionally (prompt, answers) of a request the engine
    answered at temperature 0, ``answers`` being the distinct token
    sequences it gave. Each rides in the same pass of the reference as
    a row of its own (the reference is causal, so padding the shorter
    rows on the right changes nothing), and ``served_verdicts`` judges
    them, with the first seeded sequence's logits as the control: the
    result's ``"served"``.

    Where the module has ``decidable``, the same rows go through it
    once, and what it calls undecidable is left out of both comparisons
    (the module's docstring).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.monotonic()
    ck = conf["correct"]
    vocab = arch.vocab(conf)[0]
    total = n_prompt + n_steps
    B = int(ck.get("rows", 2))
    share = float(ck.get("min_compared", 1.0))
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 77])
    seqs = rng.integers(0, vocab, (B, total + 1)).astype(np.int32)
    got = arch.served_logits(engine, cfg, seqs, n_prompt, n_steps)
    rows = [seqs[b, :total] for b in range(B)]
    if served is not None:
        s_prompt = np.asarray(served[0], np.int32)
        answers = [np.asarray(a, np.int32) for a in served[1]]
        rows += [np.concatenate([s_prompt, a[:-1]]) for a in answers]
    width = max(len(r) for r in rows)
    ref_in = np.zeros((len(rows), width), np.int32)
    for b, r in enumerate(rows):
        ref_in[b, :len(r)] = r
    from_program, forward, _loss = arch.reference(cfg)
    weights = from_program(engine.params)
    ref_all = jax.jit(forward)(weights, jnp.asarray(ref_in))
    # [row, position] -> can the reference decide its own choices there
    dec = np.ones(ref_in.shape, bool)
    if hasattr(arch, "decidable"):
        dec = np.asarray(jax.jit(arch.decidable(cfg, conf))(
            weights, jnp.asarray(ref_in)), bool)
    out = {"seconds": None, "checks": []}
    tol = ck["logits_rel_tol"]
    if served is not None:
        first = len(s_prompt) - 1
        n = len(answers[0])
        out["served"] = served_verdicts(
            [np.asarray(ref_all[B + j, first:first + len(a)], np.float32)
             for j, a in enumerate(answers)], answers, tol,
            control=np.asarray(ref_all[0, max(0, total - n):total],
                               np.float32),
            keep=[dec[B + j, first:first + len(a)]
                  for j, a in enumerate(answers)], min_compared=share)
    ok = True
    for i, pos, name in ((0, n_prompt - 1, "after_prefill"),
                         (n_steps, total - 1, "after_decode")):
        keep = dec[:B, pos]
        entry = {"where": name, "max_abs_err": None, "max_abs_ref": None,
                 "rel": None, "tol": tol, "compared": int(keep.sum()),
                 "left_out": int(B - keep.sum())}
        if keep.any():
            want = np.asarray(ref_all[:B, pos], np.float32)[keep][:, :vocab]
            err = float(np.abs(got[i][keep][:, :vocab] - want).max())
            scale = float(np.abs(want).max())
            entry.update(max_abs_err=err, max_abs_ref=scale,
                         rel=err / scale)
        ok = ok and keep.any() and entry["rel"] <= tol
        out["checks"].append(entry)
    compared = sum(c["compared"] for c in out["checks"])
    out["vectors"] = {"compared": compared,
                      "left_out": 2 * B - compared,
                      "needed": needed(2 * B, share)}
    out["ok"] = bool(ok and compared >= out["vectors"]["needed"])
    out["seconds"] = time.monotonic() - t0
    return out


def token_gaps(ref_logits, tokens):
    """For greedy tokens: how far below the reference's best logit the
    reference ranks each token that was served (0 where they agree).
    ``ref_logits`` [N, rows], ``tokens`` [N]; plain numpy."""
    import numpy as np

    ref = np.asarray(ref_logits, np.float32)
    tok = np.asarray(tokens).astype(np.int64)
    return ref.max(axis=-1) - ref[np.arange(len(tok)), tok]


def served_verdict(ref_logits, tokens, tol: float, keep=None,
                   min_compared: float = 1.0) -> dict:
    """Tokens the engine served at temperature 0 against the logits the
    reference computes at their positions (``ref_logits`` [N, rows]):
    served token i is the argmax of logits that are off by some e each,
    so the reference ranks it at most 2e below its own best. The margin
    is twice the logits' tolerance times the largest reference logit. A
    token picked from other logits (a wrong page, a stale cache, a wrong
    position) lies several standard deviations of the logits below.
    ``keep`` [N]: the tokens at positions the reference can decide
    (default: all); the others are left out and counted, and fewer than
    the share ``min_compared`` compared is not ok."""
    import numpy as np

    ref = np.asarray(ref_logits, np.float32)
    tok = np.asarray(tokens)
    n = len(tok)
    if keep is not None:
        keep = np.asarray(keep, bool)
        ref, tok = ref[keep], tok[keep]
    counts = {"tokens": int(n), "compared": int(len(tok)),
              "left_out": int(n - len(tok))}
    if not len(tok):
        return {"ok": False, "max_gap": None, "margin": None,
                "max_abs_ref": None, "agree": 0, **counts,
                "logit_std": None}
    gaps = token_gaps(ref, tok)
    scale = float(np.abs(ref).max())
    margin = 2.0 * tol * scale
    return {"ok": bool(gaps.max() <= margin
                       and len(tok) >= needed(n, min_compared)),
            "max_gap": float(gaps.max()),
            "margin": margin, "max_abs_ref": scale,
            "agree": int((gaps == 0).sum()), **counts,
            "logit_std": float(ref.std())}


def served_verdicts(ref_logits: list, answers: list, tol: float,
                    control, keep=None, min_compared: float = 1.0
                    ) -> dict:
    """Every distinct answer to the one check request against the
    reference's logits along its own tokens (``ref_logits[j]`` [N, rows]
    for ``answers[j]`` [N]): each has to pass ``served_verdict``. The
    entry is the first answer's verdict, with ``ok`` and ``max_gap``
    over all of them. ``control`` [N, rows] is the reference's logits
    for ANOTHER prompt: judged against them the first answer has to
    fail, or the verdict tells nothing and the run is not correct.
    Where two answers differ, ``parted`` says where the second first
    left the first and how far apart the reference ranks the two tokens
    there (both rows hold the same tokens up to that position): a
    near-tie is closer than the margin. ``keep[j]`` [N]: which of
    answer j's tokens stand at positions the reference can decide; the
    control is judged on the first answer's own (the verdict has to
    fail on the very tokens it looks at)."""
    import numpy as np

    keep = keep if keep is not None else [None] * len(answers)
    each = [served_verdict(r, a, tol, k, min_compared)
            for r, a, k in zip(ref_logits, answers, keep)]
    n = len(control)
    # the control is held to the gaps alone: it must not "fail" for
    # having too few tokens to look at
    ctrl = served_verdict(control, answers[0][:n], tol,
                          None if keep[0] is None else keep[0][:n],
                          min_compared=0.0)
    gaps = [v["max_gap"] for v in each if v["max_gap"] is not None]
    out = dict(each[0], distinct=len(answers),
               max_gap=max(gaps) if gaps else None,
               control_max_gap=ctrl["max_gap"],
               control_margin=ctrl["margin"])
    if len(answers) > 1:
        out["each"] = each
        at = int(np.flatnonzero(answers[0] != answers[1])[0])
        row = np.asarray(ref_logits[0][at], np.float32)
        out["parted"] = {"at": at, "ref_gap": float(abs(
            row[answers[0][at]] - row[answers[1][at]]))}
    out["ok"] = bool(all(v["ok"] for v in each)
                     and ctrl["compared"] > 0 and not ctrl["ok"])
    out["min_compared"] = min_compared
    return out


def train_check(arch, params, batch_rows, cfg, program_loss) -> dict:
    """The program's loss on a few rows of the batch against the
    reference's loss on the same rows, both on the sharded weights."""
    import jax

    t0 = time.monotonic()
    from_program, _forward, loss = arch.reference(cfg)
    sys_loss = float(jax.jit(program_loss)(params, batch_rows))
    ref_loss = float(jax.jit(loss)(from_program(params), batch_rows))
    return {"program_loss": sys_loss, "reference_loss": ref_loss,
            "abs_err": abs(sys_loss - ref_loss),
            "seconds": time.monotonic() - t0}

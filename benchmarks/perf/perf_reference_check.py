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

Nothing here knows a model: the served arithmetic and the reference are
the architecture module's (``perf_harness.load_architecture``).
"""
from __future__ import annotations

import time


def serve_check(arch, engine, cfg, conf: dict, seed: int, n_prompt: int,
                n_steps: int, served=None) -> dict:
    """Two seeded sequences through the served arithmetic
    (``arch.served_logits``) against the reference's full forward pass:
    the logits right after prefill and after ``n_steps`` cached decode
    steps, teacher-forced on the sequence's own tokens.

    ``served``: optionally (prompt, answers) of a request the engine
    answered at temperature 0, ``answers`` being the distinct token
    sequences it gave. Each rides in the same pass of the reference as
    a row of its own (the reference is causal, so padding the shorter
    rows on the right changes nothing), and ``served_verdicts`` judges
    them, with the first seeded sequence's logits as the control: the
    result's ``"served"``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.monotonic()
    vocab = arch.vocab(conf)[0]
    total = n_prompt + n_steps
    B = 2
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
    ref_all = jax.jit(forward)(from_program(engine.params),
                               jnp.asarray(ref_in))
    out = {"seconds": None, "checks": []}
    tol = conf["correct"]["logits_rel_tol"]
    if served is not None:
        first = len(s_prompt) - 1
        n = len(answers[0])
        out["served"] = served_verdicts(
            [np.asarray(ref_all[B + j, first:first + len(a)], np.float32)
             for j, a in enumerate(answers)], answers, tol,
            control=np.asarray(ref_all[0, max(0, total - n):total],
                               np.float32))
    ok = True
    for i, pos, name in ((0, n_prompt - 1, "after_prefill"),
                         (n_steps, total - 1, "after_decode")):
        want = np.asarray(ref_all[:B, pos], np.float32)[:, :vocab]
        err = float(np.abs(got[i][:, :vocab] - want).max())
        scale = float(np.abs(want).max())
        rel = err / scale
        ok = ok and rel <= tol
        out["checks"].append({"where": name, "max_abs_err": err,
                              "max_abs_ref": scale, "rel": rel,
                              "tol": tol})
    out["ok"] = bool(ok)
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


def served_verdict(ref_logits, tokens, tol: float) -> dict:
    """Tokens the engine served at temperature 0 against the logits the
    reference computes at their positions (``ref_logits`` [N, rows]):
    served token i is the argmax of logits that are off by some e each,
    so the reference ranks it at most 2e below its own best. The margin
    is twice the logits' tolerance times the largest reference logit. A
    token picked from other logits (a wrong page, a stale cache, a wrong
    position) lies several standard deviations of the logits below."""
    import numpy as np

    ref = np.asarray(ref_logits, np.float32)
    gaps = token_gaps(ref, tokens)
    scale = float(np.abs(ref).max())
    margin = 2.0 * tol * scale
    return {"ok": bool(gaps.max() <= margin), "max_gap": float(gaps.max()),
            "margin": margin, "max_abs_ref": scale,
            "agree": int((gaps == 0).sum()), "tokens": int(len(gaps)),
            "logit_std": float(ref.std())}


def served_verdicts(ref_logits: list, answers: list, tol: float,
                    control) -> dict:
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
    near-tie is closer than the margin."""
    import numpy as np

    each = [served_verdict(r, a, tol) for r, a in zip(ref_logits, answers)]
    ctrl = served_verdict(control, answers[0][:len(control)], tol)
    out = dict(each[0], distinct=len(answers),
               max_gap=max(v["max_gap"] for v in each),
               control_max_gap=ctrl["max_gap"],
               control_margin=ctrl["margin"])
    if len(answers) > 1:
        out["each"] = each
        at = int(np.flatnonzero(answers[0] != answers[1])[0])
        row = np.asarray(ref_logits[0][at], np.float32)
        out["parted"] = {"at": at, "ref_gap": float(abs(
            row[answers[0][at]] - row[answers[1][at]]))}
    out["ok"] = bool(all(v["ok"] for v in each) and not ctrl["ok"])
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

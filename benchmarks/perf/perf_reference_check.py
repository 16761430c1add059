"""The comparison that decides ``correct`` against the plain reference,
run inside the process that holds the chip, outside the measured window.

Serving is held to the reference twice. ``serve_check`` compares LOGITS
of the served arithmetic (the one tolerance that tells a cruder number
format from bfloat16) and, in the same pass of the reference, takes
tokens that came out of the ``DecodeEngine`` through the handle (chunk
program, page tables, prefix cache, eviction) and asks whether each was
a best token within a margin (``served_verdict``): a page of someone
else's keys, or a step that is no longer the scanned one, yields tokens
the reference ranks far below its best.
"""
from __future__ import annotations

import time


def serve_check(engine, cfg, conf: dict, seed: int, n_prompt: int,
                n_steps: int, served=None) -> dict:
    """Two seeded sequences through the SERVED arithmetic — the paged
    prefill program, then single decode steps through the paged cache
    with the engine's attention kernel, on a small pool of its own —
    against the reference's full forward pass: the logits right after
    prefill and after ``n_steps`` cached decode steps.

    The paged prefill returns a token, not logits, so it is given the
    prompt less its last token, and the first decode step (fed that
    last token, reading the keys and values prefill wrote) yields the
    logits "after prefill"; the tokens fed afterwards are the
    sequence's own (teacher forcing), so both sides see the same
    inputs. ``_slot_decode_step_paged`` is the program's step function
    that the chunk program scans; it is read here because no public
    entry returns logits.

    ``served``: optionally (prompt, tokens) of a request the engine
    answered at temperature 0. It rides in the same pass of the
    reference as a third row (the reference is causal, so padding the
    shorter rows on the right changes nothing), and ``served_verdict``
    judges it: the result's ``"served"``.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_gpt2
    from ray_tpu.models import gpt_decode as gd

    t0 = time.monotonic()
    ps = engine.page_size
    vocab = conf["model"]["vocab_size"]
    total = n_prompt + n_steps
    max_pages = -(-(total + 1) // ps)
    B = 2
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 77])
    seqs = rng.integers(0, vocab, (B, total + 1)).astype(np.int32)
    bucket = next(b for b in engine.prompt_buckets if b >= n_prompt - 1)
    cache = gd.init_paged_cache(cfg, B, B * max_pages, ps,
                                engine.kv_dtype)
    pt = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    prefill = gd.jit_prefill_into_slot_paged(cfg, ps, 0.0,
                                             engine.kv_dtype)
    step = jax.jit(functools.partial(
        gd._slot_decode_step_paged, cfg=cfg, page_size=ps,
        kv_dtype=engine.kv_dtype, attn_kernel=engine.attn_kernel))
    params = engine.params
    for b in range(B):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n_prompt - 1] = seqs[b, :n_prompt - 1]
        _tok, cache, _key = prefill(
            params, cache, padded, np.int32(n_prompt - 1), np.int32(0),
            pt[b], np.int32(gd.PT_SENTINEL), np.int32(b),
            jax.random.PRNGKey(0))
    active = np.ones((B,), bool)
    got = {}
    for i in range(n_steps + 1):
        pos = n_prompt - 1 + i
        logits, cache = step(params, cache, jnp.asarray(seqs[:, pos]),
                             active, jnp.asarray(pt))
        if i in (0, n_steps):
            got[i] = np.asarray(logits, np.float32)[:, :vocab]
    rows = [seqs[b, :total] for b in range(B)]
    if served is not None:
        s_prompt = np.asarray(served[0], np.int32)
        s_tokens = np.asarray(served[1], np.int32)
        rows.append(np.concatenate([s_prompt, s_tokens[:-1]]))
    width = max(len(r) for r in rows)
    ref_in = np.zeros((len(rows), width), np.int32)
    for b, r in enumerate(rows):
        ref_in[b, :len(r)] = r
    at = [n_prompt - 1, n_prompt - 1 + n_steps]
    ref_all = jax.jit(functools.partial(reference_gpt2.forward,
                                        n_head=cfg.n_head))(
        reference_gpt2.from_program(params), jnp.asarray(ref_in))
    ref = {i: np.asarray(ref_all[:B, pos], np.float32)[:, :vocab]
           for i, pos in zip((0, n_steps), at)}
    out = {"seconds": None, "checks": []}
    tol = conf["correct"]["logits_rel_tol"]
    if served is not None:
        first = len(s_prompt) - 1
        out["served"] = served_verdict(
            np.asarray(ref_all[B, first:first + len(s_tokens)],
                       np.float32), s_tokens, tol)
    ok = True
    for i, name in ((0, "after_prefill"), (n_steps, "after_decode")):
        want = ref[i]
        err = float(np.abs(got[i] - want).max())
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


def train_check(params, batch_rows, cfg, mesh, n_head: int,
                program_loss_fn) -> dict:
    """The program's loss on a few rows of the batch against the
    reference's loss on the same rows, both on the sharded weights."""
    import functools

    import jax

    import reference_gpt2

    t0 = time.monotonic()
    sys_loss = float(jax.jit(
        lambda p, t: program_loss_fn(p, {"tokens": t}, cfg, mesh)[0])(
            params, batch_rows))
    ref_loss = float(jax.jit(functools.partial(
        reference_gpt2.loss, n_head=n_head))(
            reference_gpt2.from_program(params), batch_rows))
    return {"program_loss": sys_loss, "reference_loss": ref_loss,
            "abs_err": abs(sys_loss - ref_loss),
            "seconds": time.monotonic() - t0}

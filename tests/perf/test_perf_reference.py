"""The plain reference against the program's model at ``nano``: it
shares no code with ``ray_tpu/models`` and agrees with it."""
import dataclasses
import json
import os

import numpy as np
import pytest

import perf_testlib

import kernel_costs
import reference_gpt2


@pytest.fixture(scope="module")
def nano():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg = dataclasses.replace(gpt.CONFIGS["nano"], dtype=jnp.float32,
                              remat="none")
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 33)).astype(np.int32)
    return gpt, cfg, params, tokens


def test_reference_shares_no_code_with_the_program():
    with open(os.path.join(perf_testlib.PERF, "reference_gpt2.py")) as f:
        src = f.read()
    assert "import ray_tpu" not in src and "from ray_tpu" not in src


def test_forward_agrees_with_the_program_in_float32(nano):
    import jax

    gpt, cfg, params, tokens = nano
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gpt.forward(params, tokens[:, :-1], cfg))
    got = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), tokens[:, :-1], cfg.n_head))
    # both float32: only the order of additions differs
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def _int8(x):
    """Weights as int8 with one scale per output channel, back in
    float32: what an int8 weight path would multiply with."""
    x = np.asarray(x, np.float32)
    if x.ndim < 2:
        return x
    s = np.abs(x).max(axis=-2, keepdims=True) / 127.0
    return (np.round(x / np.where(s == 0, 1, s)) * s).astype(np.float32)


def _fp8(x):
    """Weights rounded to float8 e4m3 (4 significand bits)."""
    import jax.numpy as jnp

    x = np.asarray(x, np.float32)
    if x.ndim < 2:
        return x
    return np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32))


def _serve_tol():
    with open(os.path.join(perf_testlib.PERF, "configs",
                           "cerebras-gpt-1.3b-serve.json")) as f:
        return json.load(f)["correct"]["logits_rel_tol"]


def test_bfloat16_program_is_inside_the_logits_tolerance(nano):
    import jax.numpy as jnp

    gpt, cfg, params, tokens = nano
    ref = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), tokens[:, :-1], cfg.n_head))
    bf = np.asarray(gpt.forward(
        params, tokens[:, :-1],
        dataclasses.replace(cfg, dtype=jnp.bfloat16)))
    assert np.abs(bf - ref).max() / np.abs(ref).max() <= _serve_tol()


@pytest.mark.parametrize("cruder", [_int8, _fp8], ids=["int8", "fp8"])
def test_cruder_weights_are_outside_the_logits_tolerance(nano, cruder):
    """The tolerance the serving configuration states refuses int8 and
    fp8 weights even when everything else is float32."""
    import jax

    gpt, cfg, params, tokens = nano
    ref = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), tokens[:, :-1], cfg.n_head))
    bad = np.asarray(gpt.forward(jax.tree_util.tree_map(cruder, params),
                                 tokens[:, :-1], cfg))
    assert np.abs(bad - ref).max() / np.abs(ref).max() > _serve_tol()


def test_served_tokens_are_ranked_by_the_reference(nano):
    """``token_gaps``: 0 where the served token is the reference's best,
    its distance below the best elsewhere; tokens of another sequence
    (what a wrong page yields) lie far outside the margin."""
    import perf_reference_check as C

    gpt, cfg, params, tokens = nano
    ref = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), tokens[:, :-1], cfg.n_head))
    best = ref[0].argmax(-1)
    assert (C.token_gaps(ref[0], best) == 0).all()
    second = np.argsort(ref[0], -1)[:, -2]
    gaps = C.token_gaps(ref[0], second)
    assert (gaps > 0).all() and gaps == pytest.approx(
        np.sort(ref[0], -1)[:, -1] - np.sort(ref[0], -1)[:, -2])
    margin = 2 * _serve_tol() * np.abs(ref[0]).max()
    other = ref[1].argmax(-1)            # best tokens of another prompt
    assert C.token_gaps(ref[0], other).max() > 3 * margin
    # the verdict: the program's own greedy tokens pass, another
    # sequence's fail
    prompt, n = tokens[0, :20], 8
    seq = [int(t) for t in prompt]
    for _ in range(n):
        with __import__("jax").default_matmul_precision("highest"):
            lg = np.asarray(gpt.forward(params, np.asarray([seq]), cfg))
        seq.append(int(lg[0, -1].argmax()))
    rows = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), np.asarray([seq[:-1]]),
        cfg.n_head))[0, 19:]
    good = C.served_verdict(rows, seq[20:], _serve_tol())
    assert good["ok"] and good["agree"] == n and good["tokens"] == n
    bad = C.served_verdict(rows, tokens[1, :n], _serve_tol())
    assert not bad["ok"] and bad["max_gap"] > 3 * bad["margin"]


def test_loss_agrees_with_the_program(nano):
    gpt, cfg, params, tokens = nano
    want = float(gpt.loss_fn(params, {"tokens": tokens}, cfg)[0])
    got = float(reference_gpt2.loss(reference_gpt2.from_program(params),
                                    tokens, cfg.n_head))
    assert got == pytest.approx(want, abs=1e-4)


def test_parameter_count_is_the_programs_and_costs_follow_shapes(nano):
    gpt, cfg, _params, _tokens = nano
    with open(os.path.join(perf_testlib.PERF, "configs",
                           "cerebras-gpt-1.3b-serve.json")) as f:
        model = json.load(f)["model"]
    assert kernel_costs.n_params(model) == gpt.CONFIGS["1b"].num_params()
    assert 1.2e9 < kernel_costs.n_params(model) < 1.45e9
    b0 = kernel_costs.decode_step_bytes(model, 4, 2, 0)
    b1 = kernel_costs.decode_step_bytes(model, 4, 2, 1000)
    assert b0 == 4 * kernel_costs.n_params(model)
    assert b1 - b0 == 2 * 24 * 2048 * 2 * 1000
    assert kernel_costs.train_flops_per_token(model, 2048) == \
        6 * kernel_costs.n_params(model) + 12 * 24 * 2048 * 2048

"""The fourth block through the SAME ``DecodeEngine``: the
shortcut-connected expert decoder of ``ray_tpu/models/scmoe.py`` (two
latent attentions and two dense FFNs a layer beside an expert layer
whose softmax router has a selection bias and identity experts). The
engine takes the programs, the cache's shape (two latent entries a
layer in ONE pool) and what the model does not get from the config
object's description; page ids, the page pool, the prefix cache, COW
and the driver loop are every model's. The plain reference is the
benchmark's (``benchmarks/perf/architectures/longcat_flash_reference
.py``: float32, no code shared with ``ray_tpu``), and the comparison is
on LOGITS at float32, where a mechanism left out cannot hide behind
rounding."""
import dataclasses
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import mla_moe, moe, scmoe, serving
from ray_tpu.serve.engine import DecodeEngine

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "perf")
#: float32 program against float32 reference: the order of additions
TOL = 2e-5
N_PROMPT, N_STEPS = 24, 16
#: what the reference can leave out, one at a time (its ``MECHANISMS``)
MECHANISMS = ("zero_experts", "select_bias", "route_scale", "shortcut",
              "second_attention", "q_scale", "kv_scale", "rotary",
              "absent_experts_left_out")


@pytest.fixture(scope="module")
def arch():
    sys.path.insert(0, PERF)
    try:
        import perf_harness as H

        yield H.load_architecture({"architecture": "longcat_flash"})
    finally:
        sys.path.remove(PERF)


@pytest.fixture(scope="module")
def uncut():
    """The whole model at float32: all 16 routed experts."""
    cfg = dataclasses.replace(scmoe.CONFIGS["nano"], dtype=jnp.float32,
                              param_dtype=jnp.float32)
    return cfg, scmoe.init_params(jax.random.PRNGKey(0), cfg,
                                  std={"embed": 1.0, "bias": 0.02})


@pytest.fixture(scope="module")
def model(uncut):
    """This chip's share: routed experts 4-11 of the 16."""
    whole, params = uncut
    cfg = dataclasses.replace(whole, experts_held=8, expert_offset=4)
    layers = [dict(p, experts={k: v[4:12] for k, v in p["experts"].items()})
              for p in params["layers"]]
    return cfg, dict(params, layers=layers)


@pytest.fixture(scope="module")
def seqs(model):
    cfg, _ = model
    return np.random.default_rng(0).integers(
        0, cfg.vocab_size, (6, N_PROMPT + N_STEPS + 1)).astype(np.int32)


@pytest.fixture(scope="module")
def served(arch, model, seqs):
    """Paged prefill, then decode steps through the latent pages."""
    cfg, params = model
    eng = types.SimpleNamespace(
        page_size=4, prompt_buckets=(16, 32, 64), kv_dtype="fp",
        attn_kernel="gather", params=params)
    return arch.served_logits(eng, cfg, seqs, N_PROMPT, N_STEPS)


def _reference(arch, model, uncut, tokens, without=None):
    """The reference on the UNCUT weights, told the share."""
    cfg, _ = model
    ref = arch.plain_reference()
    hp = dict(arch.hyper(cfg), weights_offset=0)
    return np.asarray(ref.forward(ref.from_program(uncut[1]),
                                  jnp.asarray(tokens), hp, without=without))


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def _distance(arch, model, uncut, seqs, served, without=None):
    total = N_PROMPT + N_STEPS
    want = _reference(arch, model, uncut, seqs[:, :total], without)
    return max(_rel(served[0], want[:, N_PROMPT - 1]),
               _rel(served[N_STEPS], want[:, total - 1]))


def test_prefill_then_decode_through_the_pages_is_the_reference(
        arch, model, uncut, seqs, served):
    assert _distance(arch, model, uncut, seqs, served) < TOL


@pytest.mark.parametrize("without", MECHANISMS)
def test_a_mechanism_left_out_fails_the_same_comparison(
        arch, model, uncut, seqs, served, without):
    assert set(arch.plain_reference().MECHANISMS) == set(MECHANISMS)
    d = _distance(arch, model, uncut, seqs, served, without)
    assert d > 1000 * TOL, (without, d)


def test_the_engine_answers_by_the_reference_fresh_hit_and_evicted(
        arch, model, uncut):
    """Tokens out of the engine at temperature 0, each judged on the
    reference's logits along its own answer: into fresh pages, as a
    prefix-cache hit on latent pages (a copy-on-write fork of the last,
    partial page), and after other prompts have evicted its pages."""
    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=4, chunk=4, max_len=96,
                       prompt_buckets=(16, 32, 64), page_size=4,
                       n_pages=56)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (23,)).astype(np.int32)

    def ask():
        return np.concatenate(list(eng.stream(prompt, 12)))

    try:
        answers = [ask()]
        st0 = eng.stats()
        answers.append(ask())
        st1 = eng.stats()
        assert st1["prefix_tokens_reused"] - st0["prefix_tokens_reused"] \
            >= 20
        assert st1["cow_copies"] - st0["cow_copies"] == 1
        for _ in range(8):      # 8 x 12 pages through a pool of 56
            list(eng.stream(rng.integers(0, cfg.vocab_size, (40,)
                                         ).astype(np.int32), 8))
        st2 = eng.stats()
        assert st2["prefix_evictions"] > st1["prefix_evictions"]
        answers.append(ask())
        assert eng.stats()["prefix_tokens_reused"] \
            == st2["prefix_tokens_reused"], "its pages were evicted"
        moved = {k: eng.stats()[k] for k in scmoe.STEP_COUNTERS}
    finally:
        eng.shutdown()
    for a in answers:
        assert len(a) == 12
        row = np.concatenate([prompt, a[:-1]])[None]
        logits = _reference(arch, model, uncut, row)[0, len(prompt) - 1:]
        gaps = logits.max(-1) - logits[np.arange(12), a]
        assert gaps.max() <= 1e-3 * np.abs(logits).max(), gaps
    # the counters came out with the tokens: every live row of every
    # expert layer was routed, and some of its choices cost nothing
    assert scmoe.STEP_COUNTERS[:4] == mla_moe.STEP_COUNTERS
    assert moved["moe_steps"] > 0 and moved["moe_tokens_sum"] > 0
    assert 0 < moved["moe_zero_choices_sum"] \
        < cfg.top_k * moved["moe_tokens_sum"]
    assert moved["moe_tokens_here_sum"] + moved["moe_zero_choices_sum"] \
        <= cfg.top_k * moved["moe_tokens_sum"]


def test_the_shares_and_the_identity_experts_once_are_the_layer(arch,
                                                                uncut):
    """The routed parts that the four chips' shares give (every
    ``expert_offset``), plus the identity experts' part counted ONCE,
    are what the uncut reference gives for the whole expert layer."""
    cfg, params = uncut
    ref = arch.plain_reference()
    p = params["layers"][1]
    u = jnp.asarray(np.random.default_rng(2).normal(size=(40, cfg.d_model)),
                    jnp.float32)
    per = cfg.n_routed // 4
    with jax.default_matmul_precision("highest"):
        ids, w = moe.route_softmax_bias(
            u, p["router"]["kernel"], p["router"]["bias"], top_k=cfg.top_k,
            route_scale=cfg.route_scale, dtype=jnp.float32)
        parts = [moe.dropless_experts(
            u, ids, w, {k: v[off:off + per] for k, v in p["experts"].items()},
            experts_held=per, expert_offset=off, dtype=jnp.float32,
            block_rows=8) for off in range(0, cfg.n_routed, per)]
        zero, n_zero = moe.zero_experts(u, ids, w, n_routed=cfg.n_routed)
        routed, ident, _ = ref.expert_layer(
            u, ref.from_program(params)["layers"][1], arch.hyper(cfg))
    assert _rel(np.asarray(zero), np.asarray(ident)) < 1e-6
    total = sum(y for y, _ in parts) + zero
    assert _rel(np.asarray(total), np.asarray(routed + ident)) < 1e-5
    # every choice landed on exactly one share or on an identity expert
    assert sum(int(c[1]) for _, c in parts) + int(n_zero) == 40 * cfg.top_k
    assert int(n_zero) == int((np.asarray(ids) >= cfg.n_routed).sum()) > 0
    # and three shares are NOT the layer
    assert _rel(np.asarray(total - parts[0][0]),
                np.asarray(routed + ident)) > 0.01


def test_a_selection_bias_moves_choices_and_leaves_their_weights(uncut):
    cfg, params = uncut
    p = params["layers"][0]["router"]
    u = jnp.asarray(np.random.default_rng(4).normal(size=(64, cfg.d_model)),
                    jnp.float32)
    kw = dict(top_k=cfg.top_k, route_scale=cfg.route_scale,
              dtype=jnp.float32)
    ids, w = moe.route_softmax_bias(u, p["kernel"], p["bias"], **kw)
    ids0, _ = moe.route_softmax_bias(u, p["kernel"],
                                     jnp.zeros_like(p["bias"]), **kw)
    scores = jax.nn.softmax(u @ p["kernel"], axis=-1)
    assert (np.sort(ids, 1) != np.sort(ids0, 1)).any()
    assert np.allclose(np.asarray(w), cfg.route_scale * np.take_along_axis(
        np.asarray(scores), np.asarray(ids), 1), rtol=1e-5)
    assert int(ids.max()) >= cfg.n_routed     # identity experts are chosen


@pytest.mark.parametrize("knobs,why", [
    (dict(kv_dtype="int8"), "no quantised layout"),
    (dict(tp=2), "no tensor-parallel programs"),
    (dict(spec_decode="ngram"), "no verify program"),
    (dict(role="prefill"), "no export/import programs"),
    (dict(role="decode"), "no export/import programs")])
def test_what_the_model_does_not_get_raises_with_the_reason(model, knobs,
                                                            why):
    cfg, params = model
    with pytest.raises(ValueError, match=why):
        DecodeEngine(params, cfg, slots=2, max_len=32, auto_start=False,
                     **knobs)


def test_the_pool_counts_two_latent_entries_a_layer(model):
    """``cache_spec`` is the one place the pool's shapes come from: the
    latent entry counts ``2 * n_layer`` attentions, and the page cost
    and the engine's ``kv_bytes_per_token`` follow it."""
    cfg, params = model
    assert serving.decode_programs(cfg) is scmoe
    spec = scmoe.cache_spec(cfg)
    assert spec.layers("latent") == 2 * cfg.n_layer == 4
    cache = scmoe.init_paged_cache(cfg, 3, 10, 4)
    assert cache["latent"].shape == (4, 10, 4, cfg.latent_row)
    assert scmoe.kv_bytes_per_page(cfg, 4) == spec.bytes_per_page(4) \
        == cache["latent"].nbytes // 10
    eng = DecodeEngine(params, cfg, slots=2, max_len=32, page_size=4,
                       auto_start=False)
    assert eng.stats()["kv_bytes_per_token"] == 4 * cfg.latent_row * 4


def test_the_programs_keep_the_names_a_trace_shows_and_hold_the_kernel(
        model):
    cfg, params = model
    assert scmoe.jit_decode_chunk_slots_paged(
        cfg, 4, 4).__wrapped__.__name__ == "decode_chunk_slots_paged"
    assert scmoe.jit_prefill_into_slot_paged(
        cfg, 4).__wrapped__.__name__ == "prefill_into_slot_paged"
    # the attention is mla_moe's own, imported: one kernel body
    assert scmoe.decode_attention is mla_moe.decode_attention
    eng = DecodeEngine(params, cfg, slots=2, chunk=2, max_len=48,
                       prompt_buckets=(16,), page_size=4, n_pages=30)
    try:
        assert eng.warm_up()["attn_kernel_mode"] == "interpret"
    finally:
        eng.shutdown()

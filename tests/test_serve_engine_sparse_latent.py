"""The seventh block through the SAME ``DecodeEngine``: the latent-
attention expert decoder of ``ray_tpu/models/dsa_moe.py`` whose
attention reads only the ``index_topk`` cached tokens a learned indexer
picks. The page holds a second per-token entry (the index key) under
the same page ids, so the prefix cache, copy-on-write and eviction
carry it as they stand; the selection lives in the decode step. The
plain reference is the benchmark's (``benchmarks/perf/architectures/
deepseek_v32_reference.py``: float32, a literal top k over the whole
prefix at every position, no code shared with ``ray_tpu``), and the
comparison is on LOGITS at float32 with ``index_topk`` 16, so that
nearly every decode step selects and a mechanism left out cannot hide
behind rounding."""
import dataclasses
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import dsa_moe, mla_moe, moe, serving
from ray_tpu.serve.engine import DecodeEngine

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "perf")
#: float32 program against float32 reference: the order of additions
TOL = 2e-5
#: the prompt is under index_topk (16), the answer runs far past it
N_PROMPT, N_STEPS = 14, 40
#: what the reference can leave out, one at a time (its ``MECHANISMS``)
MECHANISMS = ("rotary", "yarn_blend", "mscale", "latent_norm", "sigmoid",
              "group_limit", "norm_topk", "route_scale", "shared_expert",
              "selection_bias", "selection", "index_rotary", "index_norm",
              "index_relu", "index_weights")
#: those that only a step that SELECTS can show
AFTER_DECODE_ONLY = ("selection", "index_rotary", "index_norm",
                     "index_relu", "index_weights")


@pytest.fixture(scope="module")
def arch():
    sys.path.insert(0, PERF)
    try:
        import perf_harness as H

        yield H.load_architecture({"architecture": "deepseek_v32"})
    finally:
        sys.path.remove(PERF)


@pytest.fixture(scope="module")
def uncut():
    """The whole model at float32: all 16 routed experts."""
    cfg = dataclasses.replace(dsa_moe.CONFIGS["nano"], dtype=jnp.float32,
                              param_dtype=jnp.float32)
    return cfg, dsa_moe.init_params(jax.random.PRNGKey(0), cfg,
                                    std={"embed": 1.0})


@pytest.fixture(scope="module")
def model(uncut):
    """This chip's share: routed experts 4-11 of the 16."""
    whole, params = uncut
    cfg = dataclasses.replace(whole, experts_held=8, expert_offset=4)
    layers = [dict(p, experts={k: v[4:12] for k, v in p["experts"].items()})
              if "experts" in p else p for p in params["layers"]]
    return cfg, dict(params, layers=layers)


@pytest.fixture(scope="module")
def seqs(model):
    cfg, _ = model
    return np.random.default_rng(0).integers(
        0, cfg.vocab_size, (6, N_PROMPT + N_STEPS + 1)).astype(np.int32)


@pytest.fixture(scope="module")
def served(arch, model, seqs):
    """Paged prefill, then decode steps that score, pick and attend."""
    cfg, params = model
    eng = types.SimpleNamespace(
        page_size=4, prompt_buckets=(16,), kv_dtype="fp",
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


def _distances(arch, model, uncut, seqs, served, without=None):
    total = N_PROMPT + N_STEPS
    want = _reference(arch, model, uncut, seqs[:, :total], without)
    return (_rel(served[0], want[:, N_PROMPT - 1]),
            _rel(served[N_STEPS], want[:, total - 1]))


def test_prefill_then_selecting_decode_steps_are_the_reference(
        arch, model, uncut, seqs, served):
    assert max(_distances(arch, model, uncut, seqs, served)) < TOL


@pytest.mark.parametrize("without", MECHANISMS)
def test_a_mechanism_left_out_of_the_reference_fails_the_comparison(
        arch, model, uncut, seqs, served, without):
    assert set(arch.plain_reference().MECHANISMS) == set(MECHANISMS)
    after_prefill, after_decode = _distances(arch, model, uncut, seqs,
                                             served, without)
    assert after_decode > 1000 * TOL, (without, after_decode)
    if without in AFTER_DECODE_ONLY:
        # a prompt under index_topk attends over all of itself
        assert after_prefill < TOL, (without, after_prefill)


def test_the_engine_answers_by_the_reference_fresh_hit_and_reused_slot(
        arch, model, uncut):
    """Tokens out of the engine at temperature 0, each judged on the
    reference's logits along its own answer: into fresh pages, as a
    prefix-cache hit whose copy-on-write fork carries the index keys
    with the latents, and in a slot and pages other requests have used;
    all as a fresh engine answers."""
    cfg, params = model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (15,)).astype(np.int32)

    def engine():
        return DecodeEngine(params, cfg, slots=2, chunk=4, max_len=64,
                            prompt_buckets=(16,), page_size=4, n_pages=28)

    eng = engine()
    try:
        def ask():
            return np.concatenate(list(eng.stream(prompt, 36)))

        answers = [ask()]
        st0 = eng.stats()
        answers.append(ask())
        st1 = eng.stats()
        assert st1["prefix_tokens_reused"] - st0["prefix_tokens_reused"] \
            >= 12
        assert st1["cow_copies"] - st0["cow_copies"] == 1
        for _ in range(6):      # both slots and most pages used again
            list(eng.stream(rng.integers(0, cfg.vocab_size, (16,)
                                         ).astype(np.int32), 30))
        assert eng.stats()["prefix_evictions"] > st1["prefix_evictions"]
        answers.append(ask())
        moved = {k: eng.stats()[k] for k in dsa_moe.STEP_COUNTERS}
    finally:
        eng.shutdown()
    fresh = engine()
    try:
        answers.append(np.concatenate(list(fresh.stream(prompt, 36))))
    finally:
        fresh.shutdown()
    for a in answers:
        assert len(a) == 36
        row = np.concatenate([prompt, a[:-1]])[None]
        logits = _reference(arch, model, uncut, row)[0, len(prompt) - 1:]
        gaps = logits.max(-1) - logits[np.arange(36), a]
        assert gaps.max() <= 1e-3 * np.abs(logits).max(), gaps
    assert all((a == answers[-1]).all() for a in answers)
    # the selection's counters came out with the tokens
    assert dsa_moe.STEP_COUNTERS[:4] == mla_moe.STEP_COUNTERS
    assert 0 < moved["dsa_lane_steps_selecting_sum"] \
        < moved["dsa_lane_steps_sum"]
    assert moved["dsa_tokens_selected_sum"] \
        < moved["dsa_tokens_scanned_sum"]


def test_the_counters_count_the_lanes_that_select_on_the_device(model):
    """One request alone: ``cached`` tokens a step are the prompt's and
    the answer's so far, picked ``min(cached, index_topk)``."""
    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=2, chunk=4, max_len=64,
                       prompt_buckets=(16,), page_size=4, n_pages=40,
                       prefix_cache=False)
    try:
        prompt = np.arange(10, dtype=np.int32)
        list(eng.stream(prompt, 21))     # 20 decode steps: 5 launches
        st = eng.stats()
    finally:
        eng.shutdown()
    cached = [10 + i + 1 for i in range(20)]
    assert st["dsa_lane_steps_sum"] == 20
    assert st["dsa_tokens_scanned_sum"] == sum(cached)
    assert st["dsa_tokens_selected_sum"] == sum(
        min(c, cfg.index_topk) for c in cached)
    assert st["dsa_lane_steps_selecting_sum"] == sum(
        c > cfg.index_topk for c in cached)
    assert st["moe_steps"] == 20 * (cfg.n_layer - cfg.n_dense)


def test_the_picked_set_is_the_references_top_k(arch, model, uncut):
    """The step's own selection against a literal ``top_k`` of the
    reference's index scores, at every layer's first attention: the
    same positions wherever the edge is clear (float32 both sides)."""
    cfg, _ = model
    ref = arch.plain_reference()
    hp = arch.hyper(cfg)
    w = ref.from_program(uncut[1])
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, (48,))
    x = jnp.asarray(w["embed"], jnp.float32)[tokens]
    h = ref.rms(x, w["ln1"][0], hp["eps"])
    cq = ref.rms(h @ w["wqa"][0], w["q_norm"][0], hp["eps"])
    qi, ki, wi = ref.indexer(h, cq, w, 0, hp)
    p = uncut[1]["layers"][0]
    pos = jnp.arange(48)[None]
    keys = dsa_moe.index_key(h[None], p, pos, cfg)[0]       # [48, row]
    q, wq = dsa_moe.index_query(h[None], cq[None], p, pos, cfg)
    t = 47
    scores = dsa_moe.index_scores(q[:, t], wq[:, t], keys[None])[0]
    want = jnp.einsum("k,hk->k", jnp.ones(48), jax.nn.relu(
        jnp.einsum("hd,kd->hk", qi[t], ki)) * wi[t][:, None])
    np.testing.assert_allclose(scores, want, rtol=2e-4, atol=2e-6)
    picked = np.asarray(dsa_moe.pick_top(
        scores[None], jnp.asarray([cfg.index_topk])))[0]
    top = np.asarray(jax.lax.top_k(want, cfg.index_topk)[1])
    assert sorted(np.flatnonzero(picked)) == sorted(top)


@pytest.mark.parametrize("n", [1, 7, 16, 40])
def test_pick_top_is_top_k_with_ties_to_the_lower_index(n):
    rng = np.random.default_rng(n)
    scores = rng.normal(size=(5, 40)).astype(np.float32)
    scores[1] = np.round(scores[1])                 # many exact ties
    scores[2, 25:] = -np.inf                        # 25 tokens cached
    scores[3] = 0.0                                 # all tied
    scores[4, ::3] *= -0.0                          # both zeros
    k = np.minimum(n, [40, 40, 25, 40, 40]).astype(np.int32)
    got = np.asarray(dsa_moe.pick_top(jnp.asarray(scores), jnp.asarray(k)))
    for b in range(5):
        want = np.asarray(jax.lax.top_k(jnp.asarray(scores[b]),
                                        int(k[b]))[1])
        assert sorted(np.flatnonzero(got[b])) == sorted(want), b
    assert not got[2, 25:].any()
    none = np.asarray(dsa_moe.pick_top(jnp.asarray(scores),
                                       jnp.zeros((5,), jnp.int32)))
    assert not none.any()


def test_the_shares_of_every_chip_and_the_shared_expert_once_are_the_layer(
        arch, uncut):
    """The routed parts that the four chips' shares give (every
    ``expert_offset``), plus the shared expert counted ONCE, are what
    the uncut reference gives for the whole expert layer."""
    cfg, params = uncut
    ref = arch.plain_reference()
    p = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(40, cfg.d_model)),
                    jnp.float32)
    per = cfg.n_routed // 4
    with jax.default_matmul_precision("highest"):
        ids, w = moe.route_sigmoid(
            h, p["router"]["kernel"], n_group=cfg.n_group,
            topk_group=cfg.topk_group, top_k=cfg.top_k,
            norm_topk=cfg.norm_topk, route_scale=cfg.route_scale,
            dtype=jnp.float32, bias=p["router"]["bias"])
        parts = [moe.dropless_experts(
            h, ids, w, {k: v[off:off + per] for k, v in p["experts"].items()},
            experts_held=per, expert_offset=off, dtype=jnp.float32,
            block_rows=8) for off in range(0, cfg.n_routed, per)]
        shared = moe.gated_ffn(h, p["shared"], jnp.float32)
        hp = dict(arch.hyper(cfg), weights_offset=0)
        want, _ = ref.expert_layer(h, ref.from_program(params), 1, hp)
    total = sum(y for y, _ in parts) + shared
    assert _rel(np.asarray(total), np.asarray(want)) < 1e-5
    assert sum(int(c[1]) for _, c in parts) == 40 * cfg.top_k
    # three shares, or the shared expert a share, are NOT the layer
    assert _rel(np.asarray(total - parts[0][0]), np.asarray(want)) > 0.01
    assert _rel(np.asarray(total + 3 * shared), np.asarray(want)) > 0.01


def test_a_selection_bias_chooses_on_biased_scores_and_weighs_unbiased(
        uncut):
    cfg, params = uncut
    p = params["layers"][1]["router"]
    h = jnp.asarray(np.random.default_rng(4).normal(size=(64, cfg.d_model)),
                    jnp.float32)
    kw = dict(n_group=cfg.n_group, topk_group=cfg.topk_group,
              top_k=cfg.top_k, norm_topk=False, route_scale=1.0,
              dtype=jnp.float32)
    scores = jax.nn.sigmoid(h @ p["kernel"])
    ids, w = moe.route_sigmoid(h, p["kernel"], bias=p["bias"], **kw)
    want, _ = moe.group_limited_top_k(scores + p["bias"], cfg.n_group,
                                      cfg.topk_group, cfg.top_k,
                                      floor=-jnp.inf)
    assert (np.asarray(ids) == np.asarray(want)).all()
    np.testing.assert_allclose(
        w, np.take_along_axis(np.asarray(scores), np.asarray(ids), 1),
        rtol=1e-6)
    ids0, w0 = moe.route_sigmoid(h, p["kernel"], **kw)
    assert (np.sort(ids, 1) != np.sort(ids0, 1)).any()
    plain, s0 = moe.group_limited_top_k(scores, cfg.n_group,
                                        cfg.topk_group, cfg.top_k)
    assert (np.asarray(ids0) == np.asarray(plain)).all()
    np.testing.assert_allclose(w0, s0, rtol=1e-6)
    # a bias far below -1 still keeps closed groups' experts out
    low, _ = moe.route_sigmoid(h, p["kernel"], bias=p["bias"] - 5.0, **kw)
    assert (np.asarray(low) == np.asarray(ids)).all()


@pytest.mark.parametrize("knobs,why", [
    (dict(kv_dtype="int8"), "no quantised layout"),
    (dict(tp=2), "no tensor-parallel programs"),
    (dict(spec_decode="ngram"), "no verify program"),
    (dict(role="prefill"), "carry the index keys"),
    (dict(role="decode"), "carry the index keys")])
def test_what_the_sparse_model_does_not_get_raises_with_the_reason(
        model, knobs, why):
    cfg, params = model
    assert set(dsa_moe.UNSUPPORTED) == {"int8", "tp", "spec_decode",
                                        "roles", "long_prompt"}
    with pytest.raises(ValueError, match=why):
        DecodeEngine(params, cfg, slots=2, max_len=32, auto_start=False,
                     **knobs)


def test_a_prompt_bucket_past_index_topk_is_refused_not_served_densely(
        arch, model):
    """By whoever constructs the engine (the architecture's
    ``make_engine``), and by the prefill program itself when it is
    built, single or in a group: ``warm_up()`` raises before a replica
    would report ready."""
    cfg, params = model
    dsa_moe.check_prompt_buckets(cfg, (8, 16))
    with pytest.raises(ValueError, match="selection inside a prefill"):
        dsa_moe.check_prompt_buckets(cfg, (16, 32))
    conf = {"engine": dict(slots=2, chunk=2, max_len=64,
                           prompt_buckets=[16, 32], page_size=4,
                           n_pages=40, prefix_cache=False,
                           attn_kernel="gather", kv_dtype="fp")}
    with pytest.raises(ValueError, match=r"prompt buckets \[32\]"):
        arch.make_engine(params, cfg, conf)
    eng = DecodeEngine(params, cfg, slots=2, chunk=2, max_len=64,
                       prompt_buckets=(32,), page_size=4, n_pages=40)
    try:
        with pytest.raises(ValueError, match="index_topk 16"):
            eng.warm_up()
    finally:
        eng.shutdown()


def test_the_page_holds_the_index_key_beside_the_latent_row(model):
    """``cache_spec`` is the one place the pool's shapes come from: two
    per-token entries under the same page ids, each whole lane tiles."""
    cfg, params = model
    assert serving.decode_programs(cfg) is dsa_moe
    spec = dsa_moe.cache_spec(cfg)
    assert [(e.name, e.per, e.shape) for e in spec.entries] == [
        ("latent", "token", (cfg.latent_row,)),
        ("ikey", "token", (cfg.index_row,))]
    assert cfg.index_row == 128 and cfg.latent_row == 128
    cache = dsa_moe.init_paged_cache(cfg, 3, 10, 4)
    assert cache["ikey"].shape == (cfg.n_layer, 10, 4, 128)
    assert dsa_moe.kv_bytes_per_page(cfg, 4) == spec.bytes_per_page(4) \
        == (cache["latent"].nbytes + cache["ikey"].nbytes) // 10
    eng = DecodeEngine(params, cfg, slots=2, max_len=32, page_size=4,
                       auto_start=False)
    assert eng.stats()["kv_bytes_per_token"] == cfg.n_layer * 256 * 4
    big = dataclasses.replace(cfg, kv_rank=512, rope_dim=64, index_dim=128,
                              n_layer=5, dtype=jnp.bfloat16)
    assert dsa_moe.kv_bytes_per_page(big, 16) == 122_880


def test_the_step_holds_the_imported_kernel_under_the_selections_scopes(
        model):
    cfg, params = model
    assert dsa_moe.jit_decode_chunk_slots_paged(
        cfg, 4, 4).__wrapped__.__name__ == "decode_chunk_slots_paged"
    assert dsa_moe.decode_attention_fused is mla_moe.decode_attention_fused
    cache = dsa_moe.init_paged_cache(cfg, 2, 30, 4)
    text = jax.jit(lambda p, c, t, a, pt: dsa_moe._slot_decode_step_paged(
        p, c, t, a, pt, cfg, 4)).lower(
        params, cache, np.zeros((2,), np.int32), np.ones((2,), bool),
        np.zeros((2, 12), np.int32)).as_text(debug_info=True)
    for scope in ("dsa.index", "dsa.select", "dsa.attention", "moe.route",
                  "moe.experts", "moe.shared"):
        assert f"decode_step/{scope}/" in text, scope
    assert "dsa.attention/latent_attention" in text
    eng = DecodeEngine(params, cfg, slots=2, chunk=2, max_len=48,
                       prompt_buckets=(16,), page_size=4, n_pages=30)
    try:
        assert eng.warm_up()["attn_kernel_mode"] == "interpret"
    finally:
        eng.shutdown()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_over_a_selection_is_the_gathered_pages_masked(dtype):
    """``mla_moe``'s kernel with ``picked`` against its XLA body with
    the same mask: lanes of different lengths, a lane whose FIRST block
    holds no picked token (small blocks), an idle lane."""
    cfg = dataclasses.replace(mla_moe.CONFIGS["nano"], dtype=dtype)
    ps, max_pages, B = 4, 12, 4
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.normal(size=(B * max_pages, ps, cfg.latent_row)),
                       dtype)
    q = jnp.asarray(rng.normal(size=(B, cfg.n_head, cfg.latent_row)), dtype)
    pages = jnp.asarray(rng.permutation(B * max_pages).reshape(
        B, max_pages), jnp.int32)
    pos = jnp.asarray([47, 20, 0, 30], jnp.int32)
    length = jnp.asarray([48, 21, 0, 31], jnp.int32)
    picked = rng.random((B, ps * max_pages)) < 0.4
    picked[0, :16] = False                        # nothing in block 0
    picked[:, 0] |= ~picked[:, :].any(1)
    picked = jnp.asarray(picked & (np.arange(48)[None] <= np.asarray(pos)[:, None]))
    picked = picked.at[1, 20].set(True).at[3, 30].set(True)
    import unittest.mock as mock

    with mock.patch.object(mla_moe, "_ATTN_BLOCK_TOKENS", 16):
        got = mla_moe.latent_attention(q, pool, pages, pos, length, cfg, ps,
                                       picked)
    want = mla_moe.latent_attention(q, pool, pages, pos, None, cfg, ps,
                                    picked)
    live = np.asarray(length) > 0
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6
    assert err[live].max() <= mla_moe.ATTN_KERNEL_ULPS * ulp * np.abs(
        np.asarray(want, np.float32)[live]).max()
    # and without a mask the new argument changes nothing
    a = mla_moe.latent_attention(q, pool, pages, pos, length, cfg, ps)
    b = mla_moe.latent_attention(
        q, pool, pages, pos, length, cfg, ps,
        jnp.ones((B, ps * max_pages), bool))
    np.testing.assert_allclose(np.asarray(a, np.float32)[live],
                               np.asarray(b, np.float32)[live], rtol=1e-6)

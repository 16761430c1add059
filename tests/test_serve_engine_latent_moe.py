"""The second block through the SAME ``DecodeEngine``: the
latent-attention expert decoder of ``ray_tpu/models/mla_moe.py``
(ROADMAP M1, M3, D9 and the part of D1 they need). The engine takes the
programs, the cache's shape and what the model does not get from the
config object's description (``models/serving.py``); page ids, the page
pool, the prefix cache, COW and the driver loop are the GPT-2 block's.
The comparison with the plain reference is ``tests/perf/
test_perf_axk1.py``'s."""
import dataclasses
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt, gpt_decode, mla_moe, moe, serving
from ray_tpu.serve.engine import DecodeEngine


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(mla_moe.CONFIGS["nano"], experts_held=8)
    return cfg, mla_moe.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=8, chunk=4, max_len=96,
                       prompt_buckets=(16, 32, 64), page_size=4,
                       n_pages=200)
    yield eng
    eng.shutdown()


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _together(eng, prompts, max_new):
    outs = [None] * len(prompts)

    def run(i):
        outs[i] = np.concatenate(list(eng.stream(prompts[i], max_new)))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def test_a_requests_tokens_are_the_same_alone_and_among_seven_others(
        model, engine):
    """D9: the expert layer is dropless, so what a request is answered
    does not depend on who shares its batch."""
    cfg, _ = model
    prompts = _prompts(cfg, (9, 17, 30, 12, 33, 21, 40, 11))
    alone = np.concatenate(list(engine.stream(prompts[0], 14)))
    among = _together(engine, prompts, 14)
    assert len(alone) == 14
    assert np.array_equal(alone, among[0])
    # and every one of the eight alone again, now as prefix-cache hits
    for p, want in zip(prompts[1:4], among[1:4]):
        assert np.array_equal(
            np.concatenate(list(engine.stream(p, 14))), want)


def test_the_capacity_gating_it_replaces_depends_on_the_batch():
    """What D9 was about: ``top_k_gating`` gives a token a slot only
    while its expert has capacity left, first come first served, so a
    token's result changes with the tokens before it; ``dropless_moe``
    computes every choice."""
    rng = np.random.default_rng(0)
    T, d, f, E = 64, 32, 48, 4
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, E)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(E, d, f)) * 0.2, jnp.float32)
    down = jnp.asarray(rng.normal(size=(E, f, d)) * 0.2, jnp.float32)

    def old(rows):
        return moe.moe_ffn(rows[None], router, up, down, top_k=2,
                           capacity_factor=0.5, dtype=jnp.float32)[0][0]

    assert float(jnp.abs(old(x)[-1] - old(x[-1:])[0]).max()) > 1e-3
    experts = {"gate": up, "up": up, "down": down}
    kw = dict(experts_held=E, expert_offset=0, n_group=1, topk_group=1,
              top_k=2, norm_topk=True, route_scale=1.0,
              dtype=jnp.float32, block_rows=8)
    whole, counts = moe.dropless_moe(x, router, experts, **kw)
    last, _ = moe.dropless_moe(x[-1:], router, experts, **kw)
    assert float(jnp.abs(whole[-1] - last[0]).max()) < 1e-5
    assert int(counts[1]) == T * 2            # every choice landed


def test_rows_that_are_no_tokens_are_routed_nowhere():
    rng = np.random.default_rng(1)
    T, d, f, E = 12, 16, 24, 8
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, E)), jnp.float32)
    experts = {n: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
               for n, s in (("gate", (4, d, f)), ("up", (4, d, f)),
                            ("down", (4, f, d)))}
    kw = dict(experts_held=4, expert_offset=4, n_group=2, topk_group=1,
              top_k=2, norm_topk=True, route_scale=2.5,
              dtype=jnp.float32, block_rows=4)
    live = jnp.arange(T) < 7
    y, counts = moe.dropless_moe(x, router, experts, live=live, **kw)
    y7, counts7 = moe.dropless_moe(x[:7], router, experts, **kw)
    assert np.array_equal(np.asarray(counts), np.asarray(counts7))
    assert float(jnp.abs(y[:7] - y7).max()) < 1e-6
    assert float(jnp.abs(y[7:]).max()) == 0.0


def _dropless_moe_before(x, router_kernel, experts, *, experts_held,
                         expert_offset, n_group, topk_group, top_k,
                         norm_topk, route_scale, dtype, block_rows=32,
                         live=None):
    """``moe.dropless_moe`` as it stood before ISSUE 47 split the
    routing from the dispatch, line for line (commit e105427): the
    oracle of the test below and nothing else."""
    from jax import lax

    T, d = x.shape
    k, held, bm = top_k, experts_held, block_rows
    n_max = -(-T * k // bm) + held
    ids, w = moe.route_sigmoid(x, router_kernel, n_group=n_group,
                               topk_group=topk_group, top_k=k,
                               norm_topk=norm_topk,
                               route_scale=route_scale, dtype=dtype)
    local = ids - expert_offset
    here = (local >= 0) & (local < held)
    if live is not None:
        here = here & live[:, None]
    key = jnp.where(here, local, held).reshape(-1)
    sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    start = jnp.cumsum(sizes) - sizes
    blocks = -(-sizes // bm)
    first = jnp.cumsum(blocks) - blocks
    n_blocks = jnp.sum(blocks)
    blk = jnp.arange(n_max, dtype=jnp.int32)
    owner = jnp.clip(jnp.searchsorted(jnp.cumsum(blocks), blk,
                                      side="right"), 0, held - 1
                     ).astype(jnp.int32)
    place = start[owner][:, None] + (blk - first[owner])[:, None] * bm \
        + jnp.arange(bm, dtype=jnp.int32)[None]
    filled = (place < (start + sizes)[owner][:, None]) \
        & (blk < n_blocks)[:, None]
    tok = order[jnp.clip(place, 0, T * k - 1)] // k
    xs = jnp.where(filled[..., None], x.astype(dtype)[tok], 0)

    def mm(a, m, e):
        return lax.dot_general(a, m[e].astype(dtype),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def one_block(j, out):
        e, xb = owner[j], xs[j]
        h = (jax.nn.silu(mm(xb, experts["gate"], e))
             * mm(xb, experts["up"], e)).astype(dtype)
        return out.at[j].set(mm(h, experts["down"], e).astype(dtype))

    out = lax.fori_loop(0, n_blocks, one_block,
                        jnp.zeros((n_max, bm, d), dtype))
    loc = jnp.clip(local, 0, held - 1)
    r = rank.reshape(T, k) - start[loc]
    at = jnp.where(here, (first[loc] + r // bm) * bm + r % bm, 0)
    part = out.reshape(n_max * bm, d)[at].astype(jnp.float32)
    y = jnp.sum(jnp.where(here, w, 0.0)[..., None] * part, axis=1)
    counts = jnp.stack([jnp.sum(sizes > 0, dtype=jnp.int32),
                        jnp.sum(sizes), jnp.max(sizes)])
    return y.astype(dtype), counts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("routing", [
    # A.X-K1: 8 groups of which 4, top 8, normalised, times 2.5; the
    # share is experts 12-23 of 48
    dict(n_group=8, topk_group=4, top_k=8, norm_topk=True,
         route_scale=2.5, experts_held=12, expert_offset=12, E=48),
    # Solar-Open2: one group (a plain top 8), normalised, times 1;
    # the share is experts 0-9 of 40
    dict(n_group=1, topk_group=1, top_k=8, norm_topk=True,
         route_scale=1.0, experts_held=10, expert_offset=0, E=40)],
    ids=["axk1", "solar_open2"])
def test_the_sigmoid_routed_layer_is_unchanged_to_the_bit(routing, dtype):
    """ISSUE 47 made the dispatch take its routing from the caller
    (``dropless_experts``) so that a softmax-with-bias router whose ids
    reach past the routed experts shares it; ``dropless_moe`` is now
    ``route_sigmoid`` and that dispatch, and gives A.X-K1's and
    Solar-Open2's routings the bits it gave: result and counters, with
    idle rows and without."""
    kw = dict(routing)
    E = kw.pop("E")
    rng = np.random.default_rng(7)
    T, d, f, held = 37, 32, 24, kw["experts_held"]
    x = jnp.asarray(rng.normal(size=(T, d)), dtype)
    router = jnp.asarray(rng.normal(size=(d, E)) * 0.3, dtype)
    experts = {n: jnp.asarray(rng.normal(size=s) * 0.2, dtype)
               for n, s in (("gate", (held, d, f)), ("up", (held, d, f)),
                            ("down", (held, f, d)))}
    for live in (None, jnp.arange(T) % 5 != 0):
        want = jax.jit(lambda x: _dropless_moe_before(
            x, router, experts, dtype=dtype, block_rows=8, live=live,
            **kw))(x)
        got = jax.jit(lambda x: moe.dropless_moe(
            x, router, experts, dtype=dtype, block_rows=8, live=live,
            **kw))(x)
        assert int(want[1][1]) > 0
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_experts_load_comes_out_with_the_tokens(model, engine):
    cfg, _ = model
    before = engine.stats()
    list(engine.stream(_prompts(cfg, (13,), seed=5)[0], 9))
    after = engine.stats()
    moved = {k: after[k] - before[k] for k in mla_moe.STEP_COUNTERS}
    launches = after["dispatches"] - before["dispatches"]
    expert_layers = cfg.n_layer - cfg.n_dense
    assert moved["moe_steps"] == launches * engine.chunk * expert_layers
    assert 0 < moved["moe_experts_touched_sum"] \
        <= moved["moe_tokens_here_sum"]
    assert moved["moe_expert_peak_sum"] <= moved["moe_tokens_here_sum"]
    assert moved["moe_experts_touched_sum"] \
        <= cfg.experts_held * moved["moe_steps"]
    # the GPT-2 block keeps no such counters, and its stats no such keys
    assert gpt_decode.STEP_COUNTERS == ()


def test_a_cached_prefix_that_ends_mid_page_is_forked_not_shared(
        model, engine):
    """The prefix cache and COW on latent pages: a prompt of 23 tokens
    (5.75 pages of 4) sent again maps the five whole pages, forks the
    sixth, prefills one token, and answers as it does from a whole
    prefill."""
    cfg, params = model
    a = b = _prompts(cfg, (23,), seed=9)[0]
    fresh = DecodeEngine(params, cfg, slots=2, chunk=4, max_len=96,
                         prompt_buckets=(16, 32, 64), page_size=4,
                         n_pages=60, prefix_cache=False)
    try:
        want = np.concatenate(list(fresh.stream(b, 10)))
    finally:
        fresh.shutdown()
    list(engine.stream(a, 6))
    before = engine.stats()
    got = np.concatenate(list(engine.stream(b, 10)))
    after = engine.stats()
    assert after["prefix_tokens_reused"] - before["prefix_tokens_reused"] \
        > 20
    assert after["cow_copies"] - before["cow_copies"] == 1
    assert np.array_equal(got, want)


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


def test_config_plane_knobs_raise_the_same_reasons(model):
    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=2, max_len=32, page_size=4,
                       auto_start=False)
    with pytest.raises(ValueError, match="no quantised layout"):
        eng.ensure_paging(kv_dtype="int8")
    with pytest.raises(ValueError, match="no verify program"):
        eng.ensure_spec(spec_decode="ngram")
    with pytest.raises(ValueError, match="no export/import"):
        eng.ensure_role(role="prefill")
    with pytest.raises(ValueError, match="no tensor-parallel"):
        eng.ensure_tp(2)
    assert eng.ensure_role(role="both") is eng


@pytest.mark.parametrize("which", ["gpt-fp", "gpt-int8", "latent"])
def test_the_pool_its_page_cost_and_the_stats_read_one_cache_spec(
        model, which):
    """``cache_spec`` is the ONE place the pool's shapes come from, for
    both models: ``init_paged_cache``, ``kv_bytes_per_page`` and the
    engine's ``kv_bytes_per_token`` agree with it."""
    if which == "latent":
        cfg, params = model
        desc, kv = serving.decode_programs(cfg), "fp"
        assert cfg.latent_row == 128 >= cfg.latent_dim == 40
        assert dataclasses.replace(cfg, kv_rank=512,
                                   rope_dim=64).latent_row == 640
        want = {"latent": (cfg.n_layer, 10, 4, cfg.latent_row)}
        per_token = cfg.n_layer * cfg.latent_row * 2
    else:
        cfg = gpt.CONFIGS["nano"]
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        desc, kv = serving.decode_programs(cfg), which[4:]
        row = (cfg.n_layer, 10, 4, cfg.n_head, cfg.head_dim)
        want = {"k": row, "v": row}
        per_token = 2 * cfg.n_layer * cfg.d_model * 2
        if kv == "int8":
            want.update(ks=(cfg.n_layer, 10, cfg.n_head),
                        vs=(cfg.n_layer, 10, cfg.n_head))
            per_token = 2 * cfg.n_layer * (cfg.d_model
                                           + 4 * cfg.n_head / 4)
    assert desc is (mla_moe if which == "latent" else gpt_decode)
    spec = desc.cache_spec(cfg, kv)
    cache = desc.init_paged_cache(cfg, 3, 10, 4, kv)
    assert {k: v.shape for k, v in cache.items() if k != "pos"} == want
    assert cache["pos"].shape == (3,)
    assert desc.kv_bytes_per_page(cfg, 4, kv) == spec.bytes_per_page(4) \
        == sum(v.nbytes for k, v in cache.items() if k != "pos") // 10
    eng = DecodeEngine(params, cfg, slots=2, max_len=32, page_size=4,
                       kv_dtype=kv, auto_start=False)
    assert eng.stats()["kv_bytes_per_token"] == per_token


def test_a_config_without_a_description_is_refused():
    with pytest.raises(TypeError, match="decode_programs"):
        DecodeEngine({}, object(), slots=1, max_len=8, auto_start=False)


def test_the_programs_keep_the_names_a_trace_shows(model):
    cfg, _ = model
    for desc, c in ((mla_moe, cfg), (gpt_decode, gpt.CONFIGS["nano"])):
        assert desc.jit_decode_chunk_slots_paged(
            c, 4, 4).__wrapped__.__name__ == "decode_chunk_slots_paged"
        assert desc.jit_prefill_into_slot_paged(
            c, 4).__wrapped__.__name__ == "prefill_into_slot_paged"


def test_the_engine_reports_the_kernel_its_chunk_program_holds(model,
                                                               engine):
    """ROADMAP S5e: this model's one decode attention is a Pallas
    kernel wherever Mosaic can address a page, and the engine learns it
    from the description (``decode_attention_fused``), not from the
    knob's name: ``attn_kernel`` stays ``"gather"``, ``warm_up()`` reads
    how the kernel was built off the lowered program, and every launch
    of the chunk program counts as a kernel dispatch."""
    cfg, _ = model
    assert engine.attn_kernel == "gather"
    assert engine.warm_up()["attn_kernel_mode"] == "interpret"
    before = engine.stats()
    list(engine.stream(_prompts(cfg, (11,), seed=3)[0], 6))
    after = engine.stats()
    ran = after["dispatches"] - before["dispatches"]
    assert ran > 0
    assert after["attn_kernel_dispatches"] \
        - before["attn_kernel_dispatches"] == ran
    assert after["warm_up"]["attn_kernel_mode"] == "interpret"


def test_a_page_mosaic_cannot_address_takes_the_fallback(model,
                                                         monkeypatch):
    """Compiled for a TPU a page of 4 bfloat16 rows is a quarter of a
    tile: the XLA body runs, nothing raises, and the engine says so
    (``None``, no kernel dispatches). THE one decision is steered here,
    as ``tests/test_gpt_decode_kernel_tpu.py`` steers it; the knobs
    (chunk 3, 2 slots) are no other test's, because a built program is
    cached by its knobs."""
    from ray_tpu._private import chip

    cfg, params = model
    monkeypatch.setattr(chip, "pallas_interpret", lambda: False)
    eng = DecodeEngine(params, cfg, slots=2, chunk=3, max_len=48,
                       prompt_buckets=(16,), page_size=4, n_pages=40)
    try:
        assert eng.warm_up()["attn_kernel_mode"] is None
        out = np.concatenate(list(eng.stream(
            _prompts(cfg, (9,), seed=4)[0], 7)))
        st = eng.stats()
        assert len(out) == 7
        assert st["dispatches"] > 0 and st["attn_kernel_dispatches"] == 0
    finally:
        eng.shutdown()


def test_deferred_delivery_hands_every_lane_the_walks_messages(model,
                                                               engine):
    """ISSUE 43 through this model's programs: the slices, the ends and
    a replay's ``skip`` on the deferring engine are those of a walk that
    hands over at once, and a lone request's end waits for nobody."""
    from test_serve_engine_deliver import check_deferred_against_at_once

    cfg, params = model
    oracle = DecodeEngine(params, cfg, slots=8, chunk=4, max_len=96,
                          prompt_buckets=(16, 32, 64), page_size=4,
                          n_pages=200)
    check_deferred_against_at_once(
        engine, oracle, _prompts(cfg, (9, 17, 30, 12), seed=5))

"""The sixth block through the SAME ``DecodeEngine``: the state-space /
attention expert decoder of ``ray_tpu/models/ssm_moe.py``. Its layers
are of two kinds BY INDEX (``layer_types``): a Mamba-2 layer keeps a
state and a convolution's tail PER SLOT, an attention layer keeps
key/value PAGES, and no layer keeps both; the engine takes that from
the ONE cache description (``models/serving.py``), rebuilds a slot's
state in every prefill into it, leaves an idle or parked lane's alone,
and refuses what a state that belongs to a slot cannot have. The Mamba-2
mixer and both kernels are IMPORTED (``ssm_hybrid``'s public frames,
``kda_moe``'s attention); what is this model's own is the order of the
layers, the third router (``moe.route_topk_softmax``), the tied head
and the constant multipliers.

Every comparison here is with the plain reference
``benchmarks/perf/architectures/granite_moe_hybrid_reference.py``
(float32, the recurrence one token at a time, no cache), on LOGITS:
``tests/perf/test_perf_granite_moe_hybrid.py`` has the leave-one-out
controls and the benchmark's side."""
import dataclasses
import functools
import importlib.util
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import kda_moe, moe, serving, ssm_hybrid
from ray_tpu.models import ssm_moe as sm
from ray_tpu.serve.engine import DecodeEngine
from ray_tpu.util import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
#: float32 program against float32 reference: two orders of summation
REL = 1e-4


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(os.path.dirname(HERE), "benchmarks", "perf",
                         "architectures",
                         "granite_moe_hybrid_reference.py"),
            "granite_moe_hybrid_reference_for_engine_tests")


def _hp(cfg, **over):
    return dict({
        "heads": cfg.n_head, "kv_heads": cfg.n_kv_head,
        "head_dim": cfg.head_dim, "ssm_heads": cfg.ssm_heads,
        "ssm_head_dim": cfg.ssm_head_dim, "ssm_state": cfg.ssm_state,
        "ssm_groups": cfg.ssm_groups, "conv": cfg.conv_size,
        "eps": cfg.eps, "layer_types": tuple(cfg.layer_types),
        "top_k": cfg.top_k, "experts_held": cfg.experts_held,
        "expert_offset": cfg.expert_offset,
        "embedding_multiplier": cfg.embed_mult,
        "residual_multiplier": cfg.resid_mult,
        "attention_multiplier": cfg.attn_mult,
        "logits_scaling": cfg.logits_scale}, **over)


@pytest.fixture(scope="module")
def model():
    cfg = sm.CONFIGS["nano"]
    return cfg, sm.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def model32():
    cfg = dataclasses.replace(sm.CONFIGS["nano"], dtype=jnp.float32,
                              param_dtype=jnp.float32)
    return cfg, sm.init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, **kw):
    cfg, params = model
    kw = dict(dict(slots=4, chunk=4, max_len=96,
                   prompt_buckets=(16, 32, 64), page_size=4, n_pages=120),
              **kw)
    return DecodeEngine(params, cfg, **kw)


@pytest.fixture(scope="module")
def engine32(model32):
    eng = _engine(model32, prompt_buckets=(16, 32))
    yield eng
    eng.shutdown()


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _answer(eng, prompt, max_new):
    return np.concatenate(list(eng.stream(prompt, max_new)))


def _together(eng, prompts, max_new):
    outs = [None] * len(prompts)

    def run(i):
        outs[i] = _answer(eng, prompts[i], max_new)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def _reference(model, tokens):
    """The reference's logits [S, rows] on one sequence."""
    cfg, params = model
    return np.asarray(REF.forward(REF.from_program(params),
                                  jnp.asarray(tokens)[None], _hp(cfg)))[0]


def _slot_arrays(cache, name):
    return {k: np.asarray(v) for k, v in cache.items()
            if k.startswith(name)}


# ---- the description: two kinds of layer, one cache spec

def test_nano_has_both_kinds_and_the_attention_layer_is_not_first():
    cfg = sm.CONFIGS["nano"]
    assert set(cfg.layer_types) == {"mamba", "attention"}
    assert cfg.layer_types[0] == "mamba" and cfg.attn_layers == (2,)
    assert cfg.ssm_layers == (0, 1, 3) and cfg.n_layer == 4
    # the scores' scale is the published constant, through the shared
    # attention's own head_dim ** -0.5
    assert sm.q_scale(cfg) * cfg.head_dim ** -0.5 \
        == pytest.approx(cfg.attn_mult, rel=1e-12)
    assert cfg.attn_mult != pytest.approx(cfg.head_dim ** -0.5)
    granite = dataclasses.replace(cfg, head_dim=128, attn_mult=0.0078125)
    assert sm.q_scale(granite) * 128 ** -0.5 == pytest.approx(1 / 128,
                                                              rel=1e-12)


def test_the_pool_its_costs_and_the_stats_read_one_cache_spec(model):
    """``cache_spec``: pages for the ONE attention layer
    (``CacheEntry.n_layer``), per-slot entries for the three Mamba
    layers, one array a layer, named by the layer's own index."""
    cfg, _ = model
    slots, n_pages, ps = 3, 10, 4
    assert serving.decode_programs(cfg) is sm
    spec = sm.cache_spec(cfg)
    cache = sm.init_paged_cache(cfg, slots, n_pages, ps)
    row = (1, n_pages, ps, cfg.n_kv_head, cfg.head_dim)
    want = {"k": row, "v": row}
    for l in (0, 1, 3):
        want[f"state{l}"] = (1, slots, cfg.ssm_heads, cfg.ssm_head_dim,
                             cfg.ssm_state)
        want[f"conv{l}"] = (1, slots, cfg.conv_size - 1, cfg.conv_dim)
    assert {k: v.shape for k, v in cache.items() if k != "pos"} == want
    assert cache["state0"].dtype == jnp.float32
    assert [spec.layers(n) for n in ("k", "v", "state1", "conv3")] \
        == [1, 1, 1, 1]
    assert spec.bytes_per_page(ps) == (cache["k"].nbytes
                                       + cache["v"].nbytes) // n_pages
    assert spec.bytes_per_slot() == sum(
        v.nbytes for k, v in cache.items()
        if k.startswith(("state", "conv"))) // slots
    assert sm.max_positions(cfg) == cfg.max_seq
    assert sm.jit_decode_chunk_slots_paged(
        cfg, 4, 4).__wrapped__.__name__ == "decode_chunk_slots_paged"
    assert sm.jit_prefill_into_slot_paged(
        cfg, 4).__wrapped__.__name__ == "prefill_into_slot_paged"
    # the mixer's sizes are the shared frames' own (Mamba2Sizes): no
    # multiplier of the other model's
    assert isinstance(cfg, ssm_hybrid.Mamba2Sizes)
    assert cfg.ssm_col_mults is None and cfg.ssm_out_mult == 1.0


@pytest.mark.parametrize("knobs,why", [
    (dict(prefix_cache=True), "a snapshot of the state"),
    (dict(kv_dtype="int8"), "no quantised layout"),
    (dict(tp=2), "no tensor-parallel programs"),
    (dict(spec_decode="ngram"), "does not roll back"),
    (dict(role="prefill"), "no part for the per-slot state"),
    (dict(role="decode"), "no part for the per-slot state")])
def test_what_the_model_does_not_get_raises_with_the_reason(model, knobs,
                                                            why):
    cfg, params = model
    assert set(sm.UNSUPPORTED) == {"prefix_cache", "spec_decode",
                                   "roles", "int8", "tp"}
    with pytest.raises(ValueError, match=why):
        DecodeEngine(params, cfg, slots=2, max_len=32, auto_start=False,
                     **knobs)


# ---- the programs against the reference, on logits

def _prefilled(model, prompts, bucket, slots, ps=4, into=None):
    """``prompts`` {slot: tokens} prefilled one by one."""
    cfg, params = model
    max_pages = 24
    cache = into if into is not None else sm.init_paged_cache(
        cfg, slots, slots * max_pages, ps)
    pt = np.arange(slots * max_pages, dtype=np.int32).reshape(slots, -1)
    first = {}
    for slot, prompt in prompts.items():
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        tok, cache, _ = sm.jit_prefill_into_slot_paged(cfg, ps)(
            params, cache, padded, np.int32(len(prompt)), np.int32(0),
            pt[slot], np.int32(serving.PT_SENTINEL), np.int32(slot),
            jax.random.PRNGKey(0))
        first[slot] = int(tok)
    return first, cache, pt


def test_lanes_of_different_lengths_beside_an_idle_and_a_parked_lane(
        model32):
    """Prefill, then decoding through pages and slots, against the
    reference's full forward pass ON LOGITS: lane 0 holds 37 tokens
    (chunks of 16, a bucket of 64), lane 2 holds 11, lane 1 was never
    used (idle) and lane 3 holds a prefilled sequence but is PARKED
    (not active): six steps with the mask ``[1, 0, 1, 0]``. The live
    lanes' logits are the reference's at every step; the idle and the
    parked lane's state, tail and pages come out TO THE BYTE as they
    went in, and their positions stand."""
    cfg, params = model32
    a, b, c = _prompts(cfg, (43, 17, 9), seed=3)
    want = {0: _reference(model32, a), 2: _reference(model32, b)}
    first, cache, pt = _prefilled(model32, {0: a[:37], 2: b[:11], 3: c},
                                  64, slots=4)
    assert first[0] == int(want[0][36].argmax())
    assert first[2] == int(want[2][10].argmax())
    before = jax.tree_util.tree_map(np.asarray, cache)
    step = jax.jit(functools.partial(sm._slot_decode_step_paged, cfg=cfg,
                                     page_size=4))
    active = np.array([True, False, True, False])
    for i in range(6):
        logits, cache, counts = step(
            params, cache,
            jnp.asarray([int(a[37 + i]), 0, int(b[11 + i]), 5]), active,
            jnp.asarray(pt))
        for lane, off, seq in ((0, 37, a), (2, 11, b)):
            ref = want[lane][off + i]
            assert np.abs(np.asarray(logits)[lane] - ref).max() \
                < REL * np.abs(ref).max(), (lane, i)
        # four expert layers a step, two live lanes' choices
        assert list(np.asarray(counts)[[0, 2, 4]]) == [
            4, 4 * 2 * cfg.top_k, 2]
    after = jax.tree_util.tree_map(np.asarray, cache)
    assert list(after["pos"]) == [43, 0, 17, 9]
    for name in ("state", "conv"):
        for key, arr in _slot_arrays(after, name).items():
            assert np.array_equal(arr[:, [1, 3]], before[key][:, [1, 3]])
            assert not np.array_equal(arr[:, 0], before[key][:, 0])
    # lane 3's pages (72..95) and the idle lane's (24..47): untouched
    for name in ("k", "v"):
        assert np.array_equal(after[name][:, 72:], before[name][:, 72:])
        assert np.array_equal(after[name][:, 24:48],
                              before[name][:, 24:48])


def test_the_rows_that_pad_a_prompt_advance_no_state_and_route_nowhere(
        model32):
    """A prompt of 21 tokens prefilled in a bucket of 32 and in one of
    64: the same first token, state, tail and pages; only its slot is
    written; the expert layer routed the 21 live rows alone."""
    cfg, params = model32
    prompt = _prompts(cfg, (21,), seed=2)[0]
    fa, a, _ = _prefilled(model32, {1: prompt}, 32, slots=3)
    fb, b, _ = _prefilled(model32, {1: prompt}, 64, slots=3)
    assert fa == fb
    assert fa[1] == int(_reference(model32, prompt)[-1].argmax())
    for name in ("state", "conv", "k", "v"):
        for key, arr in _slot_arrays(a, name).items():
            assert np.abs(arr - np.asarray(b[key])).max() < 1e-5, key
    for key, arr in _slot_arrays(a, "state").items():
        assert np.abs(arr[0, 1]).max() > 0
        assert np.abs(arr[0, [0, 2]]).max() == 0
    rows = np.asarray(a["k"])[0, 24:48].reshape(96, -1)
    assert np.abs(rows[:21]).min(axis=-1).min() > 0
    assert np.abs(rows[21:]).max() == 0


def test_a_grouped_prefill_of_two_prompts_is_two_single_ones(model32):
    """The prompts of one chunk boundary in ONE launch (rows end to
    end, buckets 32 and 16, widest first) against one prefill each:
    first tokens, the two slots' state and tail, the pages and the
    positions."""
    cfg, params = model32
    p, q = _prompts(cfg, (27, 13), seed=5)
    first, single, pt = _prefilled(model32, {2: p}, 32, slots=4)
    f2, single, _ = _prefilled(model32, {0: q}, 16, slots=4, into=single)
    first.update(f2)
    cache = sm.init_paged_cache(cfg, 4, 4 * 24, 4)
    tokens = []
    for prompt, bucket in ((p, 32), (q, 16)):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        tokens.append(padded)
    tok, group, _ = sm.jit_prefill_into_slot_paged(cfg, 4)(
        params, cache, tuple(tokens), np.array([27, 13], np.int32),
        np.zeros((2,), np.int32), pt[[2, 0]],
        np.full((2,), serving.PT_SENTINEL, np.int32),
        np.array([2, 0], np.int32), np.zeros((2, 2), np.uint32))
    assert [int(t) for t in tok] == [first[2], first[0]]
    assert list(np.asarray(group["pos"])) == [13, 0, 27, 0]
    for key in single:
        want = np.asarray(single[key], np.float32)
        assert np.abs(np.asarray(group[key], np.float32) - want).max() \
            <= 1e-5 * max(1.0, np.abs(want).max()), key


def test_the_engines_tokens_are_the_references_best_at_every_position(
        model32, engine32):
    """Through the ENGINE (admission, grouped prefills, the chunk
    program, pages and slots): five requests of different lengths at
    once. Every token served at temperature 0 is, teacher-forced along
    the request's own answer, the reference's best within ``REL`` of
    its largest logit; and a request is answered the same alone (four
    slots: the fifth request waits for a lane and reuses its slot)."""
    cfg, _ = model32
    prompts = _prompts(cfg, (9, 30, 17, 12, 26), seed=1)
    outs = _together(engine32, prompts, 14)
    for prompt, out in zip(prompts, outs):
        assert len(out) == 14
        ref = _reference(model32, np.concatenate([prompt, out[:-1]]))
        at = ref[len(prompt) - 1:]
        gap = at.max(axis=-1) - at[np.arange(14), out]
        assert gap.max() <= REL * np.abs(at).max()
    assert np.array_equal(_answer(engine32, prompts[1], 14), outs[1])
    st = engine32.stats()
    assert st["prefill_launches"] <= st["prefills"]


def test_a_reused_slot_answers_as_a_fresh_engine_does(model):
    """Slot hygiene: ONE slot serves a long request and then a shorter
    one, which must be answered as by an engine that never saw the
    first: the second prefill rebuilds state and tail from zero."""
    cfg, _ = model
    long_, short = _prompts(cfg, (41, 11), seed=7)
    fresh = _engine(model, slots=1, prompt_buckets=(16, 64))
    try:
        want = _answer(fresh, short, 12)
    finally:
        fresh.shutdown()
    eng = _engine(model, slots=1, prompt_buckets=(16, 64))
    try:
        _answer(eng, long_, 20)
        assert np.array_equal(_answer(eng, short, 12), want)
    finally:
        eng.shutdown()


# ---- counters, kernels, spans: the hooks that exist

def test_the_counters_and_the_spans_are_the_engines(model):
    """``stats()`` carries the six counters by the names that exist
    (``moe_steps`` counts expert LAYERS run: ``n_layer`` a step),
    ``state_bytes`` and the kernel's mode through the hooks that exist;
    a traced request's spans are the engine's (``engine.admission``,
    ``engine.prefill`` with ``group``, ``decode.chunk``; the driver's
    ``engine.decode``)."""
    cfg, _ = model
    assert sm.STEP_COUNTERS == (
        "moe_steps", "moe_experts_touched_sum", "moe_tokens_here_sum",
        "moe_expert_peak_sum", "state_lanes_sum", "gqa_tokens_read_sum")
    eng = _engine(model, prompt_buckets=(16,))
    try:
        report = eng.warm_up()
        assert report["attn_kernel_mode"] == "interpret"
        before = eng.stats()
        tracing.drain()
        tracing.enable()
        try:
            ctx = {"trace_id": "ab" * 16, "span_id": "cd" * 8}
            n, new = 13, 9
            lane = eng.submit(_prompts(cfg, (n,), seed=5)[0], new,
                              trace_ctx=ctx)
            from ray_tpu.serve.batching import _EngineStream

            assert len(np.concatenate(list(_EngineStream(lane)))) == new
            spans = tracing.local_spans()
        finally:
            tracing.disable()
            tracing.drain()
        after = eng.stats()
    finally:
        eng.shutdown()
    steps = (after["dispatches"] - before["dispatches"]) * eng.chunk
    d = {k: after[k] - before[k] for k in sm.STEP_COUNTERS}
    assert d["moe_steps"] == cfg.n_layer * steps
    assert d["state_lanes_sum"] == steps
    assert d["moe_tokens_here_sum"] == cfg.n_layer * steps * cfg.top_k
    assert d["moe_experts_touched_sum"] == d["moe_tokens_here_sum"]
    assert d["moe_expert_peak_sum"] == d["moe_steps"]
    ps = 4                      # ONE attention layer's live pages
    assert d["gqa_tokens_read_sum"] == sum(
        -(-(n + i + 1) // ps) * ps for i in range(steps))
    per_slot = len(cfg.ssm_layers) * (
        cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
        + (cfg.conv_size - 1) * cfg.conv_dim * 2)
    assert after["state_bytes_per_slot"] == per_slot
    assert after["state_bytes"] == 4 * per_slot
    assert after["kv_bytes_per_token"] \
        == 2 * cfg.n_kv_head * cfg.head_dim * 2
    assert after["attn_kernel_dispatches"] > 0
    mine = sorted((s for s in spans if s["trace_id"] == ctx["trace_id"]),
                  key=lambda s: s["mono_ns"][0])
    names = [s["name"] for s in mine]
    assert names[:2] == ["engine.admission", "engine.prefill"]
    assert set(names[2:]) == {"decode.chunk"}
    assert mine[1]["attrs"]["group"] == 1
    assert mine[1]["attrs"]["bucket"] == 16
    drv = [s["name"] for s in spans if s["kind"] == "driver"]
    assert "engine.decode" in drv and "engine.prefill" in drv


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
def test_the_step_takes_both_kernels_by_shape_and_both_bodies_agree(
        model32, monkeypatch, kernel):
    """No knob: the step asks ``ssm_hybrid.state_kernel`` of the
    state's head and ``kda_moe.gqa_kernel`` of its page and heads. One
    recurrence kernel a MAMBA layer and one attention kernel an
    ATTENTION layer (their logits against the reference: the test of
    the four lanes, above); with both steered off the description reads
    a program without a kernel (the two XLA bodies are the oracles of
    ``tests/test_ssm_state_kernel.py`` and
    ``tests/test_gqa_attention_kernel.py``, at this model's shapes
    too)."""
    cfg, params = model32
    prompt = _prompts(cfg, (13,), seed=9)[0]
    _, cache, pt = _prefilled(model32, {1: prompt}, 16, slots=3)
    if not kernel:
        monkeypatch.setattr(kda_moe, "gqa_kernel", lambda *a, **k: False)
        monkeypatch.setattr(ssm_hybrid, "state_kernel", lambda m: False)
    assert sm.decode_attention_fused(cfg, 4) is kernel
    text = str(jax.make_jaxpr(functools.partial(
        sm._slot_decode_step_paged, cfg=cfg, page_size=4))(
        params, cache, jnp.zeros((3,), jnp.int32), np.zeros((3,), bool),
        jnp.asarray(pt)))
    assert text.count("pallas_call") == (cfg.n_layer if kernel else 0)


# ---- one lane tile a row: the state held N-major in the pool

@pytest.fixture(scope="module")
def model_tile():
    """granite-4.0-h-small's heads, ``[64, 128]`` in one group (four of
    them): a row of state is ONE lane tile, so the pool holds the state
    ``N``-major, two heads side by side on lanes."""
    cfg = dataclasses.replace(sm.CONFIGS["nano"], dtype=jnp.float32,
                              param_dtype=jnp.float32, ssm_heads=4,
                              ssm_head_dim=64, ssm_state=128)
    return cfg, sm.init_params(jax.random.PRNGKey(1), cfg)


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["one-prompt", "group-of-two"])
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
def test_a_prefill_into_the_n_major_entry_then_decoding_is_forward(
        model_tile, monkeypatch, kernel, grouped):
    """``put_slot`` lays a prefill's ``S_end`` as the entry holds it
    (``[H / 2, N, 2 P]``), from a single prefill and from a group of
    two, and ``ssm_decode`` steps it there, with the kernel of that
    layout and (``state_kernel`` steered off) with the XLA body through
    the accessor: the logits are ``forward``'s and the reference's at
    every step, the parked lane's state the bits that went in."""
    cfg, params = model_tile
    assert ssm_hybrid.lane_heads(cfg) == 2
    if not kernel:
        monkeypatch.setattr(ssm_hybrid, "state_kernel", lambda m: False)
    a, b, c = _prompts(cfg, (19, 15, 9), seed=11)
    want = {0: _reference(model_tile, a), 2: _reference(model_tile, b)}
    own = {0: np.asarray(sm.forward(params, jnp.asarray(a)[None], cfg))[0],
           2: np.asarray(sm.forward(params, jnp.asarray(b)[None], cfg))[0]}
    if grouped:
        _, cache, pt = _prefilled(model_tile, {1: c}, 16, slots=3)
        tokens = []
        for prompt in (a[:13], b[:9]):
            padded = np.zeros((1, 16), np.int32)
            padded[0, :len(prompt)] = prompt
            tokens.append(padded)
        _, cache, _ = sm.jit_prefill_into_slot_paged(cfg, 4)(
            params, cache, tuple(tokens), np.array([13, 9], np.int32),
            np.zeros((2,), np.int32), pt[[0, 2]],
            np.full((2,), serving.PT_SENTINEL, np.int32),
            np.array([0, 2], np.int32), np.zeros((2, 2), np.uint32))
    else:
        _, cache, pt = _prefilled(model_tile, {0: a[:13], 2: b[:9], 1: c},
                                  16, slots=3)
    for l in cfg.ssm_layers:
        assert cache[f"state{l}"].shape == (1, 3, 2, 128, 128)
    before = jax.tree_util.tree_map(np.asarray, cache)
    step = jax.jit(functools.partial(sm._slot_decode_step_paged, cfg=cfg,
                                     page_size=4))
    active = np.array([True, False, True])
    for i in range(5):
        logits, cache, _ = step(
            params, cache, jnp.asarray([int(a[13 + i]), 3, int(b[9 + i])]),
            active, jnp.asarray(pt))
        for lane, off in ((0, 13), (2, 9)):
            for ref in (want[lane][off + i], own[lane][off + i]):
                assert np.abs(np.asarray(logits)[lane] - ref).max() \
                    < REL * np.abs(ref).max(), (lane, i)
    for key, arr in _slot_arrays(cache, "state").items():
        assert np.array_equal(arr[:, 1], before[key][:, 1])
        assert not np.array_equal(arr[:, 0], before[key][:, 0])


# ---- the third router

def test_route_topk_softmax_is_the_references_router():
    """``moe.route_topk_softmax`` against the reference's ``select``:
    the same k experts (ties apart: rows whose margin is under 1e-5
    are not compared) and the same weights to float32 rounding; the
    weights sum to one; and they are the softmax over the WHOLE width
    renormalised over the chosen ones, in another order of operations:
    the two differ by at most 4e-7 (a few float32 roundings of a number
    below one), where sigmoid scores normalised differ by 0.05 and
    more."""
    rng = np.random.default_rng(0)
    T, d, E, k = 256, 64, 72, 10
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, E)) * 2 / 8, jnp.float32)
    ids, w = moe.route_topk_softmax(x, router, top_k=k, dtype=jnp.float32)
    ids, w = np.asarray(ids), np.asarray(w)
    assert ids.shape == w.shape == (T, k) and ids.dtype == np.int32
    assert np.abs(w.sum(-1) - 1).max() < 1e-6
    assert (np.diff(w, axis=-1) <= 0).all()            # best first
    with jax.default_matmul_precision("highest"):
        want, margin = REF.select(
            x, router, {"top_k": k, "expert_offset": 0,
                        "experts_held": E})
    want, clear = np.asarray(want), np.asarray(margin) > 1e-5
    assert clear.mean() > 0.99
    got = np.zeros((T, E), np.float32)
    np.put_along_axis(got, ids, w, axis=1)
    assert np.abs(got - want)[clear].max() < 2e-6
    # the other order of operations: softmax over all 72, renormalised
    logits = np.asarray(jnp.dot(x, router, precision="highest"),
                        np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.take_along_axis(p, ids, axis=1)
    assert np.abs(chosen / chosen.sum(-1, keepdims=True) - w).max() < 4e-7
    s = 1 / (1 + np.exp(-np.take_along_axis(logits, ids, axis=1)))
    assert np.abs(s / s.sum(-1, keepdims=True) - w).max() > 0.05
    # bfloat16 inputs: the same function, logits summed in float32
    ids16, w16 = moe.route_topk_softmax(x, router, top_k=k,
                                        dtype=jnp.bfloat16)
    assert w16.dtype == jnp.float32
    assert (np.asarray(ids16)[:, 0] == ids[:, 0]).mean() > 0.9

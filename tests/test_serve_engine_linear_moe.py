"""The third block through the SAME ``DecodeEngine``: the hybrid
linear-attention expert decoder of ``ray_tpu/models/kda_moe.py``
(ROADMAP M4 in part, M2's grouped KV heads, D1). Three layers in four
keep a recurrent state and a convolution's tail PER SLOT beside the one
attention layer's key/value pages; the engine takes that from the ONE
cache description (``models/serving.py``: ``per="slot"`` entries, a
layer count an entry), rebuilds a slot's state in every prefill into
it, leaves an idle or parked lane's alone, and refuses what a state
that belongs to a slot cannot have. The comparison with the plain
reference is ``tests/perf/test_perf_solar_open2.py``'s."""
import dataclasses
import functools
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt, gpt_decode, kda_moe, mla_moe, serving
from ray_tpu.serve.engine import DecodeEngine


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(kda_moe.CONFIGS["nano"], experts_held=8)
    return cfg, kda_moe.init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, **kw):
    cfg, params = model
    kw = dict(dict(slots=4, chunk=4, max_len=96,
                   prompt_buckets=(16, 32, 64), page_size=4, n_pages=120),
              **kw)
    return DecodeEngine(params, cfg, **kw)


@pytest.fixture(scope="module")
def engine(model):
    eng = _engine(model)
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


# ---- the linear-attention mixer's two forms

def _kda_inputs(T, H, D, seed, g_low=3.0):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(T, H, D))) * D ** -0.5
    k = unit(rng.normal(size=(T, H, D)))
    v = rng.normal(size=(T, H, D))
    g = -rng.uniform(0.001, g_low, size=(T, H, D))
    beta = rng.uniform(0.0, 2.0, size=(T, H))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("T,chunk,g_low", [
    (50, 16, 3.0),       # a multiple of neither; alpha down to 0.05
    (64, 64, 0.7),       # one whole chunk; alpha in (0.5, 0.999)
    (96, 32, 30.0),      # channels that forget within a token: e^-G
                         # over a chunk would overflow float32
    (7, 8, 1.0)])        # shorter than a chunk
def test_the_chunked_form_is_the_recurrence(T, chunk, g_low):
    """Prefill's chunked form (the within-chunk triangular solve and the
    state passed between chunks) against the recurrence one token at a
    time, float32: outputs and final state to 1e-5."""
    H, D = 3, 16
    q, k, v, g, beta = _kda_inputs(T, H, D, T, g_low)
    S = jnp.zeros((1, H, D, D), jnp.float32)
    want = []
    for t in range(T):
        S, o = kda_moe._kda_step(S, q[None, t], k[None, t], v[None, t],
                                 g[None, t], beta[None, t])
        want.append(o[0])
    pad = -T % chunk

    def padded(a):
        return jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:])])

    got, S_end = kda_moe._kda_chunked(
        *(padded(a) for a in (q, k, v, g, beta)),
        jnp.zeros((H, D, D), jnp.float32), chunk)
    assert float(jnp.abs(got[:T] - jnp.stack(want)).max()) < 1e-5
    # the padding (beta 0, g 0) advanced nothing
    assert float(jnp.abs(S_end - S[0]).max()) < 1e-5


def test_a_step_is_the_published_recurrence():
    """``_kda_step`` against ``S' = (I - beta k k^T) Diag(alpha) S + beta
    k v^T``, ``o = S'^T q`` written out in numpy."""
    H, D = 2, 8
    q, k, v, g, beta = (np.asarray(a) for a in _kda_inputs(5, H, D, 3))
    S = np.zeros((H, D, D))
    Sj = jnp.zeros((1, H, D, D), jnp.float32)
    for t in range(5):
        for h in range(H):
            S[h] = (np.eye(D) - beta[t, h] * np.outer(k[t, h], k[t, h])) \
                @ np.diag(np.exp(g[t, h])) @ S[h] \
                + beta[t, h] * np.outer(k[t, h], v[t, h])
        Sj, o = kda_moe._kda_step(Sj, *(jnp.asarray(a[None, t])
                                        for a in (q, k, v, g, beta)))
        want = np.einsum("hkv,hk->hv", S, q[t])
        assert np.abs(np.asarray(o[0]) - want).max() < 1e-5
    assert np.abs(np.asarray(Sj[0]) - S).max() < 1e-5


# ---- the programs: pages and per-slot state

def _prefilled(model, prompt, bucket, slots=3, slot=1, ps=4):
    cfg, params = model
    max_pages = 24
    cache = kda_moe.init_paged_cache(cfg, slots, slots * max_pages, ps)
    pt = np.arange(slots * max_pages, dtype=np.int32).reshape(slots, -1)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    tok, cache, _ = kda_moe.jit_prefill_into_slot_paged(cfg, ps)(
        params, cache, padded, np.int32(len(prompt)), np.int32(0),
        pt[slot], np.int32(serving.PT_SENTINEL), np.int32(slot),
        jax.random.PRNGKey(0))
    return int(tok), cache, pt


def test_the_rows_that_pad_a_prompt_advance_no_state_and_no_tail(model):
    """A prompt of 21 tokens (a multiple of neither the chunk 64, the
    page 4 nor its bucket) prefilled in a bucket of 32 and in one of 64:
    the same first token, state, convolution tail and pages; and the
    whole forward pass over the 21 tokens alone agrees."""
    cfg, params = model
    prompt = _prompts(cfg, (21,), seed=2)[0]
    tok_a, a, _ = _prefilled(model, prompt, 32)
    tok_b, b, _ = _prefilled(model, prompt, 64)
    assert tok_a == tok_b
    for name in ("state", "conv", "pos"):
        assert float(jnp.abs(a[name].astype(jnp.float32)
                             - b[name].astype(jnp.float32)).max()) < 1e-5
    live = np.asarray(a["k"].astype(jnp.float32))[0, 24:24 + 6]
    assert np.abs(live - np.asarray(
        b["k"].astype(jnp.float32))[0, 24:24 + 6]).max() < 1e-5
    assert np.abs(live.reshape(24, -1)[:21]).min(axis=-1).max() > 0
    assert np.abs(live.reshape(24, -1)[21:]).max() == 0   # no pad row
    # only slot 1 was written; the tail holds the last three rows
    state = np.asarray(a["state"])
    assert np.abs(state[:, 1]).max() > 0
    assert np.abs(state[:, [0, 2]]).max() == 0
    assert list(np.asarray(a["pos"])) == [0, 21, 0]
    logits = kda_moe.forward(params, jnp.asarray(prompt)[None], cfg)
    assert int(jnp.argmax(logits[0, -1])) == tok_a


def test_an_idle_or_parked_lanes_state_does_not_change(model):
    """The dispatch mask: a lane that is not active (idle, or parked for
    pages by ``_cover_pages``) neither writes a page, advances its
    position, nor touches its state and convolution tail; the active
    lane beside it does all four."""
    cfg, params = model
    prompt = _prompts(cfg, (13,), seed=6)[0]
    _, cache, pt = _prefilled(model, prompt, 16, slot=0)
    before = jax.tree_util.tree_map(np.asarray, cache)
    _, cache2, pt = _prefilled(model, prompt, 16, slot=1)
    cache = dict(cache, **{k: cache[k].at[:, 1].set(cache2[k][:, 1])
                           for k in ("state", "conv")})
    cache["pos"] = cache["pos"].at[1].set(13)
    for name in ("k", "v"):
        cache[name] = cache[name].at[:, 24:48].set(cache2[name][:, 24:48])
    held = jax.tree_util.tree_map(np.asarray, cache)
    step = jax.jit(functools.partial(kda_moe._slot_decode_step_paged,
                                     cfg=cfg, page_size=4))
    active = np.array([True, False, False])
    _, after, counts = step(params, cache, jnp.asarray([5, 7, 9]), active,
                            jnp.asarray(pt))
    after = jax.tree_util.tree_map(np.asarray, after)
    for name in ("state", "conv"):
        assert np.array_equal(after[name][:, 1:], held[name][:, 1:])
        assert not np.array_equal(after[name][:, 0], before[name][:, 0])
    assert np.array_equal(after["k"][:, 24:], held["k"][:, 24:])
    assert list(after["pos"]) == [14, 13, 0]
    assert int(counts[4]) == 1                  # one live lane


def test_a_requests_tokens_are_the_same_alone_and_among_others(model,
                                                               engine):
    """A sequence's state is its slot's and the expert layer is
    dropless: what a request is answered does not depend on who shares
    its batch, nor on which slot it lands in."""
    cfg, _ = model
    prompts = _prompts(cfg, (9, 17, 30, 12, 33, 21))
    alone = _answer(engine, prompts[0], 14)
    among = _together(engine, prompts, 14)
    assert len(alone) == 14 and np.array_equal(alone, among[0])
    for p, want in zip(prompts[1:3], among[1:3]):
        assert np.array_equal(_answer(engine, p, 14), want)


def test_a_reused_slot_answers_as_a_fresh_engine_does(model):
    """Slot hygiene: ONE slot serves a long request and then a shorter
    one, which must be answered as by an engine that never saw the
    first: the second prefill rebuilds state and tail from zero."""
    cfg, _ = model
    long_, short = _prompts(cfg, (41, 11), seed=7)
    fresh = _engine(model, slots=1)
    try:
        want = _answer(fresh, short, 12)
    finally:
        fresh.shutdown()
    eng = _engine(model, slots=1)
    try:
        _answer(eng, long_, 20)
        assert np.array_equal(_answer(eng, short, 12), want)
    finally:
        eng.shutdown()


def test_the_hygiene_test_sees_a_program_that_does_not_rebuild(
        model, monkeypatch):
    """The control: with the prefill's write of state and tail taken
    out (what a program that forgot the slot's residue would be), the
    same reuse answers differently. Knobs of its own (chunk 3, page 8):
    a built program is cached by its knobs."""
    cfg, _ = model
    long_, short = _prompts(cfg, (41, 11), seed=7)
    kw = dict(slots=1, chunk=3, page_size=8, n_pages=40)
    fresh = _engine(model, **kw)
    try:
        want = _answer(fresh, short, 12)
    finally:
        fresh.shutdown()
    monkeypatch.setattr(kda_moe, "_put", lambda pool, rows, *at: pool)
    kda_moe.jit_prefill_into_slot_paged.cache_clear()
    eng = _engine(model, **kw)
    try:
        _answer(eng, long_, 20)
        assert not np.array_equal(_answer(eng, short, 12), want)
    finally:
        eng.shutdown()
        kda_moe.jit_prefill_into_slot_paged.cache_clear()


def test_a_lane_preempted_by_recompute_resumes_as_if_it_never_stopped(
        model):
    """A starved pool: lanes park when the allocator runs dry and, on
    deadlock, the youngest is preempted BY RECOMPUTE: its pages free,
    its request requeues, and the prefill that readmits it rebuilds
    pages AND state together; the replay suppresses what was delivered.
    Every stream is what an unstarved engine gives."""
    cfg, _ = model
    prompts = _prompts(cfg, (16,) * 6, seed=4)
    mns = [24, 20, 28, 16, 24, 20]
    ref = _engine(model, prompt_buckets=(16,), page_size=8, n_pages=60)
    try:
        want = [_answer(ref, p, m) for p, m in zip(prompts, mns)]
    finally:
        ref.shutdown()
    eng = _engine(model, prompt_buckets=(16,), page_size=8, n_pages=12)
    try:
        outs = [None] * 6

        def run(i):
            outs[i] = _answer(eng, prompts[i], mns[i])

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = eng.stats()
        assert st["completed"] == 6
        assert st["lane_parks"] > 0 and st["preempted"] > 0, st
        for got, w in zip(outs, want):
            assert np.array_equal(got, w)
        assert st["pages_free"] == 12
    finally:
        eng.shutdown()


def test_warm_up_leaves_nothing_a_request_reads(model):
    cfg, _ = model
    prompt = _prompts(cfg, (19,), seed=8)[0]
    a = _engine(model, slots=2)
    b = _engine(model, slots=2)
    try:
        report = b.warm_up()
        # a bucket's program for one prompt and a pair of buckets'
        # for the group of one chunk boundary (it writes the first two
        # slots' state, which a prefill rebuilds before a request reads)
        assert set(report["programs"]) == {
            "prefill_16", "prefill_32", "prefill_64", "chunk"} | {
            f"prefill_{a}+{b}" for a in (32, 64) for b in (32, 64)
            if a >= b}         # the two widest buckets group
        # the recurrence's kernel, interpreted off the TPU
        assert report["attn_kernel_mode"] == "interpret"
        assert np.array_equal(_answer(a, prompt, 9), _answer(b, prompt, 9))
        assert b.stats()["attn_kernel_dispatches"] > 0
    finally:
        a.shutdown()
        b.shutdown()


def test_the_live_lanes_come_out_with_the_tokens(model, engine):
    cfg, _ = model
    before = engine.stats()
    _answer(engine, _prompts(cfg, (13,), seed=5)[0], 9)
    after = engine.stats()
    moved = {k: after[k] - before[k] for k in kda_moe.STEP_COUNTERS}
    launches = after["dispatches"] - before["dispatches"]
    assert kda_moe.STEP_COUNTERS[:4] == mla_moe.STEP_COUNTERS
    assert moved["moe_steps"] == launches * engine.chunk * cfg.n_layer
    # one lane was live in every step of every launch
    assert moved["state_lanes_sum"] == launches * engine.chunk
    assert 0 < moved["moe_experts_touched_sum"] \
        <= moved["moe_tokens_here_sum"]


def test_the_positions_attention_fetched_come_out_with_the_tokens(model,
                                                                  engine):
    """ISSUE 46: ``stats()`` carries ``gqa_tokens_read_sum``, the
    positions whose keys and values the steps' attention fetched. With
    the kernel (interpreted here) that is the one live lane's tokens in
    whole pages, step by step, far below the ``slots x max_len`` a
    step the gather copies."""
    cfg, _ = model
    assert kda_moe.STEP_COUNTERS[4:] == ("state_lanes_sum",
                                         "gqa_tokens_read_sum")
    before = engine.stats()
    assert "gqa_tokens_read_sum" in before
    n, new = 13, 9
    _answer(engine, _prompts(cfg, (n,), seed=5)[0], new)
    after = engine.stats()
    steps = (after["dispatches"] - before["dispatches"]) * engine.chunk
    moved = after["gqa_tokens_read_sum"] - before["gqa_tokens_read_sum"]
    ps = 4
    # the prefill made the first token; decode step i writes position
    # n + i and attends 0..n + i (8 steps in two chunks of 4)
    assert moved == cfg.n_gqa * sum(-(-(n + i + 1) // ps) * ps
                                    for i in range(steps))
    assert moved < 4 * 96 * steps              # slots x max_len x steps


# ---- what the model does not get

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
    with pytest.raises(ValueError, match=why):
        DecodeEngine(params, cfg, slots=2, max_len=32, auto_start=False,
                     **knobs)


def test_the_default_follows_what_the_model_can_have(model):
    """``prefix_cache`` left out: no prefix cache for a description
    with per-slot state, one for the two that keep all in pages; said
    aloud it raises, on the config plane too."""
    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=2, max_len=32, page_size=4,
                       auto_start=False)
    assert eng._prefix is None
    assert "prefix_evictions" not in eng.stats()
    with pytest.raises(ValueError, match="a snapshot of the state"):
        eng.ensure_paging(prefix_cache=True)
    assert eng.ensure_paging(prefix_cache=False) is eng
    with pytest.raises(ValueError, match="does not roll back"):
        eng.ensure_spec(spec_decode="ngram")
    with pytest.raises(ValueError, match="no part for the per-slot"):
        eng.ensure_role(role="prefill")
    for c, p in ((gpt.CONFIGS["nano"], None), (mla_moe.CONFIGS["nano"],
                                               None)):
        mod = serving.decode_programs(c)
        p = (gpt if mod is gpt_decode else mla_moe).init_params(
            jax.random.PRNGKey(0), c)
        other = DecodeEngine(p, c, slots=2, max_len=32, page_size=4,
                             auto_start=False)
        assert other._prefix is not None
        assert "prefix_cache" not in mod.UNSUPPORTED


# ---- the ONE cache description

@pytest.mark.parametrize("which", ["gpt-fp", "gpt-int8", "latent",
                                   "linear"])
def test_the_pool_its_costs_and_the_stats_read_one_cache_spec(model,
                                                              which):
    """``cache_spec`` is the ONE place the pool's shapes come from, for
    all three models: pages AND per-slot entries, each with its own
    count of layers. The GPT-2 block's and the latent decoder's read as
    before, to the byte."""
    slots, n_pages, ps = 3, 10, 4
    if which == "linear":
        cfg, params = model
        desc, kv = kda_moe, "fp"
        W, D, H = cfg.kda_width, cfg.kda_head_dim, cfg.kda_heads
        assert (cfg.n_gqa, cfg.n_kda) == (1, 3)
        row = (1, n_pages, ps, cfg.n_kv_head, cfg.head_dim)
        want = {"k": row, "v": row, "state": (3, slots, H, D, D),
                "conv": (3, slots, cfg.conv_size - 1, 3 * W)}
        per_token = 2 * cfg.n_kv_head * cfg.head_dim * 2
        per_slot = 3 * (H * D * D * 4 + (cfg.conv_size - 1) * 3 * W * 2)
    elif which == "latent":
        cfg = mla_moe.CONFIGS["nano"]
        params = mla_moe.init_params(jax.random.PRNGKey(0), cfg)
        desc, kv = mla_moe, "fp"
        want = {"latent": (cfg.n_layer, n_pages, ps, cfg.latent_row)}
        per_token, per_slot = cfg.n_layer * cfg.latent_row * 2, 0
    else:
        cfg = gpt.CONFIGS["nano"]
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        desc, kv = gpt_decode, which[4:]
        row = (cfg.n_layer, n_pages, ps, cfg.n_head, cfg.head_dim)
        want = {"k": row, "v": row}
        per_token, per_slot = 2 * cfg.n_layer * cfg.d_model * 2, 0
        if kv == "int8":
            want.update(ks=(cfg.n_layer, n_pages, cfg.n_head),
                        vs=(cfg.n_layer, n_pages, cfg.n_head))
            per_token = 2 * cfg.n_layer * (cfg.d_model
                                           + 4 * cfg.n_head / ps)
    assert serving.decode_programs(cfg) is desc
    spec = desc.cache_spec(cfg, kv)
    cache = desc.init_paged_cache(cfg, slots, n_pages, ps, kv)
    assert {k: v.shape for k, v in cache.items() if k != "pos"} == want
    assert cache["pos"].shape == (slots,)
    paged = sum(v.nbytes for k, v in cache.items()
                if k not in ("pos", "state", "conv"))
    assert desc.kv_bytes_per_page(cfg, ps, kv) == spec.bytes_per_page(ps) \
        == paged // n_pages == per_token * ps
    assert spec.bytes_per_slot() == per_slot == sum(
        v.nbytes for k, v in cache.items()
        if k in ("state", "conv")) // slots
    eng = DecodeEngine(params, cfg, slots=2, max_len=32, page_size=ps,
                       kv_dtype=kv, auto_start=False)
    st = eng.stats()
    assert st["kv_bytes_per_token"] == per_token
    assert st["state_bytes_per_slot"] == per_slot
    assert st["state_bytes"] == 2 * per_slot


def test_an_entry_counts_its_own_layers():
    spec = serving.CacheSpec(5, (
        serving.CacheEntry("a", "token", (2, 8), jnp.bfloat16),
        serving.CacheEntry("b", "page", (2,), jnp.float32),
        serving.CacheEntry("c", "slot", (4, 4), jnp.float32, 3),
        serving.CacheEntry("d", "token", (8,), jnp.int8, 2)))
    assert [spec.layers(n) for n in "abcd"] == [5, 5, 3, 2]
    assert spec.bytes_per_page(16) == 5 * (16 * 16 * 2 + 2 * 4) \
        + 2 * 16 * 8
    assert spec.bytes_per_slot() == 3 * 64
    assert spec.token_shape("d", 7) == (2, 7, 8)
    pool = serving.init_paged_pool(spec, 6, 9, 16)
    assert pool["c"].shape == (3, 6, 4, 4)
    assert pool["d"].shape == (2, 9, 16, 8)
    assert pool["b"].shape == (5, 9, 2)


def test_the_programs_keep_the_names_a_trace_shows(model):
    cfg, _ = model
    assert kda_moe.jit_decode_chunk_slots_paged(
        cfg, 4, 4).__wrapped__.__name__ == "decode_chunk_slots_paged"
    assert kda_moe.jit_prefill_into_slot_paged(
        cfg, 4).__wrapped__.__name__ == "prefill_into_slot_paged"
    assert kda_moe.decode_attention_fused(cfg, 4)     # interpreted here


def test_deferred_delivery_hands_every_lane_the_walks_messages(model,
                                                               engine):
    """ISSUE 43 through this model's programs: the slices, the ends and
    a replay's ``skip`` on the deferring engine are those of a walk that
    hands over at once, and a lone request's end waits for nobody."""
    from test_serve_engine_deliver import check_deferred_against_at_once

    cfg, _ = model
    check_deferred_against_at_once(
        engine, _engine(model),
        _prompts(cfg, (9, 17, 30, 12), seed=5))

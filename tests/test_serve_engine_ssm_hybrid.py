"""The fifth block through the SAME ``DecodeEngine``: the parallel
hybrid decoder of ``ray_tpu/models/ssm_hybrid.py`` (ROADMAP M7 in
part). EVERY layer keeps a state-space state and a convolution's tail
PER SLOT beside its rotary attention's key/value pages; the engine
takes that from the ONE cache description (``models/serving.py``:
``per="slot"`` entries beside ``per="token"`` ones, all counting every
layer), rebuilds a slot's state in every prefill into it, leaves an
idle or parked lane's alone, and refuses what a state that belongs to a
slot cannot have. The comparison with the plain reference is
``tests/perf/test_perf_falcon_h1.py``'s."""
import dataclasses
import functools
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import kda_moe, serving, ssm_hybrid
from ray_tpu.serve.engine import DecodeEngine


@pytest.fixture(scope="module")
def model():
    cfg = ssm_hybrid.CONFIGS["nano"]
    return cfg, ssm_hybrid.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def model32():
    cfg = dataclasses.replace(ssm_hybrid.CONFIGS["nano"],
                              dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, ssm_hybrid.init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, **kw):
    cfg, params = model
    kw = dict(dict(slots=4, chunk=4, max_len=96,
                   prompt_buckets=(16, 32, 64), page_size=4, n_pages=120),
              **kw)
    return DecodeEngine(params, cfg, **kw)


@pytest.fixture(scope="module")
def engine(model):
    eng = _engine(model, slots=8, n_pages=200)
    yield eng
    eng.shutdown()


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _slots(cache, name):
    """A per-slot entry of every layer, stacked: ``[L, slots, ...]``
    (the pool keeps one array a layer: ``cache_spec``)."""
    n = sum(k.startswith(name) for k in cache)
    return np.stack([np.asarray(cache[ssm_hybrid.slot_entry(name, l)],
                                np.float32)[0] for l in range(n)])


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


# ---- the state-space mixer's two forms

def _ssm_inputs(T, H, P, N, seed, g_low=3.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(T, H, P))
    B = rng.normal(size=(T, H, N))
    C = rng.normal(size=(T, H, N))
    dt = rng.uniform(0.001, 0.3, size=(T, H))
    g = -rng.uniform(0.001, g_low, size=(T, H))
    return tuple(jnp.asarray(a, jnp.float32) for a in (x, B, C, dt, g))


@pytest.mark.parametrize("T,chunk,g_low", [
    (50, 16, 3.0),       # a multiple of neither; a down to 0.05
    (64, 64, 0.7),       # one whole chunk
    (96, 32, 30.0),      # heads that forget within a token: e^-G over
                         # a chunk would overflow float32
    (7, 8, 1.0)])        # shorter than a chunk
def test_the_chunked_form_is_the_recurrence(T, chunk, g_low):
    """Prefill's chunked form (the masked quadratic form inside a chunk
    and the state passed between chunks) against the recurrence one
    token at a time, float32: outputs and final state to 1e-4."""
    H, P, N = 3, 8, 16
    x, B, C, dt, g = _ssm_inputs(T, H, P, N, T, g_low)
    S = jnp.zeros((1, H, P, N), jnp.float32)
    zero = jnp.zeros((H,), jnp.float32)
    want = []
    for t in range(T):
        S, y = ssm_hybrid._ssm_step(S, x[None, t], B[None, t], C[None, t],
                                    dt[None, t], g[None, t], zero)
        want.append(y[0])
    pad = -T % chunk

    def padded(a):
        return jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:])])

    got, S_end = ssm_hybrid._ssd_chunked(
        *(padded(a) for a in (x, B, C, dt, g)),
        jnp.zeros((H, P, N), jnp.float32), chunk)
    scale = float(jnp.abs(jnp.stack(want)).max())
    assert float(jnp.abs(got[:T] - jnp.stack(want)).max()) < 1e-4 * scale
    # the padding (dt 0, g 0) advanced nothing
    assert float(jnp.abs(S_end - S[0]).max()) \
        < 1e-4 * float(jnp.abs(S).max())


def test_a_step_is_the_published_recurrence():
    """``_ssm_step`` against ``S' = a S + dt x (x) B``, ``y = S' C + D
    x`` written out in numpy, a head at a time."""
    H, P, N = 2, 4, 8
    x, B, C, dt, g = (np.asarray(a) for a in _ssm_inputs(5, H, P, N, 3))
    D = np.array([0.5, -1.5], np.float32)
    S = np.zeros((H, P, N))
    Sj = jnp.zeros((1, H, P, N), jnp.float32)
    for t in range(5):
        for h in range(H):
            S[h] = np.exp(g[t, h]) * S[h] \
                + dt[t, h] * np.outer(x[t, h], B[t, h])
        Sj, y = ssm_hybrid._ssm_step(
            Sj, *(jnp.asarray(a[None, t]) for a in (x, B, C, dt, g)),
            jnp.asarray(D))
        want = np.einsum("hpn,hn->hp", S, C[t]) + D[:, None] * x[t]
        assert np.abs(np.asarray(y[0]) - want).max() < 1e-5
    assert np.abs(np.asarray(Sj[0]) - S).max() < 1e-5


def test_rotary_turns_a_pair_by_the_position_times_its_frequency():
    """Halves pairing over the whole head: channel ``i`` pairs with ``i
    + hd / 2`` and turns by ``pos * theta^(-i / (hd / 2))``; a score
    depends on the positions' difference alone."""
    hd, theta = 8, 100.0
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(1, 1, hd)), jnp.float32)
            for _ in range(2))
    out = np.asarray(ssm_hybrid._rope(q, jnp.asarray([3]), theta))[0, 0]
    for i in range(hd // 2):
        ang = 3 * theta ** (-i / (hd // 2))
        a, b = float(q[0, 0, i]), float(q[0, 0, i + hd // 2])
        assert out[i] == pytest.approx(a * np.cos(ang) - b * np.sin(ang),
                                       abs=1e-5)
        assert out[i + hd // 2] == pytest.approx(
            b * np.cos(ang) + a * np.sin(ang), abs=1e-5)

    def score(pq, pk):
        return float(jnp.sum(ssm_hybrid._rope(q, jnp.asarray([pq]), theta)
                             * ssm_hybrid._rope(k, jnp.asarray([pk]),
                                                theta)))

    assert score(9, 4) == pytest.approx(score(25, 20), abs=1e-4)
    assert abs(score(9, 4) - score(9, 5)) > 1e-3


# ---- the programs: pages and per-slot state, in every layer

def _prefilled(model, prompt, bucket, slots=3, slot=1, ps=4):
    cfg, params = model
    max_pages = 24
    cache = ssm_hybrid.init_paged_cache(cfg, slots, slots * max_pages, ps)
    pt = np.arange(slots * max_pages, dtype=np.int32).reshape(slots, -1)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    tok, cache, _ = ssm_hybrid.jit_prefill_into_slot_paged(cfg, ps)(
        params, cache, padded, np.int32(len(prompt)), np.int32(0),
        pt[slot], np.int32(serving.PT_SENTINEL), np.int32(slot),
        jax.random.PRNGKey(0))
    return int(tok), cache, pt


def test_the_rows_that_pad_a_prompt_advance_no_state_and_no_tail(model32):
    """A prompt of 21 tokens (a multiple of neither the chunk 16, the
    page 4 nor its bucket) prefilled in a bucket of 32 and in one of 64:
    the same first token, state, convolution tail and pages: the slot
    holds what the prompt's LAST token left; and the whole forward pass
    over the 21 tokens alone agrees, as does the recurrence run by
    single decode steps from an empty slot."""
    cfg, params = model32
    prompt = _prompts(cfg, (21,), seed=2)[0]
    tok_a, a, pt = _prefilled(model32, prompt, 32)
    tok_b, b, _ = _prefilled(model32, prompt, 64)
    assert tok_a == tok_b
    assert np.array_equal(np.asarray(a["pos"]), np.asarray(b["pos"]))
    for name in ("state", "conv"):
        assert np.abs(_slots(a, name) - _slots(b, name)).max() < 1e-5
    live = np.asarray(a["k"].astype(jnp.float32))[:, 24:24 + 6]
    assert np.abs(live - np.asarray(
        b["k"].astype(jnp.float32))[:, 24:24 + 6]).max() < 1e-5
    rows = live.reshape(cfg.n_layer, 24, -1)
    assert np.abs(rows[:, :21]).min(axis=-1).min() > 0     # every layer
    assert np.abs(rows[:, 21:]).max() == 0                 # no pad row
    # only slot 1 was written, in EVERY layer
    state = _slots(a, "state")
    assert state.shape[0] == cfg.n_layer
    assert np.abs(state[:, 1]).reshape(cfg.n_layer, -1).max(-1).min() > 0
    assert np.abs(state[:, [0, 2]]).max() == 0
    assert list(np.asarray(a["pos"])) == [0, 21, 0]
    logits = ssm_hybrid.forward(params, jnp.asarray(prompt)[None], cfg)
    assert int(jnp.argmax(logits[0, -1])) == tok_a
    # the state and tail AT THE LAST TOKEN: what 21 single steps leave
    step = jax.jit(functools.partial(ssm_hybrid._slot_decode_step_paged,
                                     cfg=cfg, page_size=4))
    cache = ssm_hybrid.init_paged_cache(cfg, 3, 72, 4)
    active = np.array([False, True, False])
    for t in prompt:
        _, cache, _ = step(params, cache, jnp.asarray([0, int(t), 0]),
                           active, jnp.asarray(pt))
    for name in ("state", "conv"):
        want = _slots(cache, name)
        assert np.abs(_slots(a, name) - want).max() \
            < 1e-4 * np.abs(want).max()


def test_prefill_then_decode_through_the_cache_agrees_with_forward(
        model32):
    """37 tokens prefilled (chunks of 16, a bucket of 64), then six
    single decode steps through pages and state, against the whole
    forward pass, float32: the logits at every decoded position."""
    cfg, params = model32
    toks = _prompts(cfg, (43,), seed=3)[0]
    full = np.asarray(ssm_hybrid.forward(params, jnp.asarray(toks)[None],
                                         cfg))[0]
    tok, cache, pt = _prefilled(model32, toks[:37], 64)
    assert tok == int(full[36].argmax())
    step = jax.jit(functools.partial(ssm_hybrid._slot_decode_step_paged,
                                     cfg=cfg, page_size=4))
    active = np.array([False, True, False])
    for i in range(6):
        logits, cache, _ = step(
            params, cache, jnp.asarray([0, int(toks[37 + i]), 0]), active,
            jnp.asarray(pt))
        assert np.abs(np.asarray(logits)[1] - full[37 + i]).max() \
            < 1e-4 * np.abs(full[37 + i]).max()


def test_an_idle_or_parked_lanes_state_is_bit_for_bit_untouched(model):
    """The dispatch mask: a lane that is not active (idle, or parked for
    pages by ``_cover_pages``) neither writes a page, advances its
    position, nor touches its state and convolution tail in any layer;
    the active lane beside it does all four."""
    cfg, params = model
    prompt = _prompts(cfg, (13,), seed=6)[0]
    _, cache, pt = _prefilled(model, prompt, 16, slot=0)
    before = jax.tree_util.tree_map(np.asarray, cache)
    _, cache2, pt = _prefilled(model, prompt, 16, slot=1)
    cache = dict(cache, **{k: cache[k].at[:, 1].set(cache2[k][:, 1])
                           for k in cache if k.startswith(("state", "conv"))})
    cache["pos"] = cache["pos"].at[1].set(13)
    for name in ("k", "v"):
        cache[name] = cache[name].at[:, 24:48].set(cache2[name][:, 24:48])
    held = jax.tree_util.tree_map(np.asarray, cache)
    step = jax.jit(functools.partial(ssm_hybrid._slot_decode_step_paged,
                                     cfg=cfg, page_size=4))
    active = np.array([True, False, False])
    _, after, counts = step(params, cache, jnp.asarray([5, 7, 9]), active,
                            jnp.asarray(pt))
    after = jax.tree_util.tree_map(np.asarray, after)
    for name in ("state", "conv"):
        assert np.array_equal(_slots(after, name)[:, 1:],
                              _slots(held, name)[:, 1:])
        for l in range(cfg.n_layer):
            assert not np.array_equal(_slots(after, name)[l, 0],
                                      _slots(before, name)[l, 0])
    assert np.array_equal(after["k"][:, 24:], held["k"][:, 24:])
    assert list(after["pos"]) == [14, 13, 0]
    assert int(counts[0]) == 1                  # one live lane


def test_a_requests_tokens_are_the_same_alone_and_among_seven_others(
        model, engine):
    """A sequence's state is its slot's: what a request is answered
    does not depend on who shares its batch, nor on which slot it lands
    in."""
    cfg, _ = model
    prompts = _prompts(cfg, (9, 17, 30, 12, 33, 21, 26, 14))
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


def test_a_lane_preempted_by_recompute_resumes_as_if_it_never_stopped(
        model):
    """A starved pool: lanes park when the allocator runs dry and, on
    deadlock, the youngest is preempted BY RECOMPUTE: its pages free,
    its request requeues, and the prefill that readmits it rebuilds
    pages AND state together. Every stream is what an unstarved engine
    gives."""
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
        # the attention's kernel, interpreted off the TPU
        assert report["attn_kernel_mode"] == "interpret"
        assert np.array_equal(_answer(a, prompt, 9), _answer(b, prompt, 9))
        assert b.stats()["attn_kernel_dispatches"] > 0
    finally:
        a.shutdown()
        b.shutdown()


def test_the_counters_come_out_with_the_tokens(model, engine):
    """``stats()`` carries ``state_lanes_sum`` (one a live lane a step,
    whatever the layers) and ``gqa_tokens_read_sum`` (the positions the
    attention fetched, all layers: with the kernel, interpreted here,
    the one live lane's tokens in whole pages, far below the ``slots x
    max_len`` a step a layer the gather copies), and what the per-slot
    entries take."""
    cfg, _ = model
    assert ssm_hybrid.STEP_COUNTERS == ("state_lanes_sum",
                                        "gqa_tokens_read_sum")
    before = engine.stats()
    n, new = 13, 9
    _answer(engine, _prompts(cfg, (n,), seed=5)[0], new)
    after = engine.stats()
    steps = (after["dispatches"] - before["dispatches"]) * engine.chunk
    assert after["state_lanes_sum"] - before["state_lanes_sum"] == steps
    moved = after["gqa_tokens_read_sum"] - before["gqa_tokens_read_sum"]
    ps = 4
    assert moved == cfg.n_layer * sum(-(-(n + i + 1) // ps) * ps
                                      for i in range(steps))
    assert moved < cfg.n_layer * 8 * 96 * steps
    per_slot = cfg.n_layer * (
        cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
        + (cfg.conv_size - 1) * cfg.conv_dim * 2)
    assert after["state_bytes_per_slot"] == per_slot
    assert after["state_bytes"] == 8 * per_slot
    assert after["kv_bytes_per_token"] \
        == cfg.n_layer * 2 * cfg.n_kv_head * cfg.head_dim * 2


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gather"])
def test_the_step_takes_the_attention_by_shape_and_both_agree(
        model32, monkeypatch, kernel):
    """No knob: the step asks ``kda_moe.gqa_kernel`` of its page and
    heads and ``_state_kernel`` of the state's head (ISSUE 53: the
    recurrence's kernel, its own file's subject: the ``gather`` case
    steers BOTH off, so that the description reads a program without a
    kernel). In float32 the kernels' logits are the fallbacks' to 1e-4,
    and the counter says what each attention fetched."""
    cfg, params = model32
    prompt = _prompts(cfg, (13,), seed=9)[0]
    _, cache, pt = _prefilled(model32, prompt, 16)
    if not kernel:
        monkeypatch.setattr(kda_moe, "gqa_kernel",
                            lambda *a, **k: False)
        monkeypatch.setattr(ssm_hybrid, "_state_kernel", lambda cfg: False)
    assert ssm_hybrid.decode_attention_fused(cfg, 4) is kernel
    step = str(jax.make_jaxpr(functools.partial(
        ssm_hybrid._slot_decode_step_paged, cfg=cfg, page_size=4))(
        params, cache, jnp.zeros((3,), jnp.int32), np.zeros((3,), bool),
        jnp.asarray(pt)))
    assert step.count("pallas_call") == (2 * cfg.n_layer if kernel else 0)
    logits, _, counts = ssm_hybrid._slot_decode_step_paged(
        params, cache, jnp.asarray([0, 5, 0]),
        np.array([False, True, False]), jnp.asarray(pt), cfg, 4)
    full = np.asarray(ssm_hybrid.forward(
        params, jnp.asarray(np.append(prompt, 5))[None], cfg))[0, -1]
    assert np.abs(np.asarray(logits)[1] - full).max() \
        < 1e-4 * np.abs(full).max()
    assert int(counts[1]) == cfg.n_layer * (16 if kernel else 3 * 24 * 4)


def test_the_table_and_the_head_in_row_blocks_are_the_same_model(model):
    """``init_params(vocab_blocks=4)`` holds the table and the head as
    four blocks of vocabulary rows (a list under ``"kernel"``); the
    programs read either form off the tree, to the bit: the forward
    pass over ids of every block, and a prefill's first token."""
    cfg, _ = model
    p4 = ssm_hybrid.init_params(jax.random.PRNGKey(3), cfg, vocab_blocks=4)
    assert [b.shape for b in p4["embed"]["kernel"]] \
        == [(cfg.vocab_size // 4, cfg.d_model)] * 4
    assert [b.shape for b in p4["head"]["kernel"]] \
        == [(cfg.d_model, cfg.vocab_size // 4)] * 4
    p1 = dict(p4,
              embed={"kernel": jnp.concatenate(p4["embed"]["kernel"], 0)},
              head={"kernel": jnp.concatenate(p4["head"]["kernel"], 1)})
    tokens = jnp.asarray(np.random.default_rng(1).permutation(
        cfg.vocab_size)[:40].reshape(2, 20), jnp.int32)
    whole = ssm_hybrid.forward(p1, tokens, cfg)
    assert np.array_equal(np.asarray(whole),
                          np.asarray(ssm_hybrid.forward(p4, tokens, cfg)))
    assert len(set(np.asarray(tokens).ravel() // (cfg.vocab_size // 4))) == 4
    prompt = np.asarray(tokens[0, :13])
    assert _prefilled((cfg, p4), prompt, 16)[0] \
        == _prefilled((cfg, p1), prompt, 16)[0] \
        == int(jnp.argmax(whole[0, 12]))
    with pytest.raises(AssertionError):
        ssm_hybrid.init_params(jax.random.PRNGKey(3), cfg, vocab_blocks=7)


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
    assert set(ssm_hybrid.UNSUPPORTED) == {"prefix_cache", "spec_decode",
                                           "roles", "int8", "tp"}
    with pytest.raises(ValueError, match=why):
        DecodeEngine(params, cfg, slots=2, max_len=32, auto_start=False,
                     **knobs)


def test_the_pool_its_costs_and_the_stats_read_one_cache_spec(model):
    """``cache_spec``: pages AND per-slot entries, all four in every
    layer."""
    cfg, params = model
    slots, n_pages, ps = 3, 10, 4
    assert serving.decode_programs(cfg) is ssm_hybrid
    spec = ssm_hybrid.cache_spec(cfg)
    cache = ssm_hybrid.init_paged_cache(cfg, slots, n_pages, ps)
    L = cfg.n_layer
    row = (L, n_pages, ps, cfg.n_kv_head, cfg.head_dim)
    want = {"k": row, "v": row}
    for l in range(L):                  # ONE array a layer: cache_spec
        want[f"state{l}"] = (1, slots, cfg.ssm_heads, cfg.ssm_head_dim,
                             cfg.ssm_state)
        want[f"conv{l}"] = (1, slots, cfg.conv_size - 1, cfg.conv_dim)
    assert {k: v.shape for k, v in cache.items() if k != "pos"} == want
    assert cache["state0"].dtype == jnp.float32
    assert [spec.layers(n) for n in ("k", "v", "state1", "conv0")] \
        == [L, L, 1, 1]
    assert spec.bytes_per_page(ps) == (cache["k"].nbytes
                                       + cache["v"].nbytes) // n_pages
    assert spec.bytes_per_slot() == sum(
        v.nbytes for k, v in cache.items()
        if k.startswith(("state", "conv"))) // slots
    assert ssm_hybrid.max_positions(cfg) == cfg.max_seq
    assert ssm_hybrid.jit_decode_chunk_slots_paged(
        cfg, 4, 4).__wrapped__.__name__ == "decode_chunk_slots_paged"
    assert ssm_hybrid.jit_prefill_into_slot_paged(
        cfg, 4).__wrapped__.__name__ == "prefill_into_slot_paged"


def test_deferred_delivery_hands_every_lane_the_walks_messages(model,
                                                               engine):
    """ISSUE 43 through this model's programs: the slices, the ends and
    a replay's ``skip`` on the deferring engine are those of a walk that
    hands over at once."""
    from test_serve_engine_deliver import check_deferred_against_at_once

    cfg, _ = model
    check_deferred_against_at_once(
        engine, _engine(model, slots=8, n_pages=200),
        _prompts(cfg, (9, 17, 30, 12), seed=5))

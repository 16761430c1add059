"""Paged KV cache + shared-prefix reuse (ISSUE 6): engine streams must be
token-identical to ``generate_chunked`` (temp 0 AND seeded temp > 0), COW prefix sharing
must survive frees of the sharing lanes, page exhaustion must be a
defined backpressure path (defer / park / preempt-by-recompute — never a
corrupting write), the compiled-program set must stay at
``len(prompt_buckets) + 1`` across admission storms WITH prefix hits,
and the shutdown path must fail queued lanes unconditionally."""
import threading
import time

import numpy as np
import pytest


@pytest.fixture(scope="module")
def nano():
    from ray_tpu.models import gpt

    return gpt.CONFIGS["nano"]


@pytest.fixture(scope="module")
def nano_params(nano):
    import jax

    from ray_tpu.models import gpt

    return gpt.init_params(jax.random.PRNGKey(0), nano)


def _make(nano, nano_params, **kw):
    from ray_tpu.serve.engine import DecodeEngine

    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", (8, 16))
    return DecodeEngine(nano_params, nano, **kw)


def _drain_concurrent(eng, prompts, max_news, seeds=None):
    outs = {}

    def consume(i):
        kw = {"seed": seeds[i]} if seeds else {}
        outs[i] = np.concatenate(
            list(eng.stream(prompts[i], max_news[i], **kw)))

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def _ref_chunked(params, prompt, cfg, max_new, **kw):
    from ray_tpu.models import gpt_decode

    return np.concatenate([s[0] for s in gpt_decode.generate_chunked(
        params, np.asarray(prompt)[None], cfg, max_new, **kw)])


def test_paged_flat_token_identity_greedy(nano, nano_params):
    """Mixed prompt/output lengths through a starv-able 2-slot pool:
    every stream is bit-identical to ``generate_chunked``'s (a dense
    cache per request: the oracle of the rest of the suite)."""
    paged = _make(nano, nano_params, page_size=8, prefix_cache=False)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, nano.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 8, 11, 16)]
        max_news = [10, 7, 12, 3]
        of = [_ref_chunked(nano_params, p, nano, mn, chunk=4, max_len=64)
              for p, mn in zip(prompts, max_news)]
        op = _drain_concurrent(paged, prompts, max_news)
        for i in range(4):
            assert (of[i] == op[i]).all(), (i, of[i], op[i])
        st = paged.stats()
        assert st["paged"] and st["completed"] == 4
        assert st["pages_free"] == st["n_pages"]  # all recycled
    finally:
        paged.shutdown()


def test_paged_flat_token_identity_temperature(nano, nano_params):
    """Seeded sampling: a slot's PRNG lane splits exactly as
    ``generate_chunked``'s key does — same seeds, same tokens, whoever
    shares the pool; a different seed diverges."""
    import jax

    paged = _make(nano, nano_params, temperature=1.0, page_size=8,
                  prefix_cache=False)
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, nano.vocab_size, (n,)).astype(np.int32)
                   for n in (8, 11, 16)]
        max_news = [8, 10, 6]
        seeds = [7, 11, 13]
        of = [_ref_chunked(nano_params, p, nano, mn, chunk=4, max_len=64,
                           temperature=1.0, rng=jax.random.PRNGKey(sd))
              for p, mn, sd in zip(prompts, max_news, seeds)]
        op = _drain_concurrent(paged, prompts, max_news, seeds)
        for i in range(3):
            assert (of[i] == op[i]).all(), (i, of[i], op[i])
        other = np.concatenate(list(paged.stream(prompts[0], 8, seed=8)))
        assert not (other == op[0]).all()
    finally:
        paged.shutdown()


def test_flat_pool_is_gone(nano, nano_params):
    """``paged`` has one legal value left: the keyword is accepted (the
    benchmark's architecture file still passes it) and ``False`` is
    refused by name, before anything is allocated."""
    from ray_tpu.serve.engine import DecodeEngine

    with pytest.raises(ValueError, match="flat slot pool is gone"):
        DecodeEngine(nano_params, nano, paged=False)
    with pytest.raises(ValueError, match="flat slot pool is gone"):
        _make(nano, nano_params, paged=False, kv_dtype="int8")
    eng = _make(nano, nano_params, paged=True, auto_start=False)
    assert eng.stats()["paged"] is True


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_default_pool_holds_every_slot_at_full_length(nano, nano_params,
                                                      kv_dtype):
    """``DecodeEngine(params, cfg)`` with no pool options: pages of 16,
    the prefix cache on, and the KV bytes of ``slots * max_len`` fp
    positions — ``slots * ceil(max_len / 16)`` fp pages, or the same
    BYTES re-cut into int8 pages (codes plus a float32 scale per page
    and head), never the same page count at half the bytes."""
    from ray_tpu.models import gpt_decode as gd
    from ray_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(nano_params, nano, slots=3, max_len=100,
                       kv_dtype=kv_dtype, auto_start=False)
    assert eng.page_size == 16 and eng.max_pages == 7
    assert eng._prefix is not None
    fp_pages = 3 * 7
    fp_bytes = fp_pages * gd.kv_bytes_per_page(nano, 16)
    page = gd.kv_bytes_per_page(nano, 16, kv_dtype)
    assert eng.n_pages == fp_bytes // page
    if kv_dtype == "fp":
        assert eng.n_pages == fp_pages
    else:
        assert eng.n_pages > fp_pages
        assert eng.n_pages * page <= fp_bytes < (eng.n_pages + 1) * page
    st = eng.stats()
    assert st["n_pages"] == st["pages_free"] == eng.n_pages
    assert st["kv_bytes_per_token"] == page / 16


def test_paged_prefix_hit_and_cow(nano, nano_params):
    """Shared system prompt: a page-aligned hit maps cached pages
    directly, an exact-repeat hit ends mid-page and forks the partial
    page copy-on-write. Freeing / abandoning one sharer must not
    corrupt the others, and a post-free rerun still hits the cache."""
    rng = np.random.default_rng(2)
    sysp = rng.integers(0, nano.vocab_size, (16,)).astype(np.int32)
    a = np.concatenate([sysp, rng.integers(0, nano.vocab_size,
                                           (4,)).astype(np.int32)])
    b = np.concatenate([sysp, rng.integers(0, nano.vocab_size,
                                           (4,)).astype(np.int32)])
    buckets = (8, 16, 32)
    ref = _make(nano, nano_params, prompt_buckets=buckets, page_size=8, prefix_cache=False)
    try:
        ra = np.concatenate(list(ref.stream(a, 8)))
        rb = np.concatenate(list(ref.stream(b, 8)))
    finally:
        ref.shutdown()

    eng = _make(nano, nano_params, slots=3, prompt_buckets=buckets,
                page_size=8, prefix_cache=True)
    try:
        # Cold run seeds the cache (entries at page bounds 8/16 + n=20).
        oa = np.concatenate(list(eng.stream(a, 8)))
        assert (oa == ra).all()
        assert eng.stats()["prefix_hits"] == 0
        # b: page-aligned hit on sysp (16 tokens, 2 full pages).
        # a again: exact-length hit (20 tokens) -> COW fork of the
        # partial page. Concurrent, so they also share live.
        outs = _drain_concurrent(eng, [b, a], [8, 8])
        assert (outs[0] == rb).all(), (outs[0], rb)
        assert (outs[1] == ra).all(), (outs[1], ra)
        st = eng.stats()
        assert st["prefix_hits"] >= 2
        assert st["cow_copies"] >= 1
        assert st["prefix_tokens_reused"] >= 16 + 19
        # Abandon a sharer mid-stream: its pages free at the boundary;
        # the cached prefix must stay intact for the next hit.
        it = eng.stream(b, 40)
        next(it)
        it.close()
        deadline = time.time() + 2
        while eng.stats()["active_slots"] and time.time() < deadline:
            time.sleep(0.01)
        ob = np.concatenate(list(eng.stream(b, 8)))
        assert (ob == rb).all(), (ob, rb)
        assert eng.stats()["pages_free"] > 0
    finally:
        eng.shutdown()


def test_paged_admission_defers_on_page_exhaustion(nano, nano_params):
    """A pool holding exactly ONE max-length sequence: the second
    admission must defer (FIFO kept) until the first lane frees its
    pages — and both streams stay correct, proving no lane ever read or
    wrote another lane's pages."""
    ref = _make(nano, nano_params, prompt_buckets=(16,), page_size=8, prefix_cache=False)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, nano.vocab_size, (16,)).astype(np.int32)
               for _ in range(2)]
    try:
        refs = [np.concatenate(list(ref.stream(p, 40))) for p in prompts]
    finally:
        ref.shutdown()
    # max_len=64, ps=8 -> max_pages=8 == n_pages: one sequence's worth.
    eng = _make(nano, nano_params, prompt_buckets=(16,), page_size=8, n_pages=8, prefix_cache=False)
    try:
        outs = _drain_concurrent(eng, prompts, [40, 40])
        st = eng.stats()
        assert st["admissions_deferred"] >= 1, st
        assert st["completed"] == 2
        for i in range(2):
            assert (outs[i] == refs[i]).all(), i
        assert st["pages_free"] == 8
    finally:
        eng.shutdown()


def test_paged_parking_and_recompute_preemption(nano, nano_params):
    """A starved pool under 6 concurrent long generations: lanes park
    when the allocator runs dry and, on full deadlock, the youngest is
    preempted BY RECOMPUTE (requeued, replayed, delivered tokens
    suppressed) — every stream still completes token-identical, at
    temp 0 and seeded temp > 0."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, nano.vocab_size, (16,)).astype(np.int32)
               for _ in range(6)]
    mns = [24, 20, 28, 16, 24, 20]
    seeds = list(range(6))
    for temp in (0.0, 0.9):
        ref = _make(nano, nano_params, slots=4, prompt_buckets=(16,),
                    temperature=temp, page_size=8,
                    prefix_cache=False)
        try:
            refs = [np.concatenate(list(ref.stream(p, m, seed=s)))
                    for p, m, s in zip(prompts, mns, seeds)]
        finally:
            ref.shutdown()
        eng = _make(nano, nano_params, slots=4, prompt_buckets=(16,),
                    temperature=temp, page_size=8,
                    n_pages=11, prefix_cache=False)
        try:
            outs = _drain_concurrent(eng, prompts, mns, seeds)
            st = eng.stats()
            assert st["completed"] == 6 and st["admitted"] == 6
            assert st["lane_parks"] > 0 or \
                st["admissions_deferred"] > 0, st
            for i in range(6):
                assert (outs[i] == refs[i]).all(), (temp, i)
            assert st["pages_free"] == 11    # everything recycled
        finally:
            eng.shutdown()


def test_paged_dead_parked_lane_is_culled(nano, nano_params):
    """A parked lane whose consumer walks away must be culled at the
    next chunk boundary — pages freed while it sits OUT of the dispatch
    mask (the post-dispatch closed/deadline checks never see it) — and
    must never pin its pages or force recompute-preemption of the
    healthy lane."""
    p = (np.arange(1, 17, dtype=np.int32) * 2) % nano.vocab_size
    q = (np.arange(1, 17, dtype=np.int32) * 3) % nano.vocab_size
    ref = _make(nano, nano_params, max_len=128)
    try:
        want = np.concatenate(list(ref.stream(p, 100)))
    finally:
        ref.shutdown()
    # ps=64: one page covers pos 0..63, so when the lanes cross pos 64
    # the 3-page pool runs dry — the lane that grabs the third page
    # runs on for ~25 boundaries while the other stays parked.
    eng = _make(nano, nano_params, max_len=128, page_size=64, n_pages=3, prefix_cache=False)
    try:
        s0 = eng.stream(p, 100)
        s1 = eng.stream(q, 100)
        out0 = {}

        def consume():
            out0["t"] = np.concatenate(list(s0))

        t = threading.Thread(target=consume)
        t.start()
        deadline = time.time() + 60
        while time.time() < deadline:
            if eng.stats()["parked_slots"] >= 1:
                break
            time.sleep(0.001)
        else:
            pytest.fail("no lane ever parked")
        s1.close()
        # The dead lane's page must come back while the healthy lane is
        # still mid-generation — waiting for lane 0 to finish first
        # would also free pages, which is exactly the bug.
        while time.time() < deadline:
            st = eng.stats()
            if st["pages_free"] >= 1 and st["completed"] == 0:
                break
            assert st["completed"] == 0, \
                "healthy lane finished before the dead parked lane " \
                "was culled"
            time.sleep(0.001)
        t.join(60)
        st = eng.stats()
        assert (out0["t"] == want).all()
        assert st["abandoned"] >= 1 and st["preempted"] == 0, st
        assert st["pages_free"] == 3, st
    finally:
        eng.shutdown()


def test_paged_recompile_guard_with_prefix_hits(nano, nano_params):
    """The paged compiled-program set is exactly
    ``n + n (n + 1) / 2 + 1`` of ``n`` prompt buckets (a bucket's
    program for one prompt and a pair of buckets' for the group of one
    chunk boundary; ``warm_up()`` builds them all) — prefix-hit admissions (traced
    hist_len, COW, arbitrary page tables) and page-pressure replays add
    ZERO programs across a mixed-shape storm. page_size=4 is unique to
    this test (16 is every default engine's), so the (process-wide, lru-shared) jit wrappers count
    ONLY this pool configuration's programs."""
    from ray_tpu.models.gpt_decode import (jit_decode_chunk_slots_paged,
                                           jit_prefill_into_slot_paged)

    eng = _make(nano, nano_params, slots=3, max_len=48,
                prompt_buckets=(8, 16, 32), page_size=4,
                prefix_cache=True)
    try:
        rng = np.random.default_rng(5)
        sysp = rng.integers(0, nano.vocab_size, (16,)).astype(np.int32)
        fixed_tail = rng.integers(0, nano.vocab_size,
                                  (4,)).astype(np.int32)

        def storm(n, lens, shared_every=3):
            threads = []
            for i in range(n):
                if i % shared_every == 0:
                    # Alternate an exact-repeat prompt (COW fork) with
                    # fresh tails (page-aligned hit on the 16-token
                    # system-prompt boundary: four pages).
                    tail = fixed_tail if i % (2 * shared_every) == 0 \
                        else rng.integers(0, nano.vocab_size,
                                          (4,)).astype(np.int32)
                    p = np.concatenate([sysp, tail])
                else:
                    p = rng.integers(0, nano.vocab_size,
                                     (int(lens[i % len(lens)]),)
                                     ).astype(np.int32)
                mn = int(rng.integers(1, 12))
                t = threading.Thread(
                    target=lambda p=p, mn=mn: list(eng.stream(p, mn)))
                t.start()
                threads.append(t)
                if i % 3 == 0:
                    time.sleep(0.01)  # stagger: mid-stream admissions
            for t in threads:
                t.join()

        # Warm: cold 20-token shared prompt (bucket 32), plain 5/16
        # (buckets 8/16), then shared repeats (suffix bucket 8).
        eng.warm_up()
        storm(7, [5, 16])
        pre_prefill = eng._prefill._cache_size()
        pre_step = eng._step._cache_size()
        # three buckets alone, and the three pairs of the widest two
        assert pre_prefill == len(eng.prompt_buckets) + 3
        assert pre_step == 1
        storm(14, [1, 3, 7, 8, 9, 12, 15, 16])
        assert eng._prefill._cache_size() == pre_prefill
        assert eng._step._cache_size() == pre_step
        st = eng.stats()
        assert st["prefix_hits"] >= 2 and st["cow_copies"] >= 1
        # lru wrappers shared per static-knob tuple across engines
        assert jit_prefill_into_slot_paged(nano, 4, 0.0, "fp") \
            is eng._prefill
        assert jit_decode_chunk_slots_paged(
            nano, 4, 4, 0.0, -1, "fp", "gather") is eng._step
    finally:
        eng.shutdown()


def test_engine_shutdown_fails_queued_lanes(nano, nano_params):
    """Satellite: shutdown() must fail queued/in-flight lanes with
    EngineShutdownError even when the driver never started
    (auto_start=False) or died before processing them — previously
    those streams hung forever."""
    from ray_tpu.serve.batching import _drain_stream
    from ray_tpu.serve.engine import EngineShutdownError

    prompt = np.arange(8, dtype=np.int32) % nano.vocab_size
    # Never-started driver: submissions queue for start()...
    eng = _make(nano, nano_params, auto_start=False)
    lanes = [eng.submit(prompt, 8) for _ in range(3)]
    # ...but shutdown() without start() must drain and fail them all.
    eng.shutdown()
    for lane in lanes:
        with pytest.raises(EngineShutdownError):
            list(_drain_stream(lane))
    with pytest.raises(EngineShutdownError):
        eng.submit(prompt, 8)

    # start() after submit works (the queued-before-start contract).
    eng2 = _make(nano, nano_params, auto_start=False)
    lane = eng2.submit(prompt, 4)
    eng2.start()
    try:
        from ray_tpu.models import gpt_decode

        ref = np.concatenate([s[0] for s in gpt_decode.generate_chunked(
            nano_params, np.asarray(prompt)[None], nano, 4, chunk=4,
            max_len=64)])
        out = np.concatenate(list(_drain_stream(lane)))
        assert (out == ref).all()
    finally:
        eng2.shutdown()


def test_ensure_paging_and_decorator_knobs(nano, nano_params):
    """Config plumbing: ensure_paging repages an idle engine (and
    validates instead of repaging a used one); the decorator rejects
    page-pool knobs without continuous=True."""
    from ray_tpu import serve

    eng = _make(nano, nano_params)
    try:
        assert eng.page_size == 16 and eng._prefix is not None
        eng.ensure_paging(page_size=8, prefix_cache=True)
        assert eng.page_size == 8
        assert eng._prefix is not None
        eng.ensure_paging(page_size=8)          # idempotent no-op
        eng.ensure_paging(prefix_cache=False)   # host-side toggle
        assert eng._prefix is None
        prompt = np.arange(8, dtype=np.int32) % nano.vocab_size
        want = _ref_chunked(nano_params, prompt, nano, 6, chunk=4,
                            max_len=64)
        got = np.concatenate(list(eng.stream(prompt, 6)))
        assert (got == want).all()
        with pytest.raises(ValueError, match="live engine"):
            eng.ensure_paging(page_size=16)
    finally:
        eng.shutdown()

    with pytest.raises(ValueError, match="continuous=True"):
        @serve.batch(page_size=8)
        def bad(items):
            return items


@pytest.mark.parametrize("knobs,want", [
    (dict(page_size=8), dict(page_size=8, n_pages=16)),
    (dict(n_pages=5), dict(page_size=16, n_pages=5)),
    (dict(kv_dtype="int8"), dict(kv_dtype="int8")),
    (dict(attn_kernel="pallas"), dict(attn_kernel="pallas", n_pages=8)),
    (dict(page_size=32, n_pages=3, kv_dtype="int8", attn_kernel="pallas"),
     dict(page_size=32, n_pages=3, kv_dtype="int8",
          attn_kernel="pallas")),
], ids=["page_size", "n_pages", "kv_dtype", "attn_kernel", "all"])
def test_ensure_paging_rebuilds_unused_refuses_used(nano, nano_params,
                                                    knobs, want):
    """A never-used engine is rebuilt to the pushed knob — pool, page
    table and programs — with the others as constructed, and serves
    exact streams from the new pool; an engine that has admitted a
    request refuses the same push and keeps its pool."""
    prompt = np.arange(8, dtype=np.int32) % nano.vocab_size
    ref = _ref_chunked(nano_params, prompt, nano, 6, chunk=4, max_len=64)
    eng = _make(nano, nano_params)
    try:
        built = {k: getattr(eng, k) for k in
                 ("page_size", "n_pages", "kv_dtype", "attn_kernel")}
        assert built == dict(page_size=16, n_pages=8, kv_dtype="fp",
                             attn_kernel="gather")
        old_step = eng._step
        eng.apply_config(**knobs)
        for k, v in want.items():
            assert getattr(eng, k) == v, (k, getattr(eng, k), v)
        if "kv_dtype" in knobs and "n_pages" not in knobs:
            assert eng.n_pages > built["n_pages"]   # same bytes, re-cut
        # n_pages is a shape, not a static knob: same wrapper
        assert (eng._step is old_step) == (set(knobs) == {"n_pages"})
        assert eng._pt.shape == (eng.slots, eng.max_pages)
        assert eng._cache["k"].shape[1:3] == (eng.n_pages, eng.page_size)
        assert eng._pool.available() == eng.n_pages
        st = eng.stats()
        assert st["page_size"] == eng.page_size \
            and st["kv_dtype"] == eng.kv_dtype \
            and st["attn_kernel"] == eng.attn_kernel
        got = np.concatenate(list(eng.stream(prompt, 6)))
        if eng.kv_dtype == "fp":
            assert (got == ref).all(), (got, ref)
        else:
            assert got.shape == ref.shape      # int8 rounds, by design
        eng.apply_config(**knobs)              # matching: validates
    finally:
        eng.shutdown()
    used = _make(nano, nano_params)
    try:
        list(used.stream(prompt, 2))
        with pytest.raises(ValueError, match="live engine"):
            used.ensure_paging(**knobs)
        assert (used.page_size, used.n_pages, used.kv_dtype,
                used.attn_kernel) == (16, 8, "fp", "gather")
    finally:
        used.shutdown()


def test_deployment_schema_engine_block():
    """Schema plumbing: the ``engine:`` block parses, rejects unknown
    keys, and lands on DeploymentConfig.engine_config via overrides."""
    from ray_tpu.serve.config import DeploymentConfig
    from ray_tpu.serve.schema import DeploymentSchema, apply_overrides

    s = DeploymentSchema.from_dict(
        {"name": "d", "engine": {"page_size": 8, "prefix_cache": True}})
    assert s.engine == {"page_size": 8, "prefix_cache": True}
    with pytest.raises(ValueError, match="unknown engine config"):
        DeploymentSchema.from_dict(
            {"name": "d", "engine": {"pagesize": 8}})
    spec = {"deployments": [{"name": "d", "config": DeploymentConfig()}]}
    out = apply_overrides(spec, [s])
    assert out["deployments"][0]["config"].engine_config == \
        {"page_size": 8, "prefix_cache": True}


def test_prefix_cache_survives_pinned_eviction():
    """Eviction under lane-saturation must NOT wipe the cache: an entry
    whose pages are all pinned by live lanes frees nothing, so it stays
    resident (and keeps serving hits) until a lane lets go."""
    from ray_tpu.serve.engine import _PagePool, _PrefixCache

    pool = _PagePool(4)
    pc = _PrefixCache(pool, 8)
    toks = np.arange(16, dtype=np.int32)
    lane_pages = pool.alloc(2)          # a live lane holds them
    pc.insert(toks, lane_pages)         # cache pins them too
    assert len(pc) == 2                 # page-bound + exact-length
    pool.alloc(2)                       # pool now dry
    # Every cached page is lane-pinned: eviction can free nothing and
    # must refuse (no pointless wipe) — repeatedly.
    assert pc.evict_lru() is False
    assert pc.evict_lru() is False
    assert len(pc) == 2
    pool.unref(lane_pages)              # lane done: cache-only refs
    assert pc.evict_lru() is True       # now an eviction frees a page
    assert pool.available() >= 1
    pc.clear()                          # teardown unpins EVERYTHING
    assert len(pc) == 0 and pool.available() == 2


def test_paged_engine_metrics_observed(nano, nano_params):
    """Page-pool observability: gauges + prefix/COW counters reach the
    serve metric set, and engine.stats() carries the page block."""
    from ray_tpu._private.metrics import serve_metrics

    eng = _make(nano, nano_params, page_size=8,
                prefix_cache=True, deployment="paged_probe")
    try:
        rng = np.random.default_rng(6)
        prompt = rng.integers(0, nano.vocab_size, (11,)).astype(np.int32)
        list(eng.stream(prompt, 6))
        list(eng.stream(prompt, 6))    # exact repeat: hit + COW
        sm = serve_metrics()
        key = (("deployment", "paged_probe"),)
        free = dict(sm["engine_pages_free"].collect())
        hits = dict(sm["engine_prefix_hits"].collect())
        cows = dict(sm["engine_cow_copies"].collect())
        assert key in free
        assert free[key] + eng.stats()["pages_used"] == eng.n_pages
        assert hits.get(key, 0) >= 1
        assert cows.get(key, 0) >= 1
        st = eng.stats()
        for field in ("pages_free", "pages_used", "prefix_hits",
                      "cow_copies", "page_size", "n_pages"):
            assert field in st
    finally:
        eng.shutdown()

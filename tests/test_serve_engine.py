"""Continuous-batching decode engine (ISSUE 5): slot-pool streams must
be token-identical to ``generate_chunked``, admission must happen at
chunk boundaries with per-slot freeing (EOS / max_new / deadline /
abandonment), the compiled-program set must stay bounded across ANY
admission pattern, and the ``@serve.batch(continuous=True)`` path must
carry it through a live deployment."""
import sys
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


def _ref_chunked(params, prompt, cfg, max_new, **kw):
    from ray_tpu.models import gpt_decode

    return np.concatenate([s[0] for s in gpt_decode.generate_chunked(
        params, np.asarray(prompt)[None], cfg, max_new, **kw)])


def _make_engine(nano, nano_params, **kw):
    from ray_tpu.serve.engine import DecodeEngine

    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", (8, 16))
    return DecodeEngine(nano_params, nano, **kw)


def test_engine_greedy_token_identity(nano, nano_params):
    """Four concurrent requests of mixed prompt/output lengths through a
    2-slot pool: every stream is token-identical to generate_chunked,
    the first slice is the lone prefill token (TTFT), and the engine's
    accounting sees all four admissions complete."""
    eng = _make_engine(nano, nano_params)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, nano.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 8, 11, 16)]
        max_news = [10, 7, 12, 3]
        refs = [_ref_chunked(nano_params, p, nano, mn, chunk=4, max_len=64)
                for p, mn in zip(prompts, max_news)]
        outs = {}

        def consume(i):
            chunks = list(eng.stream(prompts[i], max_news[i]))
            assert chunks[0].shape == (1,)
            assert all(c.shape[0] <= eng.chunk for c in chunks[1:])
            outs[i] = np.concatenate(chunks)

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(4):
            assert (outs[i] == refs[i]).all(), (i, outs[i], refs[i])
        st = eng.stats()
        assert st["admitted"] == 4 and st["completed"] == 4
        assert st["tokens"] == sum(max_news)
        assert st["active_slots"] == 0
        assert 0.0 < st["avg_occupancy"] <= 1.0
        # Fused amortization: far fewer dispatches than tokens.
        assert st["dispatches_per_token"] < 0.5
    finally:
        eng.shutdown()


def test_engine_metrics_observed(nano, nano_params):
    """The engine driver observes slot occupancy / admission wait /
    dispatch counters into the serve metric set."""
    from ray_tpu._private.metrics import serve_metrics

    eng = _make_engine(nano, nano_params, deployment="metrics_probe")
    try:
        prompt = np.arange(8, dtype=np.int32) % nano.vocab_size
        list(eng.stream(prompt, 6))
        sm = serve_metrics()
        occ = dict(sm["engine_slot_occupancy"].collect())
        waits = dict(sm["engine_admission_wait"].collect())
        disp = dict(sm["engine_dispatches"].collect())
        key = (("deployment", "metrics_probe"),)
        assert key in occ and occ[key][-1] > 0      # n observations
        assert key in waits and waits[key][-1] > 0
        assert key in disp and disp[key] >= 1
    finally:
        eng.shutdown()


def test_engine_temperature_per_slot_rng(nano, nano_params):
    """Sampling threads one PRNG lane per slot: same seed reproduces the
    stream (and matches generate_chunked's chain exactly); a different
    seed diverges. Admission order of other slots must not perturb it."""
    import jax

    eng = _make_engine(nano, nano_params, temperature=1.0)
    try:
        prompt = np.random.default_rng(1).integers(
            0, nano.vocab_size, (8,)).astype(np.int32)
        a = np.concatenate(list(eng.stream(prompt, 8, seed=7)))
        # occupy slot 0 so the retry lands in a different slot
        noise = eng.submit(prompt, 24, seed=3)
        b = np.concatenate(list(eng.stream(prompt, 8, seed=7)))
        c = np.concatenate(list(eng.stream(prompt, 8, seed=8)))
        from ray_tpu.serve.batching import _drain_stream

        list(_drain_stream(noise))
        ref = _ref_chunked(nano_params, prompt, nano, 8, chunk=4,
                           max_len=64, temperature=1.0,
                           rng=jax.random.PRNGKey(7))
        assert (a == b).all()
        assert (a == ref).all(), (a, ref)
        assert not (a == c).all()
    finally:
        eng.shutdown()


def test_engine_eos_frees_slot(nano, nano_params):
    """A lane sampling EOS mid-chunk ends AT the EOS (trimmed slice, no
    trailing tokens) and its slot frees for the queued request instead
    of riding out the batch."""
    prompt = np.random.default_rng(2).integers(
        0, nano.vocab_size, (8,)).astype(np.int32)
    ref = _ref_chunked(nano_params, prompt, nano, 16, chunk=4, max_len=64)
    eos = int(ref[5])
    stop = int(np.argmax(ref == eos))
    eng = _make_engine(nano, nano_params, slots=1, eos_token=eos)
    try:
        # Second request queued behind the 1-slot pool: only an EOS free
        # can admit it.
        p2 = np.random.default_rng(3).integers(
            0, nano.vocab_size, (8,)).astype(np.int32)
        ref2 = _ref_chunked(nano_params, p2, nano, 6, chunk=4, max_len=64,
                            eos_token=eos)
        out = {}

        def consume(key, p, mn):
            out[key] = np.concatenate(list(eng.stream(p, mn)))

        t1 = threading.Thread(target=consume, args=("a", prompt, 16))
        t2 = threading.Thread(target=consume, args=("b", p2, 6))
        t1.start()
        time.sleep(0.05)
        t2.start()
        t1.join()
        t2.join()
        assert out["a"].shape[0] == stop + 1
        assert int(out["a"][-1]) == eos
        assert (out["a"] == ref[:stop + 1]).all()
        assert (out["b"] == ref2).all()
        assert eng.stats()["completed"] == 2
    finally:
        eng.shutdown()


def test_engine_deadline_handling(nano, nano_params):
    """Expired-while-queued requests fail without spending a prefill;
    a deadline passing mid-generation frees the slot at the next chunk
    boundary with RequestDeadlineExceeded on the lane."""
    from ray_tpu.serve import RequestDeadlineExceeded
    from ray_tpu.serve.batching import _drain_stream

    eng = _make_engine(nano, nano_params, slots=1)
    try:
        prompt = np.random.default_rng(4).integers(
            0, nano.vocab_size, (8,)).astype(np.int32)
        # already expired: dropped at admission, no prefill spent
        before = eng.stats()["prefills"]
        lane = eng.submit(prompt, 8, deadline_s=time.time() - 1)
        with pytest.raises(RequestDeadlineExceeded):
            list(_drain_stream(lane))
        assert eng.stats()["prefills"] == before
        assert eng.stats()["expired"] == 1

        # expires mid-generation: partial stream, then the typed error
        it = eng.stream(prompt, 40, deadline_s=time.time() + 0.03)
        got = []
        with pytest.raises(RequestDeadlineExceeded):
            for s in it:
                got.append(s)
                time.sleep(0.01)
        assert got, "deadline fired before the TTFT token"
        deadline = time.time() + 2
        while eng.stats()["active_slots"] and time.time() < deadline:
            time.sleep(0.01)
        assert eng.stats()["active_slots"] == 0
        # the freed slot still serves new work
        ref = _ref_chunked(nano_params, prompt, nano, 5, chunk=4,
                           max_len=64)
        assert (np.concatenate(list(eng.stream(prompt, 5))) == ref).all()
    finally:
        eng.shutdown()


def test_engine_abandoned_consumer_frees_slot(nano, nano_params):
    """A consumer walking away mid-stream closes its lane; the driver
    frees the slot at the next boundary instead of decoding for nobody."""
    eng = _make_engine(nano, nano_params, slots=1)
    try:
        prompt = np.random.default_rng(5).integers(
            0, nano.vocab_size, (8,)).astype(np.int32)
        it = eng.stream(prompt, 40)
        next(it)
        it.close()
        deadline = time.time() + 2
        while eng.stats()["active_slots"] and time.time() < deadline:
            time.sleep(0.01)
        st = eng.stats()
        assert st["active_slots"] == 0 and st["abandoned"] == 1
        ref = _ref_chunked(nano_params, prompt, nano, 4, chunk=4,
                           max_len=64)
        assert (np.concatenate(list(eng.stream(prompt, 4))) == ref).all()
        # close BEFORE the first pull (consumer gone while still queued
        # for admission): dropped at the boundary, no prefill spent
        pre = eng.stats()["prefills"]
        it2 = eng.stream(prompt, 40)
        it2.close()
        deadline = time.time() + 2
        while eng.stats()["abandoned"] < 2 and time.time() < deadline:
            time.sleep(0.01)
        st = eng.stats()
        assert st["abandoned"] == 2 and st["prefills"] == pre
        assert st["active_slots"] == 0
    finally:
        eng.shutdown()


def test_engine_recompile_guard(nano, nano_params):
    """The compiled-program set is bounded by the bucket config, NOT the
    admission pattern: after ``warm_up()`` (each bucket's program for
    one prompt, each pair of buckets' for the group of one chunk
    boundary, and the chunk program) and one pass over the buckets, a storm of varied
    prompts/output lengths/arrival orders adds ZERO XLA programs — no
    retrace per admitted request, alone or in a group."""
    from ray_tpu.models.gpt_decode import (jit_decode_chunk_slots_paged,
                                           jit_prefill_into_slot_paged)

    eng = _make_engine(nano, nano_params, slots=3, max_len=48,
                       prompt_buckets=(8, 16))
    try:
        rng = np.random.default_rng(6)

        def storm(n, lens):
            threads = []
            for i in range(n):
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

        eng.warm_up()
        storm(4, [5, 16])             # warm pass: touch both buckets
        pre_prefill = eng._prefill._cache_size()
        pre_step = eng._step._cache_size()
        assert pre_prefill >= 5       # a bucket's, and a pair's
        storm(12, [1, 3, 7, 8, 9, 12, 15, 16])
        assert eng._prefill._cache_size() == pre_prefill
        assert eng._step._cache_size() == pre_step
        # the lru wrappers are shared per static-knob tuple, so repeated
        # engine construction reuses (not duplicates) the programs
        assert jit_prefill_into_slot_paged.cache_info().currsize <= 64
        assert jit_decode_chunk_slots_paged.cache_info().currsize <= 64
        assert jit_prefill_into_slot_paged(nano, 16, 0.0) is eng._prefill
    finally:
        eng.shutdown()


def test_engine_submit_validation(nano, nano_params):
    from ray_tpu.serve.engine import EngineShutdownError

    eng = _make_engine(nano, nano_params, max_len=32,
                       prompt_buckets=(8, 16))
    try:
        with pytest.raises(ValueError, match="exceeds largest prompt"):
            eng.submit(np.zeros(17, np.int32), 4)
        with pytest.raises(ValueError, match="exceeds cache length"):
            eng.submit(np.zeros(16, np.int32), 17)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(np.zeros(0, np.int32), 4)
        # max_new=0: an instantly-finished stream, no slot spent
        assert list(eng.stream(np.zeros(4, np.int32), 0)) == []
    finally:
        eng.shutdown()
    with pytest.raises(EngineShutdownError):
        eng.submit(np.zeros(4, np.int32), 4)


def test_batch_buckets_must_cover_max_batch_size():
    """Satellite: custom buckets that cannot hold a full batch are a
    decorate-time ValueError, not a silent negative-count 'pad'."""
    from ray_tpu import serve

    with pytest.raises(ValueError, match="do not cover"):
        @serve.batch(max_batch_size=8, pad_to_bucket=True, buckets=(2, 4))
        def bad(items):
            return items

    with pytest.raises(ValueError, match="positive"):
        @serve.batch(max_batch_size=4, buckets=(0, 4))
        def worse(items):
            return items

    @serve.batch(max_batch_size=8, pad_to_bucket=True, buckets=(2, 4, 8))
    def good(items):
        return items

    with pytest.raises(ValueError, match="continuous=True"):
        @serve.batch(continuous=True, stream=True)
        def conflicted(item):
            return item


def test_continuous_serve_deployment(rt_cluster, nano, nano_params):
    """Live data plane: @serve.batch(continuous=True) feeds the engine's
    admission queue from concurrent handle callers and streams per-slot
    slices back through the replica — token-identical to the library
    reference, with the engine's accounting visible via the handle."""
    from ray_tpu import serve

    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 512, (8,)).astype(np.int32)
               for _ in range(3)]
    max_news = [9, 5, 12]
    refs = [_ref_chunked(nano_params, p, nano, mn, chunk=4, max_len=64)
            for p, mn in zip(prompts, max_news)]

    serve.start(proxy=False)
    try:
        @serve.deployment(max_ongoing_requests=8)
        class ContinuousGPT:
            def __init__(self):
                import jax

                from ray_tpu.models import gpt
                from ray_tpu.serve.engine import DecodeEngine

                cfg = gpt.CONFIGS["nano"]
                params = gpt.init_params(jax.random.PRNGKey(0), cfg)
                self.engine = DecodeEngine(
                    params, cfg, slots=2, chunk=4, max_len=64,
                    prompt_buckets=(8,), deployment="cont_test")

            @serve.batch(continuous=True)
            def decode(self, request):
                return self.engine, {
                    "prompt": np.asarray(request["prompt"], np.int32),
                    "max_new": int(request["max_new"])}

            def stats(self):
                return self.engine.stats()

            def __call__(self, request):
                return self.decode(request)

        h = serve.run(ContinuousGPT.bind(), name="cont",
                      route_prefix=None)
        out = {}

        def call(i):
            items = list(h.options(stream=True).remote(
                {"prompt": prompts[i].tolist(),
                 "max_new": max_news[i]}))
            assert len(items[0]) == 1          # TTFT token alone
            out[i] = np.concatenate([np.asarray(x) for x in items])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(3):
            assert (out[i] == refs[i]).all(), (i, out[i], refs[i])
        st = h.options(method_name="stats").remote().result(timeout=30)
        assert st["admitted"] == 3 and st["completed"] == 3
        # flatten_chunks still flattens engine slices to tokens
        toks = list(h.options(stream=True, flatten_chunks=True).remote(
            {"prompt": prompts[0].tolist(), "max_new": max_news[0]}))
        assert toks == [int(t) for t in refs[0]]
        serve.delete("cont")
    finally:
        serve.shutdown()


def test_continuous_smoke_benchmark():
    """Satellite CI hook: the benchmark's --continuous --smoke A/B runs
    end to end (static gang AND engine under the same Poisson schedule)
    and emits the A/B summary line."""
    import json
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "serve_gpt.py"),
         "--continuous", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.strip().startswith("{")]
    ab = [r for r in rows if r["metric"].endswith("continuous_ab")]
    assert ab, rows
    assert ab[0]["smoke"] is True and ab[0]["value"] > 0
    modes = {r["metric"]: r for r in rows}
    assert any("continuous_mode" in m for m in modes)
    assert any("static_mode" in m for m in modes)

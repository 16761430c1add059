"""Offline batch inference (ISSUE 11): the Data → DecodeEngine pipeline
must stream token-identical generations at full occupancy, throttle
admission by live engine queue depth, survive retryable engine failures
in-run via ``resume_from`` replay, resume a SIGKILLed driver from its
progress log exactly-once with byte-identical output, and leave engines
clean + admissible when the consumer walks away."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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

    kw.setdefault("chunk", 4)
    kw.setdefault("max_len", 64)
    return np.concatenate([s[0] for s in gpt_decode.generate_chunked(
        params, np.asarray(prompt)[None], cfg, max_new, **kw)])


def _make_engine(nano, nano_params, **kw):
    from ray_tpu.serve.engine import DecodeEngine

    # Same static knobs as test_serve_engine.py: the jitted programs
    # are already in the process-wide lru caches.
    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", (8, 16))
    return DecodeEngine(nano_params, nano, **kw)


def _rows(nano, n, base_seed=0):
    rng = np.random.default_rng(base_seed)
    return [{"rid": int(i),
             "prompt": rng.integers(0, nano.vocab_size,
                                    (int(rng.integers(5, 17)),)
                                    ).astype(np.int32)}
            for i in range(n)]


def _flat_rows(blocks):
    from ray_tpu.data import block as B

    return [r for b in blocks for r in B.iter_rows(b)]


def test_pipeline_token_identity_and_order(nano, nano_params):
    """Every row's generation is token-identical to generate_chunked,
    rows come back in input order across block boundaries, and the
    pipeline accounting adds up."""
    from ray_tpu import data as rd

    eng = _make_engine(nano, nano_params)
    try:
        rows = _rows(nano, 10)
        ds = rd.from_items(rows, block_size=3)
        bi = rd.BatchInferencer(eng, prompts_col="prompt", max_new=9)
        got = _flat_rows(bi.run(ds))
        assert [r["rid"] for r in got] == list(range(10))
        for r in got:
            ref = _ref_chunked(nano_params, r["prompt"], nano, 9)
            assert (np.asarray(r["generated"]) == ref).all(), r["rid"]
        assert bi.stats["rows"] == 10 and bi.stats["tokens"] == 90
        assert bi.stats["blocks"] == 4
        st = eng.stats()
        assert st["admitted"] == 10 and st["active_slots"] == 0
    finally:
        eng.shutdown()


def test_dataset_generate_end_to_end(nano, nano_params):
    """Dataset.generate builds (and tears down) engines from a
    (params, cfg) ref and honors a per-row max_new column."""
    from ray_tpu import data as rd

    rows = [{"rid": i, "prompt": np.arange(5 + i, dtype=np.int32)
             % nano.vocab_size, "n": 3 + (i % 3)} for i in range(6)]
    out = rd.from_items(rows, block_size=2).generate(
        (nano_params, nano), "prompt", max_new_col="n",
        slots=2, chunk=4, max_len=64, prompt_buckets=(8, 16)).take_all()
    assert [r["rid"] for r in out] == list(range(6))
    for r in out:
        assert len(r["generated"]) == r["n"]
        ref = _ref_chunked(nano_params, r["prompt"], nano, r["n"])
        assert (np.asarray(r["generated"]) == ref).all()


def test_saturation_policy_bounds_queue():
    """The policy admits while any engine has backlog headroom and
    routes to the least-backlogged engine; at the bound it refuses."""
    from ray_tpu.data.llm import EngineSaturationPolicy

    class Fake:
        def __init__(self, slots, depth):
            self.slots, self._d = slots, depth

        def queue_depth(self):
            return self._d

    a, b = Fake(4, 0), Fake(4, 5)
    pol = EngineSaturationPolicy([a, b], queue_factor=2.0)  # limit 8
    assert pol.can_add_input(0) and pol.pick() is a
    a._d = 8
    assert pol.pick() is b and pol.can_add_input(0)
    b._d = 8
    assert pol.pick() is None and not pol.can_add_input(0)
    with pytest.raises(ValueError):
        EngineSaturationPolicy([], queue_factor=2.0)
    with pytest.raises(ValueError):
        EngineSaturationPolicy([a], queue_factor=0)


def test_queue_depth_signal_and_gauge(nano, nano_params):
    """queue_depth counts accepted-not-yet-admitted requests, shows up
    in engine.stats() (as both queue_depth and the legacy queued), and
    the driver exports it as the serve_engine_queue_depth gauge."""
    from ray_tpu._private.metrics import serve_metrics

    eng = _make_engine(nano, nano_params, deployment="qd_probe")
    try:
        eng.inject_fault("driver_slow", wedge_s=0.05)
        prompt = np.arange(8, dtype=np.int32) % nano.vocab_size
        streams = [eng.stream(prompt, 8, seed=i) for i in range(6)]
        deadline = time.time() + 5
        seen = 0
        while time.time() < deadline:
            seen = max(seen, eng.queue_depth())
            st = eng.stats()
            assert st["queue_depth"] == st["queued"]
            if seen >= 2:
                break
            time.sleep(0.01)
        assert seen >= 2, "backlog never formed behind the slow driver"
        eng.inject_fault("driver_slow", wedge_s=0.0)
        for s in streams:
            list(s)
        assert eng.queue_depth() == 0
        deadline = time.time() + 5
        key = (("deployment", "qd_probe"),)
        while time.time() < deadline:
            vals = dict(serve_metrics()["engine_queue_depth"].collect())
            if vals.get(key) == 0:
                break
            time.sleep(0.02)
        assert vals.get(key) == 0, vals
    finally:
        eng.shutdown()


def test_progress_log_resume_skips_committed(tmp_path, nano, nano_params):
    """Exactly-once: a completed run's log satisfies a rerun without a
    single resubmission, and the outputs match row for row."""
    from ray_tpu import data as rd

    rows = _rows(nano, 8)
    ds = rd.from_items(rows, block_size=3)
    d = str(tmp_path / "progress")
    eng = _make_engine(nano, nano_params)
    try:
        bi = rd.BatchInferencer(eng, prompts_col="prompt", max_new=7,
                                progress_path=d)
        first = _flat_rows(bi.run(ds))
        assert bi.stats["blocks"] == 3
    finally:
        eng.shutdown()
    eng2 = _make_engine(nano, nano_params)
    try:
        bi2 = rd.BatchInferencer(eng2, prompts_col="prompt", max_new=7,
                                 progress_path=d)
        again = _flat_rows(bi2.run(ds))
        assert eng2.stats()["admitted"] == 0     # zero rows resubmitted
        assert bi2.stats["blocks_from_log"] == 3
        assert bi2.stats["rows_resumed_from_log"] == 8
        assert [r["rid"] for r in again] == [r["rid"] for r in first]
        for a, b in zip(first, again):
            assert (np.asarray(a["generated"])
                    == np.asarray(b["generated"])).all()
            # Rows served from the log are indistinguishable from fresh
            # ones: numpy types survive the commit round-trip exactly.
            assert type(b["prompt"]) is type(a["prompt"])
            assert b["prompt"].dtype == a["prompt"].dtype
    finally:
        eng2.shutdown()


def test_progress_log_fingerprint_mismatch(tmp_path, nano, nano_params):
    """Resuming with different generation knobs must refuse, not mix
    token streams from two configurations."""
    from ray_tpu import data as rd

    d = str(tmp_path / "progress")
    eng = _make_engine(nano, nano_params)
    try:
        bi = rd.BatchInferencer(eng, prompts_col="prompt", max_new=4,
                                progress_path=d)
        list(bi.run(rd.from_items(_rows(nano, 2), block_size=2)))
        with pytest.raises(ValueError, match="different generation"):
            rd.BatchInferencer(eng, prompts_col="prompt", max_new=5,
                               progress_path=d)
        # A heterogeneous pool (different generation-determining knobs)
        # refuses up front: row routing is load-dependent, so mixed
        # engines would make output depend on timing.
        hot = _make_engine(nano, nano_params, temperature=1.0)
        try:
            with pytest.raises(ValueError, match="disagree"):
                rd.BatchInferencer([eng, hot], prompts_col="prompt",
                                   max_new=4)
        finally:
            hot.shutdown()
    finally:
        eng.shutdown()


def test_retryable_engine_failure_resumes_in_run(nano, nano_params):
    """A mid-run engine-driver death (retryable EngineRestartError)
    costs a replay, not the run: the pipeline supervises the driver
    back up and resubmits with resume_from, and the seeded temp>0
    output stays token-identical to an undisturbed engine's."""
    from ray_tpu import data as rd

    rows = _rows(nano, 8)
    ds = rd.from_items(rows, block_size=4)

    def run(arm_fault):
        eng = _make_engine(nano, nano_params, temperature=1.0)
        try:
            if arm_fault:
                eng.inject_fault("driver_die", at_tokens=20)
            bi = rd.BatchInferencer(eng, prompts_col="prompt",
                                    max_new=12, seed=5)
            out = _flat_rows(bi.run(ds))
            return out, bi.stats, eng.stats()
        finally:
            eng.shutdown()

    ref, _, _ = run(arm_fault=False)
    got, stats, est = run(arm_fault=True)
    assert est["driver_restarts"] == 1
    assert stats["retries"] >= 1
    assert [r["rid"] for r in got] == [r["rid"] for r in ref]
    for a, b in zip(ref, got):
        assert (np.asarray(a["generated"])
                == np.asarray(b["generated"])).all()


def test_abandoned_pipeline_frees_engine(nano, nano_params):
    """Satellite: walking away from the pipeline closes every in-flight
    engine stream, the engine frees slots AND pages at its next chunk
    boundary, and it remains admissible for the next run."""
    from ray_tpu import data as rd

    eng = _make_engine(nano, nano_params, page_size=8,
                       prefix_cache=False)
    n_pages = eng.n_pages
    try:
        rows = _rows(nano, 12)
        bi = rd.BatchInferencer(eng, prompts_col="prompt", max_new=40)
        gen = bi.run(rd.from_items(rows, block_size=2))
        next(gen)                       # block 0 done; more in flight
        assert bi._flights, "no in-flight streams to abandon"
        lanes = [fl.stream._lane for fl in bi._flights.values()]
        gen.close()                     # consumer walks away
        assert all(lane.closed for lane in lanes)
        deadline = time.time() + 10
        st = {}
        while time.time() < deadline:
            st = eng.stats()
            if st["active_slots"] == 0 and st["queue_depth"] == 0 \
                    and st["pages_free"] == n_pages:
                break
            time.sleep(0.02)
        assert st["active_slots"] == 0 and st["queue_depth"] == 0, st
        assert st["pages_free"] == n_pages, st
        assert st["abandoned"] >= 1, st
        # Still admissible: a fresh stream decodes token-identically.
        prompt = rows[0]["prompt"]
        out = np.concatenate(list(eng.stream(prompt, 6)))
        assert (out == _ref_chunked(nano_params, prompt, nano, 6)).all()
    finally:
        eng.shutdown()


def _bench():
    """Import benchmarks/batch_infer.py as a module (its run_pipeline
    is the shared driver body the --child subprocess runs)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "batch_infer_bench",
        os.path.join(ROOT, "benchmarks", "batch_infer.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_args(temperature, **over):
    import argparse

    a = argparse.Namespace(
        config="nano", slots=2, chunk=4, engines=1, rows=24,
        block_size=4, max_new=12, max_len=64, temperature=temperature,
        seed=0, queue_factor=2.0, throttle=0.0)
    for k, v in over.items():
        setattr(a, k, v)
    return a


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_sigkill_preemption_resume_exactly_once(temperature, tmp_path):
    """THE kill-and-resume acceptance: a throttled driver subprocess is
    SIGKILLed mid-run (>= 1 block durably committed), and the resumed
    run loses nothing, duplicates nothing, and writes output files
    byte-identical to an uninterrupted run — temp 0 AND seeded
    temp > 0. The reference and resumed runs drive the same benchmark
    pipeline body in-process (programs already compiled here); only the
    victim is a subprocess, because SIGKILL must take the whole driver."""
    from ray_tpu.data.llm import ProgressLog
    from ray_tpu.testing import sigkill_when

    mod = _bench()
    out_ref = str(tmp_path / "out_ref")
    out_res = str(tmp_path / "out_res")
    progress = str(tmp_path / "progress")
    n_blocks = 6

    # Uninterrupted reference, in-process.
    _bi, engines, _ = mod.run_pipeline(
        _bench_args(temperature), out_dir=out_ref)
    for e in engines:
        e.shutdown()

    # Victim: throttled child driver, SIGKILLed once 2 blocks committed.
    a = _bench_args(temperature, throttle=0.05)
    child = mod._child_cmd(a, out=str(tmp_path / "out_killed"),
                           progress=progress, throttle=a.throttle)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(child, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL, env=env, cwd=ROOT)
    killed = sigkill_when(
        proc, lambda: len(ProgressLog.scan(progress)) >= 2,
        timeout_s=300)
    committed = len(ProgressLog.scan(progress))
    assert killed, "driver outran the kill predicate"
    assert 1 <= committed < n_blocks, committed

    # Resume in-process from the same progress log, full speed.
    bi, engines, _ = mod.run_pipeline(
        _bench_args(temperature), out_dir=out_res, progress=progress)
    for e in engines:
        e.shutdown()
    assert bi.stats["rows_resumed_from_log"] >= committed * a.block_size
    files_ref, rids_ref = mod._read_out_dir(out_ref)
    files_res, rids_res = mod._read_out_dir(out_res)
    assert files_ref == files_res            # byte-identical output
    assert sorted(rids_res) == sorted(set(rids_res)) == sorted(rids_ref)


def test_batch_infer_smoke_benchmark():
    """Satellite CI hook: ``benchmarks/batch_infer.py --smoke`` runs
    both phases end to end; the saturation row must report >= 0.8
    steady-state slot occupancy (the ISSUE acceptance bar) with a
    bounded admission queue, and the resume row must be clean."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "batch_infer.py"), "--smoke"],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.strip().startswith("{")]
    sat = [r for r in rows if r["metric"].endswith("_saturation")]
    res = [r for r in rows if r["metric"].endswith("_resume")]
    assert sat and res, rows
    s, r = sat[0], res[0]
    assert s["smoke"] is True and s["value"] > 0
    assert s["avg_slot_occupancy"] >= 0.8, s
    assert s["queue_depth_max"] <= 2 * s["queue_factor"] * s["slots"], s
    assert s["cost_per_mtok"] > 0
    assert r["killed"] is True and r["identical"] is True, r
    assert r["lost_rows"] == 0 and r["dup_rows"] == 0, r

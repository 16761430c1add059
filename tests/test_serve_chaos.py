"""Crash-safe streaming (ISSUE 7): in-flight generation survives
replica failure, driver failure, and planned restarts.

- A mid-stream engine-driver death re-routes the stream through the
  retry path with a replay token (``resume_from``); the resumed stream
  is TOKEN-IDENTICAL to an uninterrupted run (temp 0 and seeded
  temp > 0, fp and int8 pages).
- Resume respects the ORIGINAL deadline and withdraws from the retry
  budget; a second crash during replay fails cleanly with a typed
  error after the budget runs dry.
- A wedged driver is detected by ``check_health`` and recovered by a
  one-shot driver restart WITHOUT replacing the replica.
- ``replica.drain`` stops admissions (retryable pushback), finishes
  running lanes, and the controller drains before teardown.
"""
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


def _mk_prompt(rid: int, vocab: int, n: int = 8):
    return np.random.default_rng(900 + rid).integers(
        0, vocab, (n,)).astype(np.int32)


def _chaos_deployment(serve, *, kv_dtype="fp", temperature=0.0,
                      deployment="chaos", num_replicas=2):
    """Continuous-engine deployment; every stream is a deterministic
    function of (rid, max_new) — identical weights and per-request
    seeds on every replica, so a resume replays exactly."""

    @serve.deployment(num_replicas=num_replicas, max_ongoing_requests=8,
                      health_check_period_s=0.3,
                      graceful_shutdown_timeout_s=10.0)
    class ChaosGPT:
        def __init__(self, kv_dtype: str, temperature: float,
                     deployment: str):
            import jax

            from ray_tpu.models import gpt
            from ray_tpu.serve.engine import DecodeEngine

            self.cfg = gpt.CONFIGS["nano"]
            params = gpt.init_params(jax.random.PRNGKey(0), self.cfg)
            self.engine = DecodeEngine(
                params, self.cfg, slots=2, chunk=4, max_len=64,
                prompt_buckets=(8,), deployment=deployment,
                temperature=temperature, page_size=8,
                kv_dtype=kv_dtype,
                # int8 pages: a prefix hit reads the prompt's keys
                # dequantized where a whole prefill has them exact, so
                # a replay is the same stream only from the same path
                prefix_cache=kv_dtype == "fp",
                wedge_timeout_s=2.0)
            # Compile every program NOW, before the replica registers:
            # health probes start at registration, and a first-dispatch
            # XLA compile stalls the driver loop longer than the tight
            # wedge_timeout_s this test runs with.
            list(self.engine.stream(
                np.arange(8, dtype=np.int32) % self.cfg.vocab_size, 6,
                seed=0))

        @serve.batch(continuous=True)
        def decode(self, request):
            # The prompt rides IN the request so a resume resubmission
            # replays the identical call with zero server-side state.
            import numpy as _np

            return self.engine, {
                "prompt": _np.asarray(request["prompt"], _np.int32),
                "max_new": int(request["max_new"]),
                "seed": int(request["rid"])}

        def __call__(self, request):
            return self.decode(request)

    # One name end to end: app, deployment, and engine metric label.
    return ChaosGPT.options(name=deployment).bind(
        kv_dtype, temperature, deployment)


def _req(rid: int, max_new: int, vocab: int) -> dict:
    return {"rid": rid, "max_new": max_new,
            "prompt": _mk_prompt(rid, vocab).tolist()}


def _replica_engine_stats(handles) -> dict:
    """{rid: engine stats dict} via each replica's get_metrics."""
    import ray_tpu as rt

    out = {}
    for r, h in handles.items():
        try:
            m = rt.get(h.get_metrics.remote(), timeout=10)
            out[r] = (m.get("engines") or [{}])[0]
        except Exception:  # noqa: BLE001 - replica dead (chaos test!)
            pass
    return out


def _warm(handle, req, ref):
    """One uninterrupted baseline stream per replica-ish (two passes),
    asserting token identity — also compiles every program so chaos
    timing is not dominated by XLA."""
    for _ in range(2):
        base = np.concatenate([np.asarray(x).ravel() for x in
                               handle.options(stream=True).remote(req)])
        assert (base == ref).all(), (base, ref)


@pytest.mark.parametrize("kv_dtype,temperature",
                         [("int8", 0.0), ("int8", 1.0), ("fp", 0.0),
                          ("fp", 1.0)])
def test_resume_after_driver_death_token_identical(
        rt_cluster, nano, nano_params, kv_dtype, temperature):
    """Kill the serving engine's driver mid-stream: the client stream
    stalls, resumes on the other replica, and the concatenation is
    token-identical to an uninterrupted run — fp AND int8 pages,
    greedy AND seeded sampling. fp streams are ``generate_chunked``'s;
    int8 pages round, so their reference is the deployment's own
    uninterrupted stream."""
    import jax

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.testing import _serve_replica_handles, inject_engine_fault

    name = f"chaos_{kv_dtype}_{int(temperature)}"
    serve.start(proxy=False)
    try:
        handle = serve.run(
            _chaos_deployment(serve, kv_dtype=kv_dtype,
                              temperature=temperature, deployment=name),
            name=name, route_prefix=None)
        rid, max_new = 3, 40
        kw = {"chunk": 4, "max_len": 64}
        if temperature:
            kw.update(temperature=1.0, rng=jax.random.PRNGKey(rid))
        req = _req(rid, max_new, nano.vocab_size)
        if kv_dtype == "fp":
            ref = _ref_chunked(nano_params,
                               _mk_prompt(rid, nano.vocab_size), nano,
                               max_new, **kw)
        else:
            ref = np.concatenate([
                np.asarray(x).ravel()
                for x in handle.options(stream=True).remote(req)])
            assert ref.shape == (max_new,)
        _warm(handle, req, ref)
        handles = _serve_replica_handles(name, name)
        assert len(handles) == 2
        # Throttle both engines (~1 chunk / 30 ms) so the stream is
        # reliably mid-flight when the kill lands.
        inject_engine_fault(name, name, kind="driver_slow", wedge_s=0.03)

        def killer():
            # Arm driver death at the CURRENT delivered-token count of
            # whichever engine is serving this stream; the idle engine
            # is left alone.
            for r, st in _replica_engine_stats(handles).items():
                if st.get("active_slots", 0) > 0:
                    rt.get(handles[r].inject_engine_fault.remote(
                        "driver_die", int(st["tokens"]), 0.0), timeout=10)

        fired = False
        toks = []
        it = handle.options(stream=True, resumable=True,
                            timeout_s=60.0).remote(req)
        for item in it:
            toks.extend(int(t) for t in np.asarray(item).ravel())
            if not fired and len(toks) >= 6:
                fired = True
                killer()
        assert fired, "stream finished before the fault could fire"
        assert toks == [int(t) for t in ref], (toks, ref)

        # The resume is visible end to end: router metric, engine stat.
        from ray_tpu._private.metrics import serve_metrics

        resumes = dict(serve_metrics()["stream_resumes"].collect())
        assert resumes.get((("deployment", name),), 0) >= 1
        total_resumed = sum(
            st.get("resumed", 0)
            for st in _replica_engine_stats(handles).values())
        assert total_resumed >= 1
        serve.delete(name)
    finally:
        serve.shutdown()


def test_resume_respects_deadline_and_budget():
    """Unit-level contract of the mid-stream resume decision: an
    expired original deadline forbids the resume (the failure
    surfaces), and each successful resume withdraws one retry-budget
    token and carries the delivered-token replay count."""
    from ray_tpu.exceptions import ActorDiedError
    from ray_tpu.serve.handle import (DeploymentResponseGenerator,
                                      RetryBudget, Router)

    class FakeRouter:
        deployment_name = "fake_dep"

        def __init__(self, tokens):
            self.budget = RetryBudget(deposit_ratio=0.0, reserve_per_s=0.0,
                                      initial=tokens)
            self.submissions = []
            self.marked = []

        def mark_dead(self, rid):
            self.marked.append(rid)

        def note_overloaded(self, rid):
            pass

        def release(self, rid):
            pass

        def _submit_stream_raw(self, method, args, kwargs, deadline_s,
                               model_id, flatten_chunks, resume_from=0,
                               request_id=None):
            self.submissions.append(
                {"resume_from": resume_from, "deadline_s": deadline_s})
            return "rid2", iter(())

    def dead_gen():
        raise ActorDiedError("replica crashed mid-stream")
        yield  # pragma: no cover

    # (a) original deadline already passed: NO resume, original error.
    router = FakeRouter(tokens=10.0)
    g = DeploymentResponseGenerator(
        router, "rid1", dead_gen(), call=("m", (), {}),
        deadline_s=time.time() - 1.0, resumable=True)
    g._got_first, g._delivered = True, 5
    with pytest.raises(ActorDiedError):
        next(g)
    assert router.submissions == []

    # (b) live deadline: resume carries resume_from=delivered and the
    # ORIGINAL deadline, and withdraws exactly one budget token.
    router = FakeRouter(tokens=1.0)
    deadline = time.time() + 60.0
    g = DeploymentResponseGenerator(
        router, "rid1", dead_gen(), call=("m", (), {}),
        deadline_s=deadline, resumable=True)
    g._got_first, g._delivered = True, 7
    # The resubmitted stream is empty -> clean StopIteration after the
    # transparent resume.
    with pytest.raises(StopIteration):
        next(g)
    assert router.submissions == [
        {"resume_from": 7, "deadline_s": deadline}]
    assert router.budget.tokens() < 1.0      # the token was withdrawn
    assert router.marked == ["rid1"]

    # (c) dry budget: the resume is refused, the failure surfaces.
    router = FakeRouter(tokens=0.0)
    g = DeploymentResponseGenerator(
        router, "rid1", dead_gen(), call=("m", (), {}),
        deadline_s=time.time() + 60.0, resumable=True)
    g._got_first, g._delivered = True, 3
    with pytest.raises(ActorDiedError):
        next(g)
    assert router.submissions == []

    # (d) resumable=False keeps the old mid-stream contract: raise.
    router = FakeRouter(tokens=10.0)
    g = DeploymentResponseGenerator(
        router, "rid1", dead_gen(), call=("m", (), {}),
        deadline_s=time.time() + 60.0, resumable=False)
    g._got_first, g._delivered = True, 3
    with pytest.raises(ActorDiedError):
        next(g)
    assert router.submissions == []
    assert Router.DEFAULT_MAX_RETRIES >= 1   # sanity: retries exist


def test_second_crash_during_replay_fails_cleanly(rt_cluster, nano,
                                                  nano_params):
    """Both replicas die (the second DURING the replay) with only one
    retry token in the budget: the client gets a clean typed error — no
    hang — and every token delivered before the failure is the correct
    prefix (no duplicates from the partial replay)."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.exceptions import (ActorDiedError, ActorUnavailableError,
                                    TaskError, WorkerCrashedError)
    from ray_tpu.serve.handle import RetryBudget, get_router
    from ray_tpu.testing import _serve_replica_handles, inject_engine_fault

    name = "chaos_double"
    serve.start(proxy=False)
    try:
        handle = serve.run(_chaos_deployment(serve, deployment=name),
                           name=name, route_prefix=None)
        rid, max_new = 7, 40
        req = _req(rid, max_new, nano.vocab_size)
        ref = _ref_chunked(nano_params, _mk_prompt(rid, nano.vocab_size),
                           nano, max_new, chunk=4, max_len=64)
        _warm(handle, req, ref)
        handles = _serve_replica_handles(name, name)
        inject_engine_fault(name, name, kind="driver_slow", wedge_s=0.03)
        # Exactly ONE retry token, no replenishment: the first process
        # kill resumes, the second exhausts the budget and must raise.
        router = get_router(name, name)
        router.budget = RetryBudget(deposit_ratio=0.0, reserve_per_s=0.0,
                                    initial=1.0)

        def kill_all_soon():
            # Each replica's engine hard-exits two DELIVERED tokens
            # after arming: the serving replica dies now; the resume
            # target dies mid-replay (replayed/suppressed tokens do not
            # count — only the fresh continuation does).
            for r, st in _replica_engine_stats(handles).items():
                rt.get(handles[r].inject_engine_fault.remote(
                    "kill_process", int(st.get("tokens", 0)) + 2, 0.0),
                    timeout=10)

        toks = []
        fired = False
        with pytest.raises(Exception) as ei:
            it = handle.options(stream=True, resumable=True,
                                timeout_s=30.0).remote(req)
            for item in it:
                toks.extend(int(t) for t in np.asarray(item).ravel())
                if not fired and len(toks) >= 6:
                    fired = True
                    kill_all_soon()
        assert fired
        e = ei.value
        assert isinstance(e, (ActorDiedError, ActorUnavailableError,
                              WorkerCrashedError, TaskError,
                              ConnectionError, TimeoutError)), repr(e)
        # Everything delivered before the failure is the exact prefix.
        assert toks == [int(t) for t in ref[:len(toks)]]
        assert len(toks) < max_new
        serve.delete(name)
    finally:
        serve.shutdown()


def test_wedged_driver_recovers_without_replacement(rt_cluster, nano,
                                                    nano_params):
    """A wedged engine driver (live thread, stale heartbeat) is detected
    by check_health on the controller's health pass and recovered by a
    one-shot driver restart — the replica set is UNCHANGED."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.testing import _serve_replica_handles, inject_engine_fault

    name = "chaos_wedge"
    serve.start(proxy=False)
    try:
        handle = serve.run(_chaos_deployment(serve, deployment=name),
                           name=name, route_prefix=None)
        rid, max_new = 9, 24
        req = _req(rid, max_new, nano.vocab_size)
        ref = _ref_chunked(nano_params, _mk_prompt(rid, nano.vocab_size),
                           nano, max_new, chunk=4, max_len=64)
        _warm(handle, req, ref)
        rids_before = set(_serve_replica_handles(name, name))
        assert len(rids_before) == 2
        # Wedge BOTH drivers past wedge_timeout_s=1.0; health period is
        # 0.3 s, so the pass must restart them, not replace replicas.
        armed = inject_engine_fault(name, name, kind="driver_wedge",
                                    wedge_s=4.0)
        assert len(armed) == 2
        deadline = time.time() + 30
        restarted = 0
        while time.time() < deadline:
            handles = _serve_replica_handles(name, name)
            restarted = sum(
                st.get("driver_restarts", 0)
                for st in _replica_engine_stats(handles).values())
            if restarted >= 2:
                break
            time.sleep(0.2)
        assert restarted >= 2, "wedged drivers were not restarted"
        rids_after = set(_serve_replica_handles(name, name))
        assert rids_after == rids_before, \
            f"replica set changed: {rids_before} -> {rids_after}"
        # The deployment still serves, token-identically, on the SAME
        # replicas.
        out = np.concatenate([np.asarray(x).ravel() for x in
                              handle.options(stream=True).remote(req)])
        assert (out == ref).all()
        # Driver-restart visibility: engine stats aggregated into
        # serve.status() by the controller's health pass.
        deadline = time.time() + 10
        agg = {}
        while time.time() < deadline:
            st = serve.status()
            agg = st["applications"][name]["deployments"][name] \
                .get("engine") or {}
            if agg.get("driver_restarts", 0) >= 2:
                break
            time.sleep(0.3)
        assert agg.get("driver_restarts", 0) >= 2, agg
        # queue_depth rides the same controller aggregation (ISSUE 11
        # satellite): present whenever engine stats flow at all.
        assert "queue_depth" in agg, agg
        serve.delete(name)
    finally:
        serve.shutdown()


def test_drain_stops_admissions_finishes_lanes(rt_cluster, nano,
                                               nano_params):
    """replica.drain: a running stream completes token-identically, new
    admissions push back with a retryable typed error, and the drain
    reports clean."""
    from ray_tpu import serve
    from ray_tpu.exceptions import TaskError
    from ray_tpu.serve.request import ReplicaDrainingError
    from ray_tpu.testing import drain_replicas, inject_engine_fault

    name = "chaos_drain"
    serve.start(proxy=False)
    try:
        handle = serve.run(
            _chaos_deployment(serve, deployment=name, num_replicas=1),
            name=name, route_prefix=None)
        rid, max_new = 11, 40
        req = _req(rid, max_new, nano.vocab_size)
        ref = _ref_chunked(nano_params, _mk_prompt(rid, nano.vocab_size),
                           nano, max_new, chunk=4, max_len=64)
        _warm(handle, req, ref)
        inject_engine_fault(name, name, kind="driver_slow", wedge_s=0.02)

        out = {}

        def consume():
            toks = []
            for item in handle.options(stream=True).remote(req):
                toks.extend(int(t) for t in np.asarray(item).ravel())
            out["toks"] = toks

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.2)            # stream is mid-flight (throttled)
        drained = drain_replicas(name, name, timeout_s=20.0)
        t.join(timeout=30)
        assert not t.is_alive()
        assert out["toks"] == [int(x) for x in ref], \
            "in-flight stream must finish identically through a drain"
        assert all(drained.values()), drained
        # New admissions on the drained replica push back with a typed
        # retryable error; with no other replica the request times out
        # at its deadline rather than hard-failing.
        with pytest.raises(Exception) as ei:
            list(handle.options(stream=True, timeout_s=2.0).remote(
                _req(rid, 4, nano.vocab_size)))
        e = ei.value
        ok_err = isinstance(e, (ReplicaDrainingError, TimeoutError)) or (
            isinstance(e, TaskError) and e.cause_type in (
                "ReplicaDrainingError", "EngineShutdownError"))
        assert ok_err, repr(e)
        serve.delete(name)
    finally:
        serve.shutdown()


def test_controller_drains_before_teardown(rt_cluster):
    """Teardown routes through the graceful drain: the controller-side
    drain counter reaches the head's merged /metrics with one increment
    per torn-down replica."""
    import ray_tpu as rt
    from ray_tpu import serve

    name = "chaos_scaledown"
    serve.start(proxy=False)
    try:
        @serve.deployment(num_replicas=2)
        class Echo:
            def __call__(self, x):
                return x

        h = serve.run(Echo.bind(), name=name, route_prefix=None)
        assert h.remote("ping").result(timeout=30) == "ping"
        serve.delete(name)
        deadline = time.time() + 30
        drained = 0.0
        while time.time() < deadline:
            try:
                text = rt.metrics_text()
            except Exception:  # noqa: BLE001 - head mid-flush
                text = ""
            drained = sum(
                float(line.rsplit(" ", 1)[1])
                for line in text.splitlines()
                if line.startswith("ray_tpu_serve_replica_drains_total")
                and 'deployment="Echo"' in line)
            if drained >= 2:
                break
            time.sleep(0.5)
        assert drained >= 2, "teardown did not drain replicas"
    finally:
        serve.shutdown()


def test_chaos_smoke_benchmark():
    """Satellite CI hook: ``benchmarks/serve_gpt.py --chaos --smoke``
    kills a replica mid-load and asserts ZERO client-visible broken
    streams, with every stream token-identical to its reference."""
    import json
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "serve_gpt.py"),
         "--chaos", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.strip().startswith("{")]
    chaos = [r for r in rows if r["metric"].endswith("chaos_recovery")]
    assert chaos, rows
    row = chaos[0]
    assert row["smoke"] is True
    assert row["broken_streams"] == 0
    assert row["kills"] >= 1
    assert row["completed"] == row["requests"]

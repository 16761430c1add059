"""Test/dev helpers: virtual device meshes without TPU hardware.

The reference tests distributed behavior with in-process multi-raylet
clusters (``python/ray/cluster_utils.py:135``); the analogous trick for the
numeric plane is XLA's virtual host-device flag — N CPU "chips" in one
process so every mesh/sharding path compiles and runs without a slice.
"""
from __future__ import annotations

import os


def force_host_devices(n: int = 8) -> None:
    """Force this process (and children) onto N virtual CPU devices.

    Call it before jax first initialises a backend in this process: the
    platform and ``XLA_FLAGS`` are read once, then. The environment is
    set too, so spawned worker processes inherit the CPU platform. A
    process whose backend is already up on something else (or on fewer
    CPU devices) gets an error, not a rebuilt backend.
    """
    import sys

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    if "jax" not in sys.modules:
        return
    import jax

    # jax read JAX_PLATFORMS when it was imported; the config still
    # takes the platform until the first backend use.
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < n:
        raise RuntimeError(
            f"force_host_devices({n}) came too late: jax already "
            f"initialised {len(devs)} {devs[0].platform} device(s); call "
            f"it before the first jax use in this process")


def assert_device_count(n: int) -> None:
    import jax

    got = len(jax.devices())
    assert got >= n, f"need >= {n} devices, have {got}"


class WorkerKiller:
    """Chaos harness: kill random worker processes while a workload runs
    (reference: ``_private/test_utils.py:1429`` ``ResourceKillerActor`` /
    ``WorkerKillerActor`` — assert progress under induced failures).

    Runs a driver-side thread that periodically SIGKILLs a random
    registered worker process (from the head's state listing). The
    driver's own pid and an optional protect-list are never touched.

    Usage::

        with WorkerKiller(interval_s=0.2) as killer:
            ... run workload with retries ...
        assert killer.kills > 0
    """

    def __init__(self, interval_s: float = 0.2, max_kills: int = 1_000_000,
                 kill_actors: bool = True, protect_pids=()):
        self.interval_s = interval_s
        self.max_kills = max_kills
        self.kill_actors = kill_actors
        self.protect = set(protect_pids) | {os.getpid()}
        self.kills = 0
        self.killed_pids: list = []
        self._stop = None
        self._thread = None

    def _loop(self):
        import random
        import signal

        import ray_tpu as rt

        while not self._stop.is_set() and self.kills < self.max_kills:
            self._stop.wait(self.interval_s)
            if self._stop.is_set():
                return
            try:
                workers = rt.state("workers")
            except Exception:  # noqa: BLE001 - cluster tearing down
                return
            def is_local_worker(pid: int) -> bool:
                # Safety: the listing is cluster-wide but os.kill is
                # local — a remote worker's pid could collide with an
                # unrelated local process. Only kill pids whose local
                # cmdline is actually a ray_tpu worker.
                try:
                    import psutil

                    cmd = " ".join(psutil.Process(pid).cmdline())
                    return "worker_main" in cmd or "ray_tpu" in cmd
                except ImportError:
                    try:
                        with open(f"/proc/{pid}/cmdline", "rb") as f:
                            cmd = f.read().decode(errors="replace")
                        return "worker_main" in cmd or "ray_tpu" in cmd
                    except OSError:
                        return False
                except Exception:  # noqa: BLE001 - process vanished
                    return False

            def eligible(w):
                if w["pid"] in self.protect:
                    return False
                # assignment is "None" (idle) | "lease" | an ActorID repr
                is_actor = str(w["assignment"]) not in ("None", "lease")
                if not self.kill_actors and is_actor:
                    return False
                return is_local_worker(w["pid"])

            victims = [w for w in workers if eligible(w)]
            if not victims:
                continue
            victim = random.choice(victims)
            try:
                os.kill(victim["pid"], signal.SIGKILL)
                self.kills += 1
                self.killed_pids.append(victim["pid"])
            except ProcessLookupError:
                pass

    def start(self) -> "WorkerKiller":
        import threading

        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="worker-killer", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._stop is not None:
            self._stop.set()
            self._thread.join(timeout=10)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def sigkill_when(proc, predicate, *, poll_s: float = 0.02,
                 timeout_s: float = 120.0) -> bool:
    """Preemption harness (ISSUE 11): watch ``predicate()`` and SIGKILL
    ``proc`` — a ``subprocess.Popen`` or a bare pid — the moment it
    turns true, simulating an overnight batch-inference driver dying
    mid-run (spot preemption, OOM kill). The canonical predicate is
    ``lambda: len(ProgressLog.scan(progress_dir)) >= k`` — kill once k
    blocks committed, then assert the resumed run loses nothing,
    duplicates nothing, and is byte-identical to an uninterrupted run.

    Returns True if the kill landed; False if the process exited first
    (the workload outran the predicate — enlarge it or throttle the
    engine with ``inject_fault("driver_slow", ...)``) or ``timeout_s``
    passed."""
    import signal
    import time

    pid = proc.pid if hasattr(proc, "pid") else int(proc)

    def alive() -> bool:
        if hasattr(proc, "poll"):
            return proc.poll() is None
        try:
            os.kill(pid, 0)
            return True
        except OSError:
            return False

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if not alive():
            return False
        if predicate():
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                return False      # exited between the poll and the kill
            if hasattr(proc, "wait"):
                proc.wait(timeout=30)
            return True
        time.sleep(poll_s)
    return False


def _serve_replica_handles(app_name: str, deployment_name: str,
                           timeout: float = 10.0) -> dict:
    """Live replica handles ({rid: ActorHandle}) of one serve deployment,
    straight from the controller's membership view."""
    import ray_tpu as rt
    from ray_tpu.serve.config import SERVE_CONTROLLER_NAME

    ctrl = rt.get_actor(SERVE_CONTROLLER_NAME, timeout=timeout)
    info = rt.get(ctrl.get_replicas.remote(app_name, deployment_name),
                  timeout=timeout)
    if info is None:
        return {}
    return dict(info["replicas"])


def set_replica_fault_injection(app_name: str, deployment_name: str, *,
                                latency_s: float = 0.0,
                                error_rate: float = 0.0) -> int:
    """Arm the per-request fault-injection hook on every live replica of
    one deployment (latency + probabilistic errors applied BEFORE user
    code, plus an invocation log). Returns how many replicas were armed.

    This is how overload and deadline behavior is tested without real
    slowness: ``latency_s`` saturates ``max_ongoing_requests`` on
    demand, and the invocation log proves no request ran past its
    deadline."""
    import ray_tpu as rt

    handles = _serve_replica_handles(app_name, deployment_name)
    for h in handles.values():
        rt.get(h.set_fault_injection.remote(latency_s, error_rate),
               timeout=10)
    return len(handles)


def clear_replica_fault_injection(app_name: str, deployment_name: str) -> int:
    import ray_tpu as rt

    handles = _serve_replica_handles(app_name, deployment_name)
    for h in handles.values():
        rt.get(h.clear_fault_injection.remote(), timeout=10)
    return len(handles)


def get_replica_invocation_logs(app_name: str, deployment_name: str) -> list:
    """Concatenated invocation records ({method, start, deadline}) from
    every live replica with fault injection armed."""
    import ray_tpu as rt

    out = []
    for h in _serve_replica_handles(app_name, deployment_name).values():
        try:
            out.extend(rt.get(h.get_invocation_log.remote(), timeout=10))
        except Exception:  # noqa: BLE001 - replica died mid-collection
            pass
    return out


def inject_engine_fault(app_name: str, deployment_name: str, *,
                        kind: str = "driver_die", at_tokens: int = 0,
                        wedge_s: float = 0.0, rid: str = None) -> list:
    """Arm ONE chaos fault on the DecodeEngines of a serve deployment
    (the ISSUE 7 fault points): triggered at the driver's next loop
    boundary once ``at_tokens`` tokens have been delivered.

    - ``kind="driver_die"``: the engine driver thread raises — lanes
      fail with the retryable ``EngineRestartError``, clients resume on
      another replica, and the replica's ``check_health`` restarts the
      driver once before escalating.
    - ``kind="driver_wedge"`` (with ``wedge_s``): the driver stalls
      without heartbeating — ``check_health`` detects the stale beat.
    - ``kind="kill_process"``: hard ``os._exit`` of the replica worker —
      kill-at-token-N, the realistic mid-stream replica crash.

    ``rid`` targets one replica; default arms every live replica.
    Returns the replica ids armed."""
    import ray_tpu as rt

    handles = _serve_replica_handles(app_name, deployment_name)
    if rid is not None:
        handles = {rid: handles[rid]}
    armed = []
    for r, h in handles.items():
        n = rt.get(h.inject_engine_fault.remote(kind, at_tokens, wedge_s),
                   timeout=10)
        if n:
            armed.append(r)
    return armed


def drain_replicas(app_name: str, deployment_name: str,
                   timeout_s: float = 5.0) -> dict:
    """Invoke the graceful drain on every live replica of a deployment
    (admissions stop with retryable pushback, running engine lanes
    finish, stragglers fail retryably). Returns {rid: drained_clean}."""
    import ray_tpu as rt

    handles = _serve_replica_handles(app_name, deployment_name)
    refs = {r: h.drain.remote(timeout_s) for r, h in handles.items()}
    out = {}
    for r, ref in refs.items():
        try:
            out[r] = bool(rt.get(ref, timeout=timeout_s + 10))
        except Exception:  # noqa: BLE001 - replica died mid-drain
            out[r] = False
    return out


def engine_sanitizer_findings(app_name: str,
                              deployment_name: str) -> "int | None":
    """Total runtime-sanitizer (tools/rtsan, ISSUE 13) findings across
    a deployment's live replica engines — the ``sanitizer`` block
    ``engine.stats()`` carries while rtsan is active in the replica
    process (``RT_SAN=1``). Returns None when NO replica reports the
    block (sanitizer inactive), so callers can assert
    ``findings in (None, 0)`` and stay meaningful in both modes."""
    import ray_tpu as rt

    total, seen = 0, False
    for _rid, h in _serve_replica_handles(app_name,
                                          deployment_name).items():
        try:
            m = rt.get(h.get_metrics.remote(), timeout=10)
        except Exception:  # noqa: BLE001 - dead replica: nothing to read
            continue
        # The block's count is PER PROCESS: every engine in one replica
        # reports the same number, so take the max per replica (not the
        # sum) and add across replicas (distinct processes).
        per_replica = [int(est["sanitizer"].get("findings", 0))
                       for est in (m.get("engines") or [])
                       if est.get("sanitizer") is not None]
        if per_replica:
            seen = True
            total += max(per_replica)
    return total if seen else None


class ReplicaKiller:
    """Serve-aware sibling of ``WorkerKiller``: kills random replica
    ACTORS of one deployment while traffic runs, exercising the serve
    retry path (budgeted resubmission, membership refresh, controller
    heal) rather than the task-retry path.

    Usage::

        with ReplicaKiller("app", "Deployment", interval_s=0.5) as killer:
            ... drive traffic through the handle ...
        assert killer.kills > 0
    """

    def __init__(self, app_name: str, deployment_name: str,
                 interval_s: float = 0.5, max_kills: int = 1_000_000):
        self.app_name = app_name
        self.deployment_name = deployment_name
        self.interval_s = interval_s
        self.max_kills = max_kills
        self.kills = 0
        self.killed_rids: list = []
        self._stop = None
        self._thread = None

    def _loop(self):
        import random

        import ray_tpu as rt

        while not self._stop.is_set() and self.kills < self.max_kills:
            self._stop.wait(self.interval_s)
            if self._stop.is_set():
                return
            try:
                handles = _serve_replica_handles(self.app_name,
                                                 self.deployment_name)
            except Exception:  # noqa: BLE001 - serve tearing down
                return
            if not handles:
                continue
            rid = random.choice(list(handles))
            try:
                rt.kill(handles[rid])
                self.kills += 1
                self.killed_rids.append(rid)
            except Exception:  # noqa: BLE001 - already dead
                pass

    def start(self) -> "ReplicaKiller":
        import threading

        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="replica-killer", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._stop is not None:
            self._stop.set()
            self._thread.join(timeout=10)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

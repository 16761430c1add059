"""Test fixtures: virtual 8-device CPU mesh for jax + mini-cluster fixtures.

Mirrors the reference's test strategy (SURVEY.md §4): real mini-clusters
in-process per fixture, the same way ``ray_start_regular`` works
(reference: ``python/ray/tests/conftest.py:419``).
"""
import os

# Must run before jax backends initialize anywhere in the test process.
from ray_tpu.testing import force_host_devices  # noqa: E402

force_host_devices(8)
os.environ.setdefault("RT_HEALTH_CHECK_PERIOD_S", "0.2")
# The graft-entry dryrun's 1b pp×fsdp pass executes a real 1.2B-param
# train step — minutes of single-core work the DRIVER exercises at
# round end; inside the suite it would blow the per-test watchdog.
# The nano passes (all five parallelism combos) still run here.
os.environ.setdefault("RT_DRYRUN_SKIP_1B", "1")


# Stale-segment hygiene lives in the runtime, not here: synthetic test
# domains are swept by Cluster.shutdown/remove_node and NodeService.stop
# (each knows its own domain, so live clusters are never touched; a
# blanket mtime-based sweep would be unsafe — mmap writes don't update
# st_mtime).

import faulthandler  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

# ---- runtime sanitizer (tools/rtsan, ISSUE 13) -------------------------
# RT_SAN=1  -> sanitize EVERY test (and worker processes, which read the
#              same env in worker_main);
# unset     -> patch dormant, enforce only inside the opt-in modules
#              below (the highest-concurrency paths, sanitized on every
#              tier-1 run at ~one flag check of overhead elsewhere);
# RT_SAN=0  -> fully off: no patching at all (zero overhead).
_RT_SAN_MODE = os.environ.get("RT_SAN", "")
_RTSAN = None
if _RT_SAN_MODE != "0":
    _repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if _repo_root not in sys.path:
        sys.path.insert(0, _repo_root)
    import tempfile  # noqa: E402

    import tools.rtsan as _rtsan_mod  # noqa: E402

    _RTSAN = _rtsan_mod
    if _RT_SAN_MODE == "1":
        if not os.environ.get("RT_SAN_DIR"):
            # Worker processes drop their run artifacts here
            # (best-effort); the session gate merges them.
            os.environ["RT_SAN_DIR"] = tempfile.mkdtemp(prefix="rtsan-")
        else:
            # A caller-supplied dir may hold a PREVIOUS run's artifacts;
            # merging those would fail a now-clean suite with phantom
            # findings, so this run starts from an empty dir.
            import glob as _glob

            for _p in _glob.glob(
                    os.path.join(os.environ["RT_SAN_DIR"], "*.json")):
                try:
                    os.unlink(_p)
                except OSError:
                    pass
    _RTSAN.enable(active=(_RT_SAN_MODE == "1"))

#: Modules whose tests always run with enforcement on (and a per-test
#: leaked-thread watch over engine/drafter/pipeline start sites).
_RTSAN_OPT_IN = {
    "test_serve_engine", "test_serve_engine_paged",
    "test_serve_engine_spec", "test_serve_chaos", "test_data_llm",
    "test_rtsan",
}


@pytest.fixture(autouse=True)
def _rtsan_window(request):
    if _RTSAN is None:
        yield
        return
    name = getattr(getattr(request, "module", None), "__name__", "")
    if _RT_SAN_MODE == "1" or name.rpartition(".")[-1] in _RTSAN_OPT_IN:
        # thread_watch exits (and flags leaked drivers) while the
        # activation window is still open.
        with _RTSAN.activated(), _RTSAN.thread_watch():
            yield
    else:
        yield


def pytest_sessionfinish(session, exitstatus):
    """The rtsan --check-style gate: any NEW runtime finding (not
    inline-suppressed, not in the EMPTY-by-policy baseline) fails the
    suite, exactly like a new rtlint finding does."""
    if _RTSAN is None or not _RTSAN.is_enabled():
        return
    import glob
    import json

    if _RT_SAN_MODE == "1":
        # Worker artifacts are written by each worker's atexit hook —
        # which only runs once the worker EXITS. The reused rt_cluster
        # deliberately outlives the tests, so flush it now (idempotent;
        # the session atexit teardown becomes a no-op) and give the
        # dying workers a beat to dump before the merge below. Workers
        # killed uncleanly (SIGKILL chaos) still lose theirs — that
        # path is covered by the in-test engine stats sanitizer block.
        try:
            import ray_tpu as _rt

            if _rt.is_initialized():
                _rt.shutdown()
                import time as _time

                _time.sleep(0.5)
        except Exception:  # noqa: BLE001 - gate must never wedge exit
            pass

    extra = []
    d = os.environ.get("RT_SAN_DIR")
    if d and os.path.isdir(d):
        for p in sorted(glob.glob(os.path.join(d, "*.json"))):
            try:
                with open(p) as f:
                    extra.extend(json.load(f).get("findings", []))
            except Exception:  # noqa: BLE001 - torn worker artifact
                pass
    verdict = _RTSAN.gate(extra=extra)
    art = os.path.join(d, f"rtsan-{os.getpid()}.json") if d \
        else f"/tmp/rtsan-{os.getpid()}.json"
    try:
        _RTSAN.dump(art)
    except Exception:  # noqa: BLE001 - report-only path
        art = None
    if verdict["new"]:
        print("\nrtsan: NEW runtime findings — the gate fails the "
              "suite; fix them (preferred) or suppress inline with "
              "'# rtsan: disable=RSxxx <why>':")
        for f in verdict["new"]:
            print("  " + f.render().splitlines()[0])
        if art:
            print(f"rtsan: full report: "
                  f"python -m tools.rtsan --report {art}")
        session.exitstatus = 1


def pytest_configure(config):
    # Tier-1 runs `-m 'not slow'`; long chaos/soak variants opt out.
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 suite")

# Hang watchdog: any single test running >120s dumps every thread's stack
# AND every asyncio task's coroutine stack (the part thread dumps can't see)
# to /tmp/rt_stacks_<pid>.txt (pytest's fd capture would swallow stderr).
_stack_dump_file = open(f"/tmp/rt_stacks_{os.getpid()}.txt", "w")


def _dump_asyncio_tasks():
    import asyncio
    import threading as _threading

    f = _stack_dump_file

    loops = []
    try:
        from ray_tpu.core.worker import CoreWorker

        core = CoreWorker._current
        if core is not None and core._loop is not None:
            loops.append(("core", core._loop))
    except Exception:
        pass
    try:
        from ray_tpu import api as _api

        ht = _api._global_state.get("head_thread")
        if ht is not None and ht._loop is not None:
            loops.append(("head", ht._loop))
    except Exception:
        pass

    for name, loop in loops:
        done = _threading.Event()

        def dump(name=name, loop=loop, done=done):
            try:
                print(f"--- asyncio tasks: {name} loop ---", file=f)
                for t in asyncio.all_tasks(loop):
                    print(repr(t), file=f)
                    t.print_stack(file=f)
            finally:
                f.flush()
                done.set()

        try:
            loop.call_soon_threadsafe(dump)
            done.wait(5)
        except Exception:
            pass
    f.flush()


class TestHungError(Exception):
    """Raised IN the hung test by the watchdog — a hang becomes a FAILURE
    with stacks on disk, never a silent multi-hour stall (round-4
    post-mortem: one lost RPC reply hung the cold suite for 55 min)."""


_WATCHDOG_S = float(os.environ.get("RT_TEST_WATCHDOG_S", "300"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    import signal
    import threading as _threading

    done = _threading.Event()
    # Serializes "watchdog fires" against "teardown begins": teardown
    # sets done as its first statement and then passes through this
    # gate before restoring the handler; the watchdog re-checks done
    # under the gate right before pthread_kill, and the signal handler
    # itself re-checks done at delivery. Together these close both
    # SIGALRM races (ADVICE.md low): a test finishing at the deadline
    # can't be failed post-hoc, and a stack dump outlasting the
    # finally's join can't fire into a restored (default) handler and
    # kill pytest.
    kill_gate = _threading.Lock()

    def watch():
        if not done.wait(_WATCHDOG_S):
            print(f"=== WATCHDOG: {item.nodeid} hung ===",
                  file=_stack_dump_file)
            faulthandler.dump_traceback(file=_stack_dump_file,
                                        all_threads=True)
            _dump_asyncio_tasks()
            # Fail the test rather than hang the suite. The signal lands
            # in the MAIN thread (test body); loops on worker threads
            # keep running so teardown fixtures can still clean up.
            import signal as _signal

            with kill_gate:
                # Teardown may have begun while the (slow) stack dumps
                # ran: once done is set the test finished — firing now
                # would fail it after the fact (or, after the handler
                # restore, terminate the whole process).
                if done.is_set():
                    return
                try:
                    _signal.pthread_kill(_threading.main_thread().ident,
                                         _signal.SIGALRM)
                except Exception:
                    pass

    def _raise(signum, frame):
        # The handler runs on the main thread, possibly only once it
        # re-enters the interpreter INSIDE the finally below — after the
        # test body already returned. done is the test-completion fact,
        # so a late-delivered signal becomes a no-op instead of failing
        # a finished test from its own teardown.
        if done.is_set():
            return
        raise TestHungError(
            f"{item.nodeid} exceeded {_WATCHDOG_S}s watchdog; stacks in "
            f"/tmp/rt_stacks_{os.getpid()}.txt")

    prev = signal.signal(signal.SIGALRM, _raise)
    t = _threading.Thread(target=watch, daemon=True)
    t.start()
    try:
        return (yield)
    finally:
        # done FIRST (single atomic call): both the watchdog's gate
        # check and the signal handler consult it, so a kill decided or
        # delivered from here on is a no-op.
        done.set()
        with kill_gate:
            # Barrier only: if the watchdog is mid-decision, wait it
            # out before restoring the handler.
            pass
        # The join is best-effort (a slow dump may outlast it); the
        # done/gate pair above keeps a late watchdog from firing either
        # way, so restoring the handler here is safe even on timeout.
        t.join(timeout=10)
        try:
            signal.signal(signal.SIGALRM, prev)
        except Exception:
            pass


@pytest.fixture
def rt_cluster():
    """A running 8-CPU cluster, reused across tests (re-inits if torn down)."""
    import ray_tpu as rt

    rt.init(num_cpus=8, num_tpus=0, ignore_reinit_error=True)
    yield rt
    # Leave running for reuse; session-level atexit handles final teardown.


@pytest.fixture
def rt_fresh():
    """A fresh cluster per test (for failure-injection tests)."""
    import ray_tpu as rt

    if rt.is_initialized():
        rt.shutdown()
    rt.init(num_cpus=8, num_tpus=0)
    yield rt
    rt.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    """Ensure jax sees 8 virtual CPU devices."""
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual cpu devices, got {devs}"
    return devs

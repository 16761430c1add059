"""A serving cell's traced slice (PR 31): counted in launches with the
seconds as its limit, ended by a watcher beside the trace; what the
window's side asks of the replica; and a traced run that has no trace
to read says why, in the run's last line, before any reader runs.

REHEARSALS, like ``test_perf_addition.py``: control flow on the CPU,
no number here is a measurement of any device.
"""
import json
import os
import threading
import time

import pytest

import perf_testlib as L

import perf_deployment
import perf_harness as H
import perf_serve_cell


# ---- the slice's end

def test_wait_slice_reads_the_counter_no_more_often_than_its_period():
    now = [100.0]
    naps, reads = [], []

    def sleep(s):
        naps.append(s)
        now[0] += s

    def advanced():
        reads.append(now[0])
        return int((now[0] - 100.0) / 0.25)     # a launch every 250 ms

    got = perf_deployment.wait_slice(advanced, 4, 108.0, 0.1,
                                     clock=lambda: now[0], sleep=sleep)
    assert got == "launches"
    assert set(naps) == {0.1} and len(reads) == len(naps)
    # within one reading of the fourth launch, which came at 101.0
    assert 101.0 <= now[0] < 101.0 + 0.1 + 1e-9
    # a counter that stalls: the seconds end it, within one reading
    now[0], stalled = 100.0, []
    got = perf_deployment.wait_slice(lambda: stalled.append(1) or 3, 4,
                                     100.75, 0.1, clock=lambda: now[0],
                                     sleep=sleep)
    assert got == "seconds" and 100.75 <= now[0] < 100.85 + 1e-9


@pytest.fixture
def no_profiler(monkeypatch):
    """The Tracer as it is, around a profiler that records nothing."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    return calls


class _Engine:
    """A counter the test drives: a launch every ``every_s`` until
    ``stall_at`` launches."""

    def __init__(self, every_s, stall_at=10 ** 9):
        self.t0 = time.monotonic()
        self.every_s, self.stall_at = every_s, stall_at

    def dispatches(self):
        return 7 + min(int((time.monotonic() - self.t0) / self.every_s),
                       self.stall_at)


def _tracer(tmp_path, counter):
    return perf_deployment.Tracer(str(tmp_path), "no_such_file.py",
                                  "nothing", counter=counter)


def test_a_slice_counted_in_launches_ends_by_them(tmp_path, no_profiler):
    eng = _Engine(0.05)
    tr = _tracer(tmp_path, eng.dispatches)
    t_a = time.monotonic()
    tr.start(launches=6, limit_s=5.0)
    sl = tr.stop()                  # waits for the watcher
    t_b = time.monotonic()
    assert no_profiler == ["start", "stop"]
    assert sl["ended_by"] == "launches"
    # six launches take 0.3 s; the watcher reads every 100 ms
    assert 6 <= sl["launches"] <= 9 and 0.25 <= sl["slice_s"] < 0.6
    assert t_a <= sl["t_start_s"] < sl["t_stop_s"] <= t_b
    assert sl["slice_s"] == pytest.approx(sl["t_stop_s"] - sl["t_start_s"])
    assert sl["mid_s"] == pytest.approx(
        (sl["t_start_s"] + sl["t_stop_s"]) / 2)
    assert sl["trace_stop_s"] >= 0
    h = tr.handoff()
    assert (h["launches"], h["ended_by"], h["slice_s"]) == \
        (sl["launches"], "launches", sl["slice_s"])
    assert h["log_dir"] == str(tmp_path) and h["trace_stop_s"] >= 0
    assert (h["t_stop"] - h["t_start"]) / 1e9 == pytest.approx(
        sl["slice_s"])
    json.dumps(h)                   # it travels as JSON


def test_a_counter_that_stalls_leaves_the_end_to_the_seconds(
        tmp_path, no_profiler):
    eng = _Engine(0.05, stall_at=3)
    tr = _tracer(tmp_path, eng.dispatches)
    tr.start(launches=6, limit_s=0.5)
    sl = tr.stop()
    assert sl["ended_by"] == "seconds" and sl["launches"] == 3
    assert 0.5 <= sl["slice_s"] < 0.5 + 2 * tr.WATCH_PERIOD_S
    assert sl["mid_s"] == pytest.approx(sl["t_start_s"]
                                        + sl["slice_s"] / 2)
    assert no_profiler == ["start", "stop"]


def test_without_trace_launches_the_caller_ends_the_slice(
        tmp_path, no_profiler):
    """A mix without ``trace_launches`` (the rehearsal's, rag-burst):
    as before PR 31, the slice lasts from ``start()`` to ``stop()``;
    training's Tracer has no counter and counts nothing."""
    eng = _Engine(0.05)
    tr = _tracer(tmp_path, eng.dispatches)
    tr.start(None, 0.3)
    assert tr._watcher is None
    time.sleep(0.3)
    sl = tr.stop()
    assert sl["ended_by"] == "seconds" and 5 <= sl["launches"] <= 8
    assert 0.3 <= sl["slice_s"] < 0.45
    tr = _tracer(tmp_path, None)
    tr.start()
    sl = tr.stop()
    assert sl["ended_by"] is None and sl["launches"] is None
    assert no_profiler == ["start", "stop"] * 2


# ---- what the window's side asks of the replica

class _Handle:
    """Stands where the serve handle does: ``h.<method>.remote(*a)
    .result()``, every call noted with its time."""

    def __init__(self, fail=()):
        self.calls, self.fail = [], fail

    def __getattr__(self, name):
        handle = self

        class _Method:
            def remote(self, *args):
                handle.calls.append((name, args, time.monotonic()))
                return self

            def result(self):
                if name in handle.fail:
                    raise RuntimeError(f"planted in {name}")
                if name == "trace_stop":
                    return {"mid_s": 1234.5, "slice_s": 0.2,
                            "launches": 5, "ended_by": "launches",
                            "trace_stop_s": 0.1}
                if name == "trace_result":
                    return {"log_dir": "d", "samples": []}
                return {"t": time.monotonic(), "stats": {}}

        return _Method()


def _side(mix, fail=()):
    handle, tracer = _Handle(fail), {"red": None}
    t0 = time.monotonic() + 0.05
    perf_serve_cell._window_side(handle, t0, t0 + 1.2, 1, mix, tracer)
    return handle, tracer, t0


def test_the_windows_side_hands_the_count_and_the_limit_to_the_replica():
    mix = {"trace_after_s": 0.2, "trace_s": 0.4, "trace_launches": 5}
    handle, tracer, t0 = _side(mix)
    assert [c[0] for c in handle.calls] == [
        "trace_start", "trace_stop", "report", "trace_result"]
    start, stop = handle.calls[0], handle.calls[1]
    assert start[1][0] == 5 and start[1][1] == pytest.approx(0.4)
    assert start[2] == pytest.approx(t0 + 0.2, abs=0.05)
    assert stop[2] - start[2] < 0.05    # waits in the replica, not here
    assert tracer["mid"] == 1234.5      # as it was, not as planned
    assert tracer["slice"]["ended_by"] == "launches"
    assert tracer["handoff"] == {"log_dir": "d", "samples": []}
    assert "error" not in tracer


def test_a_mix_without_trace_launches_is_traced_for_its_seconds():
    for name in ("nano-chat", "rag-burst"):
        with open(L.fixture(name + ".json")) as f:
            assert "trace_launches" not in json.load(f)
    handle, tracer, t0 = _side({"trace_after_s": 0.2, "trace_s": 0.4})
    start, stop = handle.calls[0], handle.calls[1]
    assert start[1][0] is None
    assert stop[2] == pytest.approx(t0 + 0.6, abs=0.05)
    # the limit is the window's end less half a second, as it was
    handle, tracer, t0 = _side({"trace_after_s": 0.2, "trace_s": 4.0,
                                "trace_launches": 9})
    assert handle.calls[0][1] == (9, pytest.approx(0.5))


def test_the_serving_mixes_state_their_launches_and_keep_their_seconds():
    chat, batch = (H.load_mix(n) for n in ("chat-steady", "batch-offline"))
    assert (chat["trace_launches"], chat["trace_s"],
            chat["trace_after_s"]) == (22, 8, 5)
    assert (batch["trace_launches"], batch["trace_s"],
            batch["trace_after_s"]) == (11, 12, 5)
    for mix in (chat, batch):
        assert "trace_launches" in mix["why"]


# ---- a lost trace says so

HEALTH = {"driver_restarts": 0, "pid_before": 41, "pid_after": 41}
SLICE = {"trace_stop_s": 7.25, "slice_s": 6.9, "launches": 24,
         "ended_by": "launches"}


def test_lost_trace_names_the_cause_in_order():
    lost = perf_serve_cell.lost_trace
    home = {"slice": SLICE, "red": {"cost": dict(SLICE,
                                                 device_events=140000)}}
    assert lost(home, HEALTH) is None
    # a restarted driver with the trace home stays correct: false
    assert lost(home, dict(HEALTH, driver_restarts=1)) is None
    msg = lost({"slice": SLICE, "error": "trace_result: Boom(x y)"},
               dict(HEALTH, driver_restarts=2))
    assert msg == ("the traced slice was lost: trace_result: Boom(x y) "
                   "(trace_stop_s 7.25, slice_s 6.9, launches 24, "
                   "ended_by launches, driver_restarts 2)")
    moved = dict(HEALTH, pid_after=58)
    assert lost(home, moved) == (
        "the replica was replaced during the run (pid 41 -> 58); "
        "trace_stop_s 7.25, slice_s 6.9, launches 24, ended_by launches, "
        "device_events 140000, driver_restarts 0")
    # both: the lost slice first, and the replacement behind it
    msg = lost({"error": "trace_result: AttributeError()"}, moved)
    assert msg.startswith("the traced slice was lost: trace_result: "
                          "AttributeError() (driver_restarts 0; the "
                          "replica was replaced, pid 41 -> 58)")
    assert "\n" not in msg


def test_a_side_thread_that_fails_reports_where(capsys):
    handle, tracer, _t0 = _side({"trace_after_s": 0.1, "trace_s": 0.2,
                                 "trace_launches": 3},
                                fail=("trace_stop",))
    assert tracer["error"] == \
        "trace_stop: RuntimeError(planted in trace_stop)"
    assert perf_serve_cell._one_line(ValueError("a\n b\tc" * 200)) == \
        "ValueError(" + ("a b c" * 200)[:400] + ")"
    assert "handoff" not in tracer and tracer["red"] is None
    assert "WINDOW-SIDE ERROR" in capsys.readouterr().out


def _planted_copy(tmp_path, plant):
    with open(L.fixture("nano-serve.json")) as f:
        conf = json.load(f)
    conf["architecture"], conf["plant"] = "lossy_trace", plant
    conf_path = os.path.join(str(tmp_path), "nano-lossy.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    return L.copy_with_additions(
        tmp_path, configs=[("nano-lossy", conf_path)],
        mixes=[("nano-chat", L.fixture("nano-chat.json"))],
        architectures=[("lossy_trace.py", L.fixture("lossy_trace.py"))],
        cells=[L.cell("nano-lossy", "nano-lossy", "nano-chat")],
        join={"nano-lossy": "cgpt1b3-chat-steady"})


@pytest.mark.parametrize("plant,trace,rc_want,last", [
    ("trace_stop_raises", 1, 2,
     r"^perfbench: the traced slice was lost: trace_stop: .*planted: "
     r"the profiler could not stop.*\(driver_restarts 0\)$"),
    ("pid_differs", 1, 2,
     r"^perfbench: the replica was replaced during the run \(pid (\d+) "
     r"-> (\d+)\); trace_stop_s [\d.e-]+, slice_s [\d.]+, launches \d+, "
     r"ended_by seconds, device_events 0, driver_restarts 0$"),
    ("pid_differs", 0, 0, r"^perfbench correct: False$")])
def test_a_planted_loss_ends_a_traced_run_with_its_own_message(
        tmp_path, plant, trace, rc_want, last):
    """Through the handle and the replica at ``nano`` on the CPU
    (``fixtures/lossy_trace.py``, added to a copy as an architecture).
    A traced run fails with exit 2 and the cause as the last line of
    standard error; an UNTRACED run whose replica was replaced stays
    what it was: ``correct: false``, exit 0."""
    import re

    root = _planted_copy(tmp_path, plant)
    rc, out, err = L.run_copy(
        root, "--workload", "nano-lossy", "--seed", str(2 ** 31 + 31),
        "--seconds", "4", "--trace", str(trace), "--rehearsal")
    assert rc == rc_want, (out[-5:], err[-2000:])
    tail = err.strip().splitlines()[-1]
    m = re.match(last, tail)
    assert m, tail
    result = [ln for ln in out if ln.startswith("{") and '"metrics"' in ln]
    if trace:
        assert not result               # no result line
        if plant == "pid_differs":
            assert int(m.group(2)) == int(m.group(1)) + 1
        else:
            assert any(ln.startswith("WINDOW-SIDE ERROR") for ln in out)
    else:
        assert json.loads(result[-1])["correct"] is False


def test_a_trace_that_is_there_and_a_stale_reader_names_the_reader():
    """The reader's own message is kept for the case it was written
    for: the trace came home and a reader finds nothing in it."""
    import run as perf_run

    found = H.find_cell(L.benchmark(), "cgpt1b3-chat-steady")
    red = {"devices": 1, "busy_s": 1.0, "window_s": 2.0, "scopes": {},
           "programs": {}, "launches_by_host": {}, "cost": dict(SLICE)}
    found["per_layer"] = [m for m in found["per_layer"]
                          if m["name"] == "decode_step_dev_ms"]
    res = {"run": {"trace": red, "conf": {"engine": {"chunk": 8}}}}
    with pytest.raises(H.BenchError, match=(
            r"per-layer metric decode_step_dev_ms found nothing to read "
            r"in cgpt1b3-chat-steady: its reader \(layer_metrics/"
            r"decode_step_dev_ms\.py\) no longer matches")):
        perf_run._metrics(found, res, 1, strict=True)
    assert perf_run._metrics(found, res, 1, strict=False) == {}

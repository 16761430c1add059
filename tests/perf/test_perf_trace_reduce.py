"""The reduction from trace to numbers: on hand-made events whose
answers are known, and on a piece of a trace recorded on the chip."""
import json
import os

import pytest

import perf_testlib

import trace_reduce as R

OP = "%{} = bf16[4,8]{{1,0}} fusion(bf16[4,8]{{1,0}} %p)"


def _trace(ops, modules, host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": list(host)}]}]}


def test_busy_is_the_union_and_a_while_is_charged_its_own_time_only():
    ops = [["%while.1 = (s32[]) while(%t)", 100, 900],
           [OP.format("fusion.1"), 100, 300],
           [OP.format("fusion.2"), 500, 400],
           [OP.format("copy.3"), 1200, 100]]
    red = R.reduce(_trace(ops, [["jit_a(1)", 100, 900],
                                ["jit_b(2)", 1200, 100]]),
                   window=(0, 1500))
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(1000e-9)
    assert red["window_s"] == pytest.approx(1500e-9)
    assert red["idle_s"] == pytest.approx(500e-9)
    ops_s = dict((k, v) for k, v in red["device_ops"])
    assert ops_s["fusion.2 bf16[4,8]"] == pytest.approx(400e-9)
    assert ops_s["while.1 (s32[]"] == pytest.approx(200e-9)
    assert sum(ops_s.values()) == pytest.approx(red["busy_s"])
    assert red["programs"]["jit_a(1)"] == {"launches": 1.0,
                                           "seconds": 900e-9}
    assert red["idle_gaps"] == [["unattributed", pytest.approx(500e-9)]]


def test_gaps_and_launches_are_attributed_to_what_the_host_was_doing():
    ops = [[OP.format("fusion.1"), 100, 900],
           [OP.format("fusion.2"), 1200, 100]]
    mods = [["jit__unknown(1)", 100, 900], ["jit__unknown(2)", 1200, 100],
            ["jit__unknown(1)", 1450, 500]]      # runs past the window
    samples = [(0, "engine.py:_run"), (150, "engine.py:_dispatch_chunk"),
               (950, "engine.py:evict_lru"),
               (1100, "engine.py:_prefill_paged")]
    red = R.reduce(_trace(ops, mods), window=(0, 1500), samples=samples,
                   host_offset_ns=50)
    gaps = dict((k, v) for k, v in red["idle_gaps"])
    # gap 1000..1200: evict_lru from 1000 to 1150, then prefill
    assert gaps["engine.py:evict_lru"] == pytest.approx(150e-9)
    assert gaps["engine.py:_prefill_paged"] == pytest.approx(250e-9)
    by = red["launches_by_host"]
    assert by["engine.py:_dispatch_chunk"] == {
        "launches": 1, "seconds": pytest.approx(900e-9),
        "programs": {"jit__unknown(1)": {
            "launches": 1, "seconds": pytest.approx(900e-9)}}}
    assert by["engine.py:_prefill_paged"]["launches"] == 1
    assert sum(g["launches"] for g in by.values()) == 2   # whole ones


def test_mean_over_devices_and_window_clipping():
    tr = _trace([[OP.format("fusion.1"), 0, 1000]], [["jit_a(1)", 0, 1000]])
    tr["planes"].insert(1, {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [[OP.format("fusion.1"), 0, 500]]},
        {"name": "XLA Modules", "events": [["jit_a(1)", 0, 500]]}]})
    red = R.reduce(tr, window=(200, 1000))
    assert red["devices"] == 2
    assert red["busy_s_by_device"] == [pytest.approx(800e-9),
                                       pytest.approx(300e-9)]
    assert red["busy_s"] == pytest.approx(550e-9)
    assert R.reduce({"planes": []}) == {"devices": 0}


def test_short_name_keeps_the_hlo_name_and_result_shape():
    assert R.short_name(
        "%convert.49 = f32[4096,16,16,128]{3,2,1,0:T(8,128)} convert("
        "bf16[4096,16,16,128]{3,2,1,0} %x)") == \
        "convert.49 f32[4096,16,16,128]"
    assert R.short_name("jit_step(123)") == "jit_step(123)"


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(perf_testlib.PERF, "recorded", "trace_small.json")
    with open(path) as f:
        return json.load(f)


def test_recorded_chip_trace_reduces_to_sane_numbers(recorded):
    """The events that begin in 0.7 s of ``cgpt1b3-chat-steady`` on a
    TPU v5 lite (see the file's ``origin``), to the end of the last:
    a decode chunk and the prefills around it."""
    red = R.reduce(recorded, window=tuple(recorded["window"]),
                   samples=[tuple(s) for s in recorded["samples"]],
                   host_offset_ns=recorded["host_offset_ns"])
    assert red["devices"] == 1
    assert 0.7 < red["window_s"] < 2.0
    assert 0.5 * red["window_s"] < red["busy_s"] <= red["window_s"]
    assert red["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"],
                                          abs=1e-6)
    assert len(red["device_ops"]) == 10
    secs = [s for _n, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all("[" in n for n, _s in red["device_ops"])
    import perf_harness as H

    run = {"trace": red, "conf": {"engine": {"chunk": 8}}}
    per_step_ms = H.load_reader("decode_step_dev_ms").read(run)
    assert 50 < per_step_ms < 150      # 87 ms in the run it is cut from
    share = H.load_reader("prefill_dev_share_pct").read(run)
    assert 0 < share < 30
    assert sum(s for _n, s in red["idle_gaps"]) == \
        pytest.approx(red["idle_s"], rel=1e-6)

"""The readers of a decode launch's own account (PR 42): the clock's
counted steps, the prefills inside a gap, the driver thread's CPU time,
and the prefill program found by its name. Each on a hand-made run, the
nine entries that wait in ``fixtures/launch-counter-entries.json`` (see
its ``origin``) and the one appended to BENCHMARK.json against the
contract, and a rehearsal at ``nano`` in which they read a real
engine's counters. Numbers of a rehearsal measure no device.
"""
import json
import os

import pytest

import perf_testlib as L

import perf_harness as H

with open(os.path.join(L.FIXTURES, "launch-counter-entries.json")) as f:
    WAITING = json.load(f)["per_layer"]
#: reads what the parent's trace already offers
APPENDED = "prefill_prog_dev_ms.sat"
#: its plain twin, the file it reads through: listed nowhere (the
#: fixture's ``origin``: chat-steady's slice may hold no prefill)
PLAIN = "prefill_prog_dev_ms"
SOLAR2 = ["solar2-ep8-reason-offline"]
CLOSED_LOOP = ["cgpt1b3-batch-offline", "axk1-ep16-reason-offline",
               "solar2-ep8-reason-offline"]

MS = 10 ** 6
#: engine.stats() differenced over a window, as run["stats_delta"] has
#: it: 50 launches of 800 ms (20 enqueue + 760 wait + 15 read + 5 between
#: the steps), 200 ms of gap after each of which 120 a prefill
DELTA = {
    "dispatches": 50, "prefills": 100,
    "decode_gap_ns_sum": 10_000 * MS,
    "decode_gap_prefill_ns_sum": 6_000 * MS,
    "driver_ns_decode": 40_000 * MS,
    "driver_ns_decode_enqueue": 1_000 * MS,
    "driver_ns_decode_wait": 38_000 * MS,
    "driver_ns_decode_read": 750 * MS,
    "driver_ns_deliver": 2_500 * MS, "driver_cpu_ns_deliver": 1_000 * MS,
    "driver_ns_admit": 200 * MS, "driver_cpu_ns_admit": 120 * MS,
    "driver_ns_cover": 50 * MS, "driver_cpu_ns_cover": 50 * MS,
    "driver_ns_other": 1_250 * MS, "driver_cpu_ns_other": 30 * MS,
    "driver_ns_prefill": 6_000 * MS,
    "driver_ns_prefill_key": 150 * MS,
    "driver_ns_prefill_dispatch": 350 * MS,
    "driver_ns_prefill_read": 5_400 * MS,
}
PREFILL = "jit_prefill_into_slot_paged"
CHUNK = "jit_decode_chunk_slots_paged(7)"
TRACE = {
    "programs": {CHUNK: {"launches": 5.0, "seconds": 3.0},
                 PREFILL + "(9)": {"launches": 3.5, "seconds": 0.14},
                 PREFILL + "(4)": {"launches": 1.5, "seconds": 0.11}},
    "launches_by_host": {
        "engine.py:_prefill_paged": {
            "launches": 9, "seconds": 0.18, "programs": {
                PREFILL + "(9)": {"launches": 3, "seconds": 0.12},
                PREFILL + "(4)": {"launches": 1, "seconds": 0.05},
                "jit__threefry_seed(3)": {"launches": 5,
                                          "seconds": 1e-5}}},
        "engine.py:_run": {"launches": 1, "seconds": 0.03, "programs": {
            PREFILL + "(4)": {"launches": 1, "seconds": 0.03}}},
        "engine.py:_dispatch_chunk": {
            "launches": 3, "seconds": 2.4, "programs": {
                CHUNK: {"launches": 3, "seconds": 2.4}}}},
}
RUN = {"stats_delta": DELTA, "trace": TRACE,
       "conf": {"engine": {"chunk": 8}}}
WANT = {
    # lane = 10000 + 40000 ms; 6000 of it other requests' prefills
    "lane_prefill_stall_pct.sat": 12.0,
    # (10000 - 6000 + 1000 + 750) / 50000
    "lane_host_stall_pct.sat": 11.5,
    "launch_enqueue_ms.sat": 20.0,          # 1000 ms / 50 launches
    "launch_read_ms.sat": 15.0,             # 750 / 50
    "launch_deliver_ms.sat": 50.0,          # 2500 / 50
    "deliver_offcpu_pct.sat": 60.0,         # 1 - 1000 / 2500
    "prefill_enqueue_ms.sat": 5.0,          # (150 + 350) / 100 prefills
    # admit, cover, deliver, other: 1 - (120 + 50 + 1000 + 30)
    # / (200 + 50 + 2500 + 1250)
    "host_offcpu_pct.sat": 70.0,
    "prefill_wait_ms.sat": 54.0,            # 5400 / 100 prefills
    # whole launches only, every bucket and host label:
    # (0.12 + 0.05 + 0.03) s / (3 + 1 + 1) launches
    "prefill_prog_dev_ms": 40.0,
    "prefill_prog_dev_ms.sat": 40.0,
}
#: the key each counter reader divides by
OVER = {"launch_enqueue_ms.sat": "dispatches",
        "launch_read_ms.sat": "dispatches",
        "launch_deliver_ms.sat": "dispatches",
        "prefill_enqueue_ms.sat": "prefills",
        "prefill_wait_ms.sat": "prefills",
        "deliver_offcpu_pct.sat": "driver_ns_deliver",
        "host_offcpu_pct.sat": "driver_ns_deliver",
        "lane_prefill_stall_pct.sat": "driver_ns_decode",
        "lane_host_stall_pct.sat": "driver_ns_decode"}
#: stats_delta of a program without this PR's counters (its parent)
PARENT = {k: v for k, v in DELTA.items()
          if k in ("dispatches", "prefills", "decode_gap_ns_sum",
                   "driver_ns_decode", "driver_ns_deliver",
                   "driver_ns_prefill", "driver_ns_admit",
                   "driver_ns_cover", "driver_ns_other")}
#: reads a phase PR 24 brought, so the parent has it; it waits with the
#: eight that the parent cannot feed so that the launch's account
#: arrives whole
ON_PARENT = {"launch_deliver_ms.sat": 50.0}


def _entry(name):
    by_name = {e["name"]: e for e in WAITING}
    by_name.update({m["name"]: m for m in L.benchmark()["per_layer"]
                    if m["name"] == APPENDED})
    # the plain twin is its twin's entry but for the metric it moves
    by_name[PLAIN] = dict(by_name[APPENDED], moves="tpot_mean_ms")
    return by_name[name]


@pytest.mark.parametrize("name", list(WANT))
def test_reader_on_a_hand_made_run(name):
    reader = H.load_reader(name)
    assert reader.read(RUN) == pytest.approx(WANT[name])
    # a program without the counters (the parent of PR 42) and an
    # untraced run: nothing, never 0, no raise
    assert reader.read({"stats_delta": PARENT, "trace": None,
                        "conf": RUN["conf"]}) == ON_PARENT.get(name)
    assert reader.read({"conf": RUN["conf"]}) is None
    if name in OVER:
        # a window without a launch (a prefill; any lane or host time)
        none = dict(DELTA, **{OVER[name]: 0}, decode_gap_ns_sum=0,
                    driver_ns_admit=0, driver_ns_cover=0,
                    driver_ns_other=0)
        assert reader.read(dict(RUN, stats_delta=none)) is None
    else:
        # a slice that holds launches of other programs alone
        assert reader.read(dict(RUN, trace={
            "programs": {CHUNK: TRACE["programs"][CHUNK]},
            "launches_by_host": {"engine.py:_dispatch_chunk": TRACE[
                "launches_by_host"]["engine.py:_dispatch_chunk"]}})) \
            is None
    entry = _entry(name)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == \
        (entry["layer"], entry["unit"], entry["source"], entry["moves"])


def test_the_three_shares_of_lane_time_sum_to_it():
    """Another request's prefill, the host, the wait for the device:
    100 but for the stamps between a launch's steps (5 of 800 ms)."""
    lane = DELTA["decode_gap_ns_sum"] + DELTA["driver_ns_decode"]
    parts = (H.load_reader("lane_prefill_stall_pct.sat").read(RUN)
             + H.load_reader("lane_host_stall_pct.sat").read(RUN)
             + 100.0 * DELTA["driver_ns_decode_wait"] / lane)
    assert parts == pytest.approx(100 - 100 * 250 * MS / lane)
    # and the two stalls are decode_stall_pct plus the launch's own
    # host steps: nothing of the gap is counted twice or dropped
    stall = H.load_reader("decode_stall_pct.sat").read(RUN)
    own = 100.0 * (DELTA["driver_ns_decode_enqueue"]
                   + DELTA["driver_ns_decode_read"]) / lane
    assert WANT["lane_prefill_stall_pct.sat"] \
        + WANT["lane_host_stall_pct.sat"] == pytest.approx(stall + own)


def test_clipped_programs_stand_in_where_no_whole_launches_are_kept():
    run = dict(RUN, trace={"programs": TRACE["programs"]})
    assert H.load_reader("prefill_prog_dev_ms").read(run) == \
        pytest.approx(1e3 * 0.25 / 5)


def test_entries_keep_the_contract():
    """The nine that wait are complete entries for the three
    closed-loop cells (delivery's own off-CPU share for the one cell in
    which it reads: the fixture's ``origin``); the one appended is in
    BENCHMARK.json for the same three; all ten against the contract's
    forms. Once a benchmark PR has appended the nine, each is there as
    it is kept here, its list BEGINNING with the list kept here (a
    later cell may be appended, as for
    ``engine-counter-entries.json``)."""
    import test_perf_benchmark_json as C

    bench = L.benchmark()
    layers = {m["layer"] for m in bench["per_layer"]}
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [e["name"] for e in WAITING] == [n for n in WANT
                                            if n not in (APPENDED, PLAIN)]
    assert PLAIN not in listed
    for e in WAITING + [listed[APPENDED]]:
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert C.NAME.match(e["name"]) and C.UNIT.match(e["unit"])
        assert e["source"] in C.SOURCES and e["layer"] in layers
        assert e["better"] == "lower"
        first = SOLAR2 if e["name"] == "deliver_offcpu_pct.sat" \
            else CLOSED_LOOP
        assert e["workloads"][:len(first)] == first
        moved = next(m for m in bench["end_to_end"]
                     if m["name"] == e["moves"])
        assert set(e["workloads"]) <= set(moved["workloads"])
        assert os.path.exists(os.path.join(
            L.PERF, "layer_metrics", e["name"] + ".py"))
        got = listed.get(e["name"], e)
        assert dict(got, workloads=e["workloads"]) == e
        assert got["workloads"][:len(e["workloads"])] == e["workloads"]


def test_why_the_nine_wait():
    """On the chip (``strict``) run.py fails a traced run whose listed
    metric reads nothing, and the driver runs the parent with the
    change's benchmark files: listed, eight of the nine would fail
    every traced run of a parent that lacks the counters."""
    import run as perf_run

    for entry in WAITING:
        found = {"cell": {"name": "c"}, "per_layer": [entry]}
        res = {"run": {"stats_delta": PARENT}}
        if entry["name"] not in ON_PARENT:
            assert perf_run._metrics(found, res, 1, strict=False) == {}
            with pytest.raises(H.BenchError, match=entry["name"]):
                perf_run._metrics(found, res, 1, strict=True)
        res["run"]["stats_delta"] = DELTA
        assert perf_run._metrics(found, res, 1, strict=True) == {
            entry["name"]: {"value": pytest.approx(WANT[entry["name"]]),
                            "unit": entry["unit"]}}


def test_rehearsal_reads_a_real_engines_counters(tmp_path):
    """The nine entries, joined to a nano closed-loop cell in a copy,
    read the counters of the engine that served the window. The one
    that reads a device plane finds none in a CPU trace and is left
    out."""
    cell = L.cell("nano-batch", "nano-serve", "nano-batch")
    root = L.copy_with_additions(
        tmp_path,
        configs=[("nano-serve", L.fixture("nano-serve.json"))],
        mixes=[("nano-batch", L.fixture("nano-batch.json"))],
        cells=[cell],
        metrics=[("per_layer", dict(e, workloads=["nano-batch"]))
                 for e in WAITING],
        join={"nano-batch": "cgpt1b3-batch-offline"})
    rc, out, err = L.run_copy(
        root, "--workload", "nano-batch", "--seed", str(2 ** 31 + 42),
        "--seconds", "5", "--trace", "1", "--rehearsal")
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert not [e["name"] for e in WAITING if e["name"] not in got]
    assert 0 < got["lane_prefill_stall_pct.sat"] < 100
    assert 0 < got["lane_host_stall_pct.sat"] < 100
    # the two stalls hold decode_stall_pct and the launch's host steps
    assert got["lane_prefill_stall_pct.sat"] \
        + got["lane_host_stall_pct.sat"] > got["decode_stall_pct.sat"]
    for name in ("launch_enqueue_ms.sat", "launch_read_ms.sat",
                 "launch_deliver_ms.sat", "prefill_enqueue_ms.sat",
                 "prefill_wait_ms.sat"):
        assert got[name] > 0, name
    # a prefill on the host is its steps and what lies between them
    assert got["prefill_enqueue_ms.sat"] + got["prefill_wait_ms.sat"] \
        < got["prefill_host_mean_ms.sat"]
    assert -5 < got["deliver_offcpu_pct.sat"] < 100
    assert -5 < got["host_offcpu_pct.sat"] < 100
    # no device plane in a CPU trace: nothing to read, left out
    assert APPENDED not in got


def test_recorded_trace_holds_six_named_prefills():
    """A piece of a chip trace of PR 24 (see the file's ``origin``:
    six prefills and the decode chunk after them): the prefill program
    read by name is the launches the sampler's label picks."""
    import trace_reduce as R

    with open(os.path.join(L.PERF, "recorded", "trace_named.json")) as f:
        rec = json.load(f)
    red = R.reduce(rec, window=tuple(rec["window"]),
                   samples=[tuple(s) for s in rec["samples"]],
                   host_offset_ns=rec["host_offset_ns"])
    whole = [p for g in red["launches_by_host"].values()
             for name, p in g["programs"].items()
             if name.startswith(PREFILL + "(")]
    assert sum(p["launches"] for p in whole) == 6
    run = {"trace": red, "conf": {"engine": {"chunk": 8}}}
    by_name = H.load_reader("prefill_prog_dev_ms").read(run)
    assert by_name == pytest.approx(
        1e3 * sum(p["seconds"] for p in whole) / 6)
    assert H.load_reader("prefill_prog_dev_ms.sat").read(run) == by_name
    assert 20 < by_name < 30        # 24.47 ms in the run it is cut from
    # the share the sampler's label gives is these launches' time
    share = H.load_reader("prefill_dev_share_pct").read(run)
    assert share == pytest.approx(
        100 * by_name * 6 / 1e3 / red["busy_s"], rel=1e-3)

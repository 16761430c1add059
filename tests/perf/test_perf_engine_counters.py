"""The readers of the engine's own counters and program names (PR 24):
each on a hand-made run, their BENCHMARK.json entries (appended by PR
29 from ``fixtures/engine-counter-entries.json``: see its ``origin``)
against the contract, and a rehearsal at ``nano`` in which they read a
real engine's counters. Numbers of a rehearsal measure no device.
"""
import json
import os

import pytest

import perf_testlib as L

import perf_harness as H

with open(os.path.join(L.FIXTURES, "engine-counter-entries.json")) as f:
    ENTRIES = json.load(f)["per_layer"]

MS = 10 ** 6
#: engine.stats() differenced over a window, as run["stats_delta"] has it
DELTA = {
    "admitted": 100, "prefills": 104, "dispatches": 50,
    "admission_wait_ns_sum": 40_000 * MS, "prefill_ns_sum": 5_200 * MS,
    "prefill_tokens_sum": 13_000, "decode_gap_ns_sum": 6_000 * MS,
    "driver_ns_idle": 2_000 * MS, "driver_ns_admit": 300 * MS,
    "driver_ns_prefill": 5_200 * MS, "driver_ns_cover": 50 * MS,
    "driver_ns_decode": 34_000 * MS, "driver_ns_deliver": 250 * MS,
    "driver_ns_other": 200 * MS, "driver_ns_total": 42_000 * MS,
    "compiles": 0, "compile_ns": 0,
}
CHUNK = "jit_decode_chunk_slots_paged(7)"
TRACE = {
    "programs": {CHUNK: {"launches": 5.0, "seconds": 3.0},
                 "jit_prefill_into_slot_paged(9)": {"launches": 3.0,
                                                    "seconds": 0.2}},
    "launches_by_host": {
        "engine.py:_dispatch_chunk": {"launches": 3, "seconds": 2.4,
                                      "programs": {CHUNK: {
                                          "launches": 3, "seconds": 2.4}}},
        "engine.py:_run": {"launches": 2, "seconds": 0.8, "programs": {
            CHUNK: {"launches": 1, "seconds": 0.8},
            "jit__threefry_seed(3)": {"launches": 1, "seconds": 1e-5}}}},
}
RUN = {"stats_delta": DELTA, "trace": TRACE,
       "conf": {"engine": {"chunk": 8}}}
WANT = {
    "admit_queue_mean_ms": 400.0,
    "prefill_host_mean_ms": 50.0,
    "decode_stall_pct": 15.0,
    # (300 + 50 + 250 + 200) / (42000 - 2000)
    "driver_host_pct": 2.0,
    "compiles_in_window": 0,
    # whole launches only: 3.2 s / (4 launches x 8 steps)
    "decode_prog_dev_ms": 100.0,
}


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_reader_on_a_hand_made_run(entry):
    reader = H.load_reader(entry["name"])
    plain = entry["name"].removesuffix(".sat")
    assert reader.read(RUN) == pytest.approx(WANT[plain])
    # a program without the counters (the parent of PR 24), an untraced
    # run, a window in which nothing was admitted: nothing, no raise
    assert reader.read({"stats_delta": {"admitted": 3, "prefills": 3},
                        "trace": None, "conf": RUN["conf"]}) is None
    assert reader.read({"stats_delta": {k: 0 for k in DELTA},
                        "trace": {"programs": {}},
                        "conf": RUN["conf"]}) in (None, 0)
    for key in ("LAYER", "UNIT", "SOURCE", "MOVES"):
        assert getattr(reader, key) == entry[
            {"LAYER": "layer", "UNIT": "unit", "SOURCE": "source",
             "MOVES": "moves"}[key]]


def test_clipped_programs_stand_in_where_no_whole_launches_are_kept():
    run = dict(RUN, trace={"programs": TRACE["programs"]})
    assert H.load_reader("decode_prog_dev_ms").read(run) == \
        pytest.approx(1e3 * 3.0 / (5 * 8))


def test_entries_keep_the_contract():
    """Each of the eleven entries is in BENCHMARK.json as it is kept
    here, in every key; its ``workloads`` list BEGINS with the list
    kept here, in that order: a PR that adds a cell may append it (and
    then the cell has to report the metric the entry moves), none is
    taken away or reordered (PR 36: it was equality, which any added
    serving cell tripped)."""
    import test_perf_benchmark_json as C

    bench = L.benchmark()
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len(ENTRIES) == 11
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert len(listed) == len(bench["per_layer"])     # each once
    for e in ENTRIES:
        got = listed[e["name"]]
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert dict(got, workloads=e["workloads"]) == e   # all other keys
        assert got["workloads"][:len(e["workloads"])] == e["workloads"]
        assert len(set(got["workloads"])) == len(got["workloads"])
        assert C.NAME.match(e["name"]) and C.UNIT.match(e["unit"])
        assert e["source"] in C.SOURCES and e["layer"] in layers
        moved = next(m for m in bench["end_to_end"]
                     if m["name"] == e["moves"])
        assert set(got["workloads"]) <= set(moved["workloads"])


def test_a_listed_metric_that_reads_nothing_fails_a_chip_run_only():
    """Why the entries waited for a parent that has the counters: on
    the chip (``strict``) run.py fails a traced run whose listed metric
    reads nothing, and the driver runs the parent with the change's
    benchmark files."""
    import run as perf_run

    found = {"cell": {"name": "c"}, "per_layer": [ENTRIES[0]]}
    res = {"run": {"stats_delta": {"admitted": 5}}}
    assert perf_run._metrics(found, res, 1, strict=False) == {}
    with pytest.raises(H.BenchError, match="admit_queue_mean_ms"):
        perf_run._metrics(found, res, 1, strict=True)
    res["run"]["stats_delta"] = DELTA
    assert perf_run._metrics(found, res, 1, strict=True) == {
        "admit_queue_mean_ms": {"value": 400.0, "unit": "ms"}}


def test_rehearsal_reads_a_real_engines_counters(tmp_path):
    """The entries, joined to a nano cell in a copy, read the counters
    of the engine that served the window."""
    root = L.copy_with_additions(
        tmp_path,
        configs=[("nano-serve", os.path.join(L.FIXTURES,
                                             "nano-serve.json"))],
        mixes=[("nano-chat", os.path.join(L.FIXTURES,
                                          "nano-chat.json"))],
        cells=[{"name": "nano-chat", "config": "nano-serve",
                "traffic": "nano-chat", "chips": 1, "why": "test"}],
        join={"nano-chat": "cgpt1b3-chat-steady"})
    rc, out, err = L.run_copy(
        root, "--workload", "nano-chat", "--seed", str(2 ** 31 + 24),
        "--seconds", "5", "--trace", "1", "--rehearsal")
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["compiles_in_window"] == 0
    assert got["admit_queue_mean_ms"] > 0
    assert got["prefill_host_mean_ms"] > 0
    assert 0 < got["decode_stall_pct"] < 100
    assert 0 < got["driver_host_pct"] < 100
    # no device plane in a CPU trace: nothing to read, left out
    assert "decode_prog_dev_ms" not in got


def test_recorded_trace_names_every_program():
    """A piece of a chip trace of PR 24 (see the file's ``origin``): the
    programs carry their factories' names, and the chunk program read by
    name is the one the sampler's labels pick."""
    import trace_reduce as R

    with open(os.path.join(L.PERF, "recorded", "trace_named.json")) as f:
        rec = json.load(f)
    red = R.reduce(rec, window=tuple(rec["window"]),
                   samples=[tuple(s) for s in rec["samples"]],
                   host_offset_ns=rec["host_offset_ns"])
    names = {k.split("(")[0] for k in red["programs"]}
    assert {"jit_decode_chunk_slots_paged",
            "jit_prefill_into_slot_paged"} <= names
    for name, p in red["programs"].items():
        if name.startswith("jit__unknown"):     # none that does work
            assert p["seconds"] / p["launches"] < 1e-3
    run = {"trace": red, "conf": {"engine": {"chunk": 8}}}
    by_name = H.load_reader("decode_prog_dev_ms").read(run)
    by_wait = H.load_reader("decode_step_dev_ms").read(run)
    assert by_name == pytest.approx(by_wait, rel=0.02)
    assert 50 < by_name < 150       # 88.7 ms in the run it is cut from

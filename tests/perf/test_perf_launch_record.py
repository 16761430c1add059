"""The benchmark's part of PR 57. What PRs 42, 43 and 54 counted is
LISTED at last: seven of the nine entries that waited in
``fixtures/launch-counter-entries.json`` (the two that read the thread
CPU clock wander by 20 points between runs of one program and stay
there) and two new readers over counters the parent has
(``deliver_overlap_pct.sat``, ``prompts_per_prefill_launch.sat``), nine
appended to BENCHMARK.json. The readers of PR 57's OWN counters
(stalled launches, collections, a kept slice's hold) are files with
their ``.sat`` twins, and their entries wait in
``fixtures/launch-record-entries.json`` (see its ``origin``) for the
same reason the nine did. Each reader on a
hand-made run, the entries against the contract, and a rehearsal at
``nano`` in which all of them read a real engine's counters. Numbers of
a rehearsal measure no device.
"""
import json
import os

import pytest

import perf_testlib as L

import perf_harness as H

with open(os.path.join(L.FIXTURES, "launch-record-entries.json")) as f:
    WAITING = json.load(f)["per_layer"]
with open(os.path.join(L.FIXTURES, "launch-counter-entries.json")) as f:
    NINE = json.load(f)["per_layer"]
CLOSED_LOOP = ["cgpt1b3-batch-offline", "axk1-ep16-reason-offline",
               "solar2-ep8-reason-offline", "lcflash-ep32-reason-offline",
               "fh1-34b-reason-offline", "g4hs-ep2-reason-offline"]
CHAT = ["cgpt1b3-chat-steady"]
#: listed by this PR: their counters are the parent's (PRs 43 and 54)
NEW_LISTED = ("deliver_overlap_pct.sat", "prompts_per_prefill_launch.sat")
#: of the nine, the two left in their fixture: both read the thread CPU
#: clock's 10 ms grain (PERF.md section 7)
LEFT = ("deliver_offcpu_pct.sat", "host_offcpu_pct.sat")
SEVEN = [e for e in NINE if e["name"] not in LEFT]

MS = 10 ** 6
#: engine.stats() differenced over a window of 50 s on the driver's
#: clock: 1,000 launches, two of them stalled (3.2 s of excess), 120 ms
#: of collections,
#: 20,000 messages handed over (19,000 behind a launch) after 0.9 ms
#: each, 300 prompts in 200 prefill launches
DELTA = {
    "dispatches": 1000, "driver_ns_total": 50_000 * MS,
    "launch_stalls": 2, "launch_stall_ns_sum": 3_200 * MS,
    "gc_pauses": 400, "gc_pause_ns_sum": 120 * MS,
    "gc2_pauses": 1, "gc2_pause_ns_sum": 40 * MS,
    "deliver_puts": 20_000, "deliver_puts_overlapped": 19_000,
    "deliver_hold_ns_sum": 18_000 * MS,
    "prefills": 300, "prefill_launches": 200,
}
RUN = {"stats_delta": DELTA}
WANT = {
    "deliver_overlap_pct.sat": 95.0,            # 19000 / 20000
    "prompts_per_prefill_launch.sat": 1.5,      # 300 / 200
    "launch_stall_s": 3.2, "launch_stall_s.sat": 3.2,
    # 120 ms over 50 s
    "gc_pause_ms_per_s": 2.4, "gc_pause_ms_per_s.sat": 2.4,
    # 18000 ms / 20000 messages
    "deliver_hold_mean_ms": 0.9, "deliver_hold_mean_ms.sat": 0.9,
}
#: stats_delta of the parent's program (7edb722): PR 43's and PR 54's
#: counters, none of PR 57's
PARENT = {k: DELTA[k] for k in (
    "dispatches", "driver_ns_total", "deliver_puts",
    "deliver_puts_overlapped", "prefills", "prefill_launches")}
ON_PARENT = {n: WANT[n] for n in NEW_LISTED}
#: the key each reader divides by (a window in which it is 0: nothing)
OVER = {"deliver_overlap_pct.sat": "deliver_puts",
        "prompts_per_prefill_launch.sat": "prefill_launches",
        "gc_pause_ms_per_s": "driver_ns_total",
        "gc_pause_ms_per_s.sat": "driver_ns_total",
        "deliver_hold_mean_ms": "deliver_puts",
        "deliver_hold_mean_ms.sat": "deliver_puts"}


def _listed():
    return {m["name"]: m for m in L.benchmark()["per_layer"]}


def _entry(name):
    by_name = {e["name"]: e for e in WAITING}
    by_name.update({n: _listed()[n] for n in NEW_LISTED})
    return by_name[name]


@pytest.mark.parametrize("name", list(WANT))
def test_reader_on_a_hand_made_run(name):
    reader = H.load_reader(name)
    assert reader.read(RUN) == pytest.approx(WANT[name])
    # the parent's program and a run without counters: nothing, never
    # 0, no raise
    assert reader.read({"stats_delta": PARENT}) == ON_PARENT.get(name)
    assert reader.read({}) is None
    if name in OVER:
        assert reader.read({"stats_delta": dict(DELTA, **{OVER[name]: 0})}) \
            is None
    else:
        # no launch stalled: the counter is there, and 0.0 is a reading
        quiet = dict(DELTA, launch_stalls=0, launch_stall_ns_sum=0)
        assert reader.read({"stats_delta": quiet}) == 0.0
    entry = _entry(name)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == \
        (entry["layer"], entry["unit"], entry["source"], entry["moves"])


def test_the_nine_are_listed_with_a_reader_each():
    """BENCHMARK.json ends with seven of the nine that waited, each as
    it was kept but for the cells PRs 47, 52 and 55 added behind the
    kept list, and the two new ones; the two whose readings did not
    repeat are not in it."""
    bench = L.benchmark()
    tail = bench["per_layer"][-9:]
    assert [m["name"] for m in tail] == \
        [e["name"] for e in SEVEN] + list(NEW_LISTED)
    for m, kept in zip(tail, SEVEN):
        assert m == dict(kept, workloads=CLOSED_LOOP)
        assert m["workloads"][:len(kept["workloads"])] == kept["workloads"]
    for m in tail[7:]:
        assert m["workloads"] == CLOSED_LOOP and m["better"] == "higher"
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    assert not [n for n in LEFT if n in names]
    for m in tail:
        assert os.path.exists(os.path.join(
            L.PERF, "layer_metrics", m["name"] + ".py"))


def test_entries_keep_the_contract():
    """The six that wait are complete entries: the plain ones for the
    open loop, the twins for the six closed-loop cells. None of them
    is in BENCHMARK.json; once a later PR has appended the six, each is
    there as it is kept here, its list BEGINNING with the list kept
    here."""
    import test_perf_benchmark_json as C

    bench = L.benchmark()
    layers = {m["layer"] for m in bench["per_layer"]}
    listed = _listed()
    assert [e["name"] for e in WAITING] == [
        n for n in WANT if n not in NEW_LISTED]
    assert not [e["name"] for e in WAITING if e["name"] in listed]
    for e in WAITING + [listed[n] for n in NEW_LISTED]:
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert C.NAME.match(e["name"]) and C.UNIT.match(e["unit"])
        assert e["source"] in C.SOURCES and e["layer"] in layers
        first = CLOSED_LOOP if e["name"].endswith(".sat") else CHAT
        assert e["workloads"][:len(first)] == first
        moved = next(m for m in bench["end_to_end"]
                     if m["name"] == e["moves"])
        assert set(e["workloads"]) <= set(moved["workloads"])
        assert os.path.exists(os.path.join(
            L.PERF, "layer_metrics", e["name"] + ".py"))
        got = listed.get(e["name"], e)
        assert dict(got, workloads=e["workloads"]) == e
        assert got["workloads"][:len(e["workloads"])] == e["workloads"]
    for e in WAITING:
        assert e["better"] == "lower"


def test_why_the_six_wait_and_the_two_do_not():
    """On the chip (``strict``) run.py fails a traced run whose listed
    metric reads nothing, and the driver runs the parent with the
    change's benchmark files: listed, each of the six would fail every
    traced run of the parent, which lacks the counters; the two new
    ones read what the parent counts."""
    import run as perf_run

    for entry in WAITING + [_listed()[n] for n in NEW_LISTED]:
        found = {"cell": {"name": "c"}, "per_layer": [entry]}
        res = {"run": {"stats_delta": PARENT}}
        if entry["name"] in ON_PARENT:
            assert perf_run._metrics(found, res, 1, strict=True)[
                entry["name"]]["value"] == pytest.approx(
                    WANT[entry["name"]])
        else:
            assert perf_run._metrics(found, res, 1, strict=False) == {}
            with pytest.raises(H.BenchError, match=entry["name"]):
                perf_run._metrics(found, res, 1, strict=True)
        res["run"]["stats_delta"] = DELTA
        assert perf_run._metrics(found, res, 1, strict=True) == {
            entry["name"]: {"value": pytest.approx(WANT[entry["name"]]),
                            "unit": entry["unit"]}}


def test_rehearsal_reads_a_real_engines_counters(tmp_path):
    """A nano closed-loop cell in a copy, joined to batch-offline (so
    that it reports the nine now listed) and given the twins that
    wait: every one reads the counters of the engine that served the
    window."""
    cell = L.cell("nano-batch", "nano-serve", "nano-batch")
    twins = [e for e in WAITING if e["name"].endswith(".sat")]
    root = L.copy_with_additions(
        tmp_path,
        configs=[("nano-serve", L.fixture("nano-serve.json"))],
        mixes=[("nano-batch", L.fixture("nano-batch.json"))],
        cells=[cell],
        metrics=[("per_layer", dict(e, workloads=["nano-batch"]))
                 for e in twins],
        join={"nano-batch": "cgpt1b3-batch-offline"})
    rc, out, err = L.run_copy(
        root, "--workload", "nano-batch", "--seed", str(2 ** 31 + 57),
        "--seconds", "5", "--trace", "1", "--rehearsal")
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    listed = [e["name"] for e in SEVEN] + list(NEW_LISTED)
    assert not [n for n in listed + [e["name"] for e in twins]
                if n not in got]
    assert 0 < got["deliver_overlap_pct.sat"] <= 100
    assert 1 <= got["prompts_per_prefill_launch.sat"] <= 2
    assert got["launch_stall_s.sat"] >= 0
    assert got["gc_pause_ms_per_s.sat"] >= 0
    # a slice is held for the gap and the enqueue at least
    assert got["deliver_hold_mean_ms.sat"] > 0
    # the plain files read the same run's counters
    with open(os.path.join(root, "chiprun_out", "perf",
                           f"nano-batch-s{2 ** 31 + 57}-t1",
                           "run.json")) as f:
        run = json.load(f)
    for e in WAITING:
        if not e["name"].endswith(".sat"):
            assert H.load_reader(e["name"]).read(run) == \
                pytest.approx(got[e["name"] + ".sat"])
    d = run["stats_delta"]
    assert d["launch_stalls"] >= 0 and d["deliver_hold_ns_sum"] > 0
    assert d["gc_pause_ns_sum"] >= d["gc2_pause_ns_sum"] >= 0

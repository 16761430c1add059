"""A later PR adds a configuration, a mix, a per-layer metric and a
cell as new files and new entries and edits nothing that is there: a
temporary copy gets a dummy of each and runs, at ``nano``, on the CPU.

These are REHEARSALS: they prove the command's control flow and its one
result line. No number they print is a measurement of any device.
"""
import json
import os

import pytest

import perf_testlib as L

READER = '''"""A dummy per-layer metric: requests the window attempted."""
LAYER = "load generator"
UNIT = "1"
SOURCE = "host_clock"
MOVES = "tpot_p90_ms"


def read(run):
    return float(run["e2e"]["attempted"])
'''


def _cell(name, config, traffic, chips=1):
    return {"name": name, "config": config, "traffic": traffic,
            "chips": chips, "why": "test"}


@pytest.fixture(scope="module")
def serve_copy(tmp_path_factory):
    return L.copy_with_additions(
        tmp_path_factory.mktemp("perf_serve"),
        configs=[("nano-serve", os.path.join(L.FIXTURES,
                                             "nano-serve.json"))],
        mixes=[("nano-chat", os.path.join(L.FIXTURES,
                                          "nano-chat.json"))],
        readers=[("dummy_attempted", READER)],
        cells=[_cell("nano-chat", "nano-serve", "nano-chat")],
        metrics=[("per_layer", {
            "name": "dummy_attempted", "unit": "1", "better": "higher",
            "source": "host_clock", "layer": "load generator",
            "moves": "tpot_p90_ms", "workloads": ["nano-chat"]})],
        join={"nano-chat": "cgpt1b3-chat-steady"})


def test_nothing_that_was_there_is_edited(serve_copy):
    import filecmp

    cmp = filecmp.dircmp(L.PERF, os.path.join(serve_copy, "benchmarks",
                                              "perf"),
                         ignore=["__pycache__"])

    def walk(c):
        assert not c.diff_files and not c.left_only, \
            (c.diff_files, c.left_only)
        for sub in c.subdirs.values():
            walk(sub)

    walk(cmp)
    with open(os.path.join(serve_copy, "BENCHMARK.json")) as f:
        new = json.load(f)
    old = L.benchmark()
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[key], new[key]):
            # an entry stays as it was; a metric's list of cells may
            # only grow at its end
            cells = was.get("workloads", [])
            assert now.get("workloads", [])[:len(cells)] == cells
            assert {k: v for k, v in now.items() if k != "workloads"} \
                == {k: v for k, v in was.items() if k != "workloads"}


def test_whole_run_of_the_added_chat_cell_ends_in_one_result_line(
        serve_copy):
    rc, out, err = L.run_copy(
        serve_copy, "--workload", "nano-chat", "--seed",
        str(2 ** 31 + 7), "--seconds", "5", "--trace", "1",
        "--rehearsal")
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} \
        <= set(res)
    assert res["rehearsal"] is True
    assert res["device"]["platform"] == "cpu"      # and says so
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 15                   # 3/s for 5 s
    assert res["metrics"]["dummy_attempted"]["value"] == 15.0
    # no device plane in a CPU trace: the device metrics are left out
    assert "busy_s" not in res["device"]


def test_without_a_tpu_the_command_fails_and_prints_no_result(
        serve_copy):
    rc, out, err = L.run_copy(
        serve_copy, "--workload", "nano-chat", "--seed", "1",
        "--seconds", "2", "--trace", "0", timeout=300)
    assert rc != 0
    assert not any(ln.startswith("{") and '"metrics"' in ln
                   for ln in out)


def test_unknown_workload_fails_without_a_result(serve_copy):
    rc, out, _err = L.run_copy(
        serve_copy, "--workload", "no-such-cell", "--seed", "1",
        "--seconds", "2", "--trace", "0", "--rehearsal")
    assert rc != 0 and not out


def test_added_training_cell_runs_on_four_virtual_devices(
        tmp_path_factory):
    root = L.copy_with_additions(
        tmp_path_factory.mktemp("perf_train"),
        configs=[("nano-fsdp4", os.path.join(L.FIXTURES,
                                             "nano-fsdp4.json"))],
        cells=[_cell("nano-train", "nano-fsdp4", "train-steps", 4)],
        join={"nano-train": "cgpt1b3-train-fsdp4"})
    rc, out, err = L.run_copy(
        root, "--workload", "nano-train", "--seed", "5", "--seconds",
        "2", "--trace", "0", "--rehearsal", devices=4)
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert res["device"]["count"] == 4 and res["correct"] is True
    assert res["attempted"] > 3 and res["rehearsal"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}

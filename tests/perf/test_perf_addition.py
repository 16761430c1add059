"""A later PR adds a configuration, a mix, a per-layer metric, a cell
and a MODEL as new files and new entries and edits nothing that is
there: a temporary copy gets a dummy of each and runs, at ``nano``, on
the CPU.

These are REHEARSALS: they prove the command's control flow and its one
result line. No number they print is a measurement of any device.
"""
import json
import os

import pytest

import perf_testlib as L

_cell, _fixture, DUMMY_ARCH = L.cell, L.fixture, L.DUMMY_ARCH


@pytest.fixture(scope="module")
def serve_copy(tmp_path_factory):
    return L.rehearsal_copy(tmp_path_factory.mktemp("perf_serve"))


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
    assert "attn_kernel_share_pct" not in res["metrics"]
    # what tracing cost is part of the run
    setup = json.loads(next(ln for ln in out
                            if ln.startswith("SETUP "))[6:])
    cost = setup["trace_cost"]
    assert set(cost) == {"trace_stop_s", "xplane_bytes", "device_events",
                         "reduce_s", "slice_s", "launches", "ended_by"}
    assert cost["trace_stop_s"] > 0 and cost["xplane_bytes"] > 0
    assert cost["device_events"] == 0          # a CPU has no device plane
    # nano-chat states no trace_launches: its slice is its trace_s, as
    # before PR 31, and the launches it held are counted all the same
    assert cost["ended_by"] == "seconds" and cost["launches"] > 0
    assert 0.8 <= cost["slice_s"] < 1.5
    # each number compared stands beside its limit at the end of stderr
    tail = [ln for ln in err.strip().splitlines()
            if ln.startswith("perfbench ")]
    assert tail[-1] == "perfbench correct: True"
    assert sum("logits after_" in ln and "<= tol 0.025" in ln
               and "compared 2, left out 0" in ln for ln in tail) == 2
    assert any("served tokens: max gap" in ln and "control max gap" in ln
               for ln in tail)


def test_a_model_added_as_files_serves_under_its_own_reference(
        serve_copy):
    """``architectures/dummy.py``, ``dummy_reference.py`` and a
    configuration that names them (its ``model`` block under key names
    of its own) were ADDED to the copy; the cell runs and is judged by
    the dummy's reference."""
    import perf_harness as H

    rc, out, err = L.run_copy(
        serve_copy, "--workload", "dummy-chat", "--seed",
        str(2 ** 31 + 9), "--seconds", "3", "--trace", "0",
        "--rehearsal")
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"tpot_mean_ms", "setup_s"}
    setup = json.loads(next(ln for ln in out
                            if ln.startswith("SETUP "))[6:])
    served = setup["served_check"]["reference"]
    assert served["ok"] and served["distinct"] >= 1
    assert served["control_max_gap"] > served["control_margin"]
    assert all(c["rel"] <= c["tol"] for c in setup["reference"])
    # the configuration's correct.rows (3) seeded rows, each compared
    # after prefill and after decode; no decidable: nothing left out
    assert [(c["compared"], c["left_out"])
            for c in setup["reference"]] == [(3, 0), (3, 0)]
    assert setup["reference_vectors"] == {"compared": 6, "left_out": 0,
                                          "needed": 6}
    assert (served["tokens"], served["compared"], served["left_out"]) \
        == (6, 6, 0)
    # found by name, in the copy, with the reference beside it
    conf = H.load_json(_fixture("dummy-serve.json"))
    arch = H.load_architecture(conf, here=os.path.join(
        serve_copy, "benchmarks", "perf"))
    assert arch.vocab(conf) == (500, 512)
    _, forward, loss = arch.reference(arch.model_cfg(conf))
    assert forward.func.__module__ == loss.func.__module__ \
        == "perf_arch_dummy_reference"


def test_a_configuration_that_names_no_architecture_file_fails(
        serve_copy):
    import perf_harness as H

    assert H.load_architecture({}).__name__ == "perf_arch_gpt2"
    with pytest.raises(H.BenchError, match="no architecture file"):
        H.load_architecture({"name": "x", "architecture": "absent"})


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


@pytest.mark.parametrize("config", ["nano-fsdp4", "dummy-fsdp4"])
def test_added_training_cell_runs_on_four_virtual_devices(
        tmp_path_factory, config):
    """``dummy-fsdp4``: the training cell of the model added as files."""
    root = L.copy_with_additions(
        tmp_path_factory.mktemp("perf_train"),
        configs=[(config, _fixture(config + ".json"))],
        architectures=DUMMY_ARCH,
        cells=[_cell("nano-train", config, "train-steps", 4)],
        join={"nano-train": "cgpt1b3-train-fsdp4"})
    rc, out, err = L.run_copy(
        root, "--workload", "nano-train", "--seed", "5", "--seconds",
        "2", "--trace", "0", "--rehearsal", devices=4)
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert res["device"]["count"] == 4 and res["correct"] is True
    assert res["attempted"] > 3 and res["rehearsal"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


#: read from the parent of PR 27 (``perf_deployment.model_cfg`` and
#: ``seeded_params``, seed 2**31 + 11) before they moved behind
#: ``architectures/gpt2.py``
PARENT_CFG = dict(vocab_size=512, n_layer=2, n_head=2, d_model=64,
                  d_ff=256, max_seq=128, dtype="bfloat16",
                  param_dtype="float32", remat="dots")
PARENT_WEIGHTS = \
    "e2aee54930b223b9e716ed2b4a72e7fa1ee5414af820f719b945195e03f43542"


@pytest.mark.parametrize("fixture,loss_chunk", [("nano-serve", 0),
                                                ("nano-fsdp4", 32)])
def test_gpt2_config_and_seeded_weights_did_not_move(fixture,
                                                     loss_chunk):
    import dataclasses
    import hashlib

    import jax
    import numpy as np

    import perf_deployment
    import perf_harness as H

    conf = H.load_json(_fixture(fixture + ".json"))
    arch = H.load_architecture(conf)
    cfg = arch.model_cfg(conf)
    got = dataclasses.asdict(cfg)
    for key in ("dtype", "param_dtype"):
        got[key] = np.dtype(got[key]).name
    assert {k: got[k] for k in PARENT_CFG} == PARENT_CFG
    assert got["loss_chunk"] == loss_chunk and got["n_experts"] == 0
    params = perf_deployment.seeded_params(arch, cfg, 2 ** 31 + 11,
                                           conf["init"])
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), a.dtype, a.shape):
            h.update(str(part).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT_WEIGHTS

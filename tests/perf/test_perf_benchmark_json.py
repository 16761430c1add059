"""BENCHMARK.json against the contract's letter, and against the files
it names: of the tree, and of the rehearsal's copy, which holds what a
``model_config`` PR brings (``perf_testlib.rehearsal_copy``: a second
architecture with a CUT configuration). A test here that cannot hold a
second architecture fails in this suite, not in the next model's PR."""
import json
import os
import re
import shutil

import pytest

import perf_testlib

import perf_harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


@pytest.fixture(scope="module", params=["tree", "rehearsal"])
def root(request, tmp_path_factory):
    return perf_testlib.root_of(request.param, tmp_path_factory)


@pytest.fixture(scope="module")
def bench(root):
    return perf_testlib.benchmark(root)


@pytest.fixture(scope="module")
def here(root):
    return perf_testlib.perf_dir(root)


def test_top_level_keys_and_sizes(bench, root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(
        root, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # the full 24 cells must fit the check's budget at this length
    assert 2 * 60 + 14 * 24 * (bench["run_seconds"] + 60) \
        + 24 * 2 * 90 + 1200 <= 43200
    assert bench["paths"] == ["benchmarks/perf", "tests/perf"]
    assert bench["command"][-1].startswith("benchmarks/perf/")


def test_every_name_and_unit_is_within_the_allowed_characters(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200, (w["name"], len(w["why"]))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_finds_its_files_and_its_metrics(bench, root, here):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        found = H.find_cell(bench, w["name"])
        conf = H.load_config(found["config"], root)
        mix = H.load_mix(w["traffic"], here)
        arch = H.load_architecture(conf, here)
        assert callable(arch.vocab) and callable(arch.reference)
        assert conf["name"] == w["config"]
        assert mix["loop"] in ("open", "closed", "steps")
        mine = {m["name"] for m in found["end_to_end"]}
        assert "setup_s" in mine and len(mine) >= 2
        assert found["per_layer"]
        for m in found["per_layer"]:
            # a layer metric moves an end-to-end metric its cell reports
            assert m["moves"] in mine, (w["name"], m["name"])
    configs_used = {w["config"] for w in bench["workloads"]}
    assert configs_used == {c["name"] for c in bench["configs"]}


def test_every_per_layer_metric_has_its_reader_and_they_agree(bench,
                                                               here):
    for m in bench["per_layer"]:
        mod = H.load_reader(m["name"], here)
        assert callable(mod.read)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
            (m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
    # a reader that finds nothing to read returns nothing
    empty = {"e2e": {}, "rows": [], "t0": 0.0, "t1": 1.0,
             "stats_before": {"dispatches": 0, "avg_occupancy": 0.0,
                              "prefix_tokens_reused": 0},
             "stats_after": {"dispatches": 0, "avg_occupancy": 0.0,
                             "prefix_tokens_reused": 0},
             "stats_delta": {}, "conf": {"engine": {"chunk": 8}}}
    for m in bench["per_layer"]:
        assert H.load_reader(m["name"], here).read(empty) is None, \
            m["name"]


def test_configuration_files_state_source_sizes_and_cuts(bench, root,
                                                         here):
    """Of EVERY configuration what the ``model-configs`` guide asks
    (``perf_testlib.check_configuration``; the key names are the
    contract's, in ``architectures/gpt2.py``): ``reduced`` and the
    source as the entry has them, what was assumed, and for a cut one
    the published value beside the value held and the deployment it
    stands for. The two that are Cerebras-GPT-1.3B keep its sizes."""
    for c in bench["configs"]:
        conf = H.load_config(c, root)
        perf_testlib.check_configuration(
            c, conf, H.load_architecture(conf, here))
    cerebras = [c for c in bench["configs"]
                if c["source"] == perf_testlib.CEREBRAS_1B3]
    assert len(cerebras) == 2
    if root != perf_testlib.ROOT:
        # the rehearsal's cut configuration, found BY NAME: the tree may
        # hold cut configurations of its own, each held to its own
        # statement above
        dummy = next(c for c in bench["configs"]
                     if c["name"] == "dummy-serve")
        assert dummy["reduced"] == ["layers", "vocab"]
        conf = H.load_config(dummy, root)
        assert conf["cut"]["layers"] == {"published": 4, "held": 2}
        assert conf["cut_stands_for"]["chips_sharing_a_layer"] == 2


def _copy_of_one_config(tmp_path, name):
    """(entry, configuration) of one of the tree's configurations, the
    file copied so that a test may plant a fault in the copy."""
    entry = next(c for c in perf_testlib.benchmark()["configs"]
                 if c["name"] == name)
    path = os.path.join(str(tmp_path), name + ".json")
    shutil.copy(os.path.join(perf_testlib.ROOT, entry["file"]), path)
    with open(path) as f:
        return dict(entry), json.load(f)


@pytest.mark.parametrize("fault", [
    "cerebras_sizes_altered", "reduced_disagrees_with_the_entry",
    "cut_without_its_published_value", "cut_without_its_deployment",
    "held_value_is_not_the_files", "nothing_assumed"])
def test_a_planted_fault_in_a_configuration_file_is_refused(tmp_path,
                                                            fault):
    cut = {"reduced": ["n_layer"],
           "cut": {"n_layer": {"published": 48, "held": 24}},
           "cut_stands_for": {"chips_sharing_a_layer": 1,
                              "how": "half the depth on this chip"}}
    entry, conf = _copy_of_one_config(tmp_path,
                                      "cerebras-gpt-1.3b-serve")
    perf_testlib.check_configuration(entry, conf)        # sound as it is
    if fault == "cerebras_sizes_altered":
        conf["model"]["n_inner"] = 4096
    elif fault == "reduced_disagrees_with_the_entry":
        conf.update(cut)
    else:
        # a cut configuration of another source, sound until the fault
        conf["source"]["url"] = entry["source"] = "https://example.org/c"
        conf.update(cut)
        entry["reduced"] = ["n_layer"]
        perf_testlib.check_configuration(entry, conf)
        if fault == "cut_without_its_published_value":
            del conf["cut"]["n_layer"]["published"]
        elif fault == "cut_without_its_deployment":
            del conf["cut_stands_for"]
        elif fault == "held_value_is_not_the_files":
            conf["cut"]["n_layer"]["held"] = 12
        else:
            conf["assumed"] = []
    with pytest.raises((AssertionError, KeyError)):
        perf_testlib.check_configuration(entry, conf)


def test_peaks_table_refuses_an_unknown_device(here):
    assert H.peaks("TPU v5 lite", here)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(H.BenchError):
        H.peaks("TPU v9 imaginary", here)

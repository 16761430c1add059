"""BENCHMARK.json against the contract's letter, and against the files
it names."""
import os
import re

import pytest

import perf_testlib

import perf_harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return perf_testlib.benchmark()


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(
        perf_testlib.ROOT, "BENCHMARK.json")) <= 64 * 1024
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


def test_every_cell_finds_its_files_and_its_metrics(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        found = H.find_cell(bench, w["name"])
        conf = H.load_config(found["config"])
        mix = H.load_mix(w["traffic"])
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


def test_every_per_layer_metric_has_its_reader_and_they_agree(bench):
    for m in bench["per_layer"]:
        mod = H.load_reader(m["name"])
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
        assert H.load_reader(m["name"]).read(empty) is None, m["name"]


def test_configuration_files_state_source_sizes_and_cuts(bench):
    for c in bench["configs"]:
        conf = H.load_config(c)
        assert conf["reduced"] == c["reduced"] == []
        assert conf["source"]["url"] == c["source"]
        m = conf["model"]
        assert (m["n_layer"], m["n_embd"], m["n_head"], m["n_inner"],
                m["n_positions"], m["vocab_size"]) == \
            (24, 2048, 16, 8192, 2048, 50257)
        assert conf["assumed"]


def test_peaks_table_refuses_an_unknown_device():
    assert H.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(H.BenchError):
        H.peaks("TPU v9 imaginary")

"""The tree takes an added architecture without an edit (PR 36).

``perf_testlib.planted_tree`` copies BENCHMARK.json, ``benchmarks/perf``
AND ``tests/perf`` and plants in place what a ``model_config`` PR
brings: a second architecture with its reference, a CUT configuration,
a mix, a reader with its entry, and a serving cell appended to every
list of metrics that ``cgpt1b3-batch-offline`` reports. The COPY'S OWN
structural tests then run on the copy, in a process of their own
(``perf_testlib.ROOT`` follows the file's place): a test that pins a
list of what the tree holds fails HERE, in this suite, and not in the
PR that adds a model and may edit no test (four did until PR 36, which
``rehearsal_copy`` never met: it plants beside a copy, so no
``root == ROOT`` branch saw a tree that had grown). Planted faults must
make the copy's tests fail, so the rules that replaced the lists still
say something.

The run of the planted cell is a REHEARSAL on the CPU: it proves
control flow and the result line, and measures no device.
"""
import json
import os
import re

import pytest

import perf_testlib as L

import perf_harness as H

#: test_perf_benchmark_json.py: 6 tests on two roots + 6 planted faults;
#: who knows the model on two roots + 4 planted files; the entries
STRUCTURAL_PASSES = 6 * 2 + 6 + 2 + 4 + 1


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return L.planted_tree(tmp_path_factory.mktemp("perf_planted"))


def _failed(out):
    return set(re.findall(r"^FAILED \S*::(\w+)", out, re.M))


def test_the_copys_own_structural_tests_pass_on_the_planted_tree(tree):
    rc, out = L.run_planted_tests(tree)
    assert rc == 0, out[-4000:]
    assert re.search(rf"\b{STRUCTURAL_PASSES} passed", out), out[-500:]
    assert " failed" not in out and " error" not in out


def test_nothing_that_was_there_is_edited_and_every_list_is_joined(tree):
    """The planting is a PR's diff: files added, entries appended, each
    list of cells grown at its end only; and the planted cell is in
    every list the cell it is like is in, the share of the whole step
    and the counters' ``.sat`` twins among them."""
    import filecmp

    import test_perf_addition as A

    A.test_nothing_that_was_there_is_edited(tree)
    tests = filecmp.dircmp(os.path.dirname(L.FIXTURES),
                           os.path.join(tree, "tests", "perf"),
                           ignore=["__pycache__"])
    assert not (tests.diff_files or tests.left_only or tests.right_only)
    bench = L.benchmark(tree)
    joined = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if L.PLANTED_LIKE in m.get("workloads", [])]
    assert {"out_tokens_per_s", "decode_roofline_pct.sat",
            "decode_stall_pct.sat", "prefill_host_mean_ms.sat",
            "driver_host_pct.sat", "compiles_in_window.sat",
            "decode_prog_dev_ms.sat"} <= set(joined)
    found = H.find_cell(bench, L.PLANTED_CELL)
    assert [m["name"] for m in found["end_to_end"] + found["per_layer"]
            if "workloads" in m] == joined + [L.PLANTED_READER]
    conf = H.load_config(found["config"], tree)
    assert conf["architecture"] == L.PLANTED and conf["reduced"]
    arch = H.load_architecture(conf, L.perf_dir(tree))
    assert callable(arch.decidable) and conf["correct"]["tie_eps"] > 0
    assert L.architectures_and_references(L.perf_dir(tree)) == \
        sorted(L.architectures_and_references(L.PERF) + [L.PLANTED])


#: the test of the copy that has to refuse each planted fault
REFUSED_BY = {
    "the_cell_in_a_list_whose_moves_it_does_not_report":
        "test_entries_keep_the_contract",
    "a_cell_taken_out_of_a_counter_entrys_list":
        "test_entries_keep_the_contract",
    "a_cell_put_before_the_cells_that_were_there":
        "test_entries_keep_the_contract",
    "a_reference_without_its_module":
        "test_only_the_architecture_file_knows_the_model",
    "an_architecture_without_its_reference":
        "test_only_the_architecture_file_knows_the_model",
    "the_planted_architecture_names_reference_gpt2":
        "test_only_the_architecture_file_knows_the_model"}


@pytest.mark.parametrize("fault", L.PLANTED_FAULTS)
def test_a_fault_planted_with_the_addition_fails_the_copys_tests(
        tmp_path, fault):
    """What an addition may NOT do: join a list whose ``moves`` its
    cell does not report, take a cell out of a list or put its own
    before the cells that were there, bring a reference without its
    module or a module without its reference, or lean on gpt2's
    reference."""
    rc, out = L.run_planted_tests(L.planted_tree(tmp_path, fault))
    assert rc == 1, out[-4000:]
    assert REFUSED_BY[fault] in _failed(out), out[-4000:]
    if fault == "the_cell_in_a_list_whose_moves_it_does_not_report":
        # the contract's own rule refuses it too, on both roots
        assert "test_every_cell_finds_its_files_and_its_metrics" \
            in _failed(out)


def test_the_step_share_takes_its_bytes_from_the_planted_architecture(
        tree):
    """``decode_roofline_pct`` on a hand-made traced run of the planted
    configuration: the numerator is the planted module's
    ``decode_step_bytes``, under key names ``kernel_costs`` does not
    know (``width``, ``ffn``, ``layers``)."""
    from program_names import CHUNK_WAIT

    perf = L.perf_dir(tree)
    conf = H.load_config(H.find_cell(L.benchmark(tree),
                                     L.PLANTED_CELL)["config"], tree)
    assert "n_embd" not in conf["model"]
    launches, step_ms, live = 10, 0.02, 100
    seconds = step_ms * 1e-3 * conf["engine"]["chunk"] * launches
    run = {"conf": conf, "trace_mid": 10.0,
           "peaks": H.peaks("TPU v5 lite"), "stats_delta": {},
           "trace": {"launches_by_host": {CHUNK_WAIT: {
               "launches": launches, "seconds": seconds,
               "programs": {"jit_decode_chunk_slots_paged": {
                   "launches": launches, "seconds": seconds}}}}},
           "rows": [{"prompt_len": live - 8, "end": None,
                     "slices": [[9.0, 8], [11.0, 8]]}]}
    m = conf["model"]
    weights = m["rows"] * m["width"] + m["layers"] * (
        4 * m["width"] ** 2 + 2 * m["width"] * m["ffn"]
        + 2 * m["width"]) + m["width"]
    need = 2 * weights + 2 * m["layers"] * m["width"] * 2 * live
    got = H.load_reader("decode_roofline_pct", perf).read(run)
    assert got == pytest.approx(100 * need / 819e9 / (step_ms / 1e3))
    assert 0 < got < 100


def test_the_planted_cell_runs_through_the_copys_run_py(tree):
    """As ``test_perf_addition`` rehearses ``dummy-chat``: one traced
    rehearsal of the planted closed-loop cell in the planted tree.
    Every listed reader reads, or is left out as a rehearsal leaves it
    (a CPU trace has no device plane)."""
    rc, out, err = L.run_copy(
        tree, "--workload", L.PLANTED_CELL, "--seed", str(2 ** 31 + 36),
        "--seconds", "4", "--trace", "1", "--rehearsal")
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert res["rehearsal"] is True and res["device"]["platform"] == "cpu"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 8            # more than the callers' first
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got[L.PLANTED_READER] == float(res["attempted"])
    assert got["compiles_in_window.sat"] == 0
    assert got["prefill_host_mean_ms.sat"] > 0
    assert 0 < got["decode_stall_pct.sat"] < 100
    assert 0 < got["driver_host_pct.sat"] < 100
    assert 0 < got["slot_occupancy_pct.sat"] <= 100
    assert got["dispatches_per_token.sat"] > 0
    listed = {m["name"] for m in H.find_cell(
        L.benchmark(tree), L.PLANTED_CELL)["per_layer"]}
    assert set(got) <= listed
    # what reads the device plane is left out, the step's share too
    assert listed - set(got) >= {
        "decode_roofline_pct.sat", "decode_step_dev_ms.sat",
        "decode_prog_dev_ms.sat", "device_idle_pct.sat",
        "attn_kernel_share_pct.sat"}
    # judged by the planted reference; its decidable was asked and left
    # nothing out (the configuration's correct.rows is 3)
    setup = json.loads(next(ln for ln in out
                            if ln.startswith("SETUP "))[6:])
    assert setup["reference_vectors"] == {"compared": 6, "left_out": 0,
                                          "needed": 6}
    assert all(c["rel"] <= c["tol"] for c in setup["reference"])
    served = setup["served_check"]["reference"]
    assert served["ok"] and served["left_out"] == 0
    assert served["control_max_gap"] > served["control_margin"]

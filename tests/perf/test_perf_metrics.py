"""The metric arithmetic on a synthetic stamp log."""
import pytest

import perf_testlib  # noqa: F401

import perf_harness as H
import perf_metrics as M


def _row(idx, due, first=None, n=0, gap=0.1, end=True, error=None,
         phase="window", max_new=None):
    slices = []
    if first is not None:
        slices.append([due + first, 1])
        t = due + first
        left = n - 1
        while left > 0:
            t += gap * min(8, left)
            slices.append([t, min(8, left)])
            left -= min(8, left)
    return {"idx": idx, "phase": phase, "due": due, "sent": due + 0.001,
            "slices": slices, "end": (slices[-1][0] if end and slices
                                      else None),
            "error": error, "error_t": (due + 1.0) if error else None,
            "prompt_len": 10, "max_new": max_new or n, "id_min": 0,
            "id_max": 5}


def test_quantile_interpolates():
    assert H.quantile([1, 2, 3, 4, 5], 0.5) == 3
    assert H.quantile([0, 10], 0.9) == pytest.approx(9.0)
    assert H.quantile([], 0.5) is None


def test_open_loop_counts_a_failed_and_a_still_running_request_as_worst():
    t0, t1, t_close = 100.0, 140.0, 150.0
    rows = [_row(i, 100.0 + i, first=0.5, n=17, gap=0.1)
            for i in range(8)]
    rows.append(_row(8, 110.0, error="Boom: refused"))
    rows.append(_row(9, 139.0, first=0.4, n=9, end=False))  # running
    rows.append(_row(10, 95.0, first=0.2, n=9, phase="ramp"))
    got = M.open_loop(rows, t0, t1, t_close)
    assert got["attempted"] == 10 and got["failed"] == 2
    # 10 values: eight at 0.5 s and two at the worst (50 s)
    assert got["ttft_p50_ms"] == pytest.approx(500.0)
    assert got["ttft_p90_ms"] > 40_000
    assert got["tpot_p50_ms"] == pytest.approx(100.0)
    # the mean over requests takes the two lost ones at the worst too
    assert got["tpot_mean_ms"] == pytest.approx((8 * 0.1 + 2 * 50.0) * 100)
    assert got["gen_late_p99_ms"] == pytest.approx(1.0)
    none_failed = M.open_loop(rows[:8], t0, t1, t_close)
    assert none_failed["failed"] == 0
    assert none_failed["ttft_p90_ms"] == pytest.approx(500.0)
    assert none_failed["tpot_p90_ms"] == pytest.approx(100.0)
    assert none_failed["tpot_mean_ms"] == pytest.approx(100.0)


def test_tpot_is_last_minus_first_over_tokens_less_one():
    row = _row(0, 0.0, first=1.0, n=17, gap=0.25)
    assert M.n_tokens(row) == 17
    assert M.tpot_s(row) == pytest.approx(0.25)
    assert M.ttft_s(row) == pytest.approx(1.0)
    assert M.tpot_s(_row(1, 0.0, first=1.0, n=1)) is None


def test_closed_loop_rate_counts_the_tokens_made_in_the_window():
    t0, t1 = 10.0, 20.0
    before = _row(0, 5.0, first=1.0, n=17, gap=0.5)   # straddles t0
    inside = _row(1, 11.0, first=1.0, n=9, gap=0.1)
    running = _row(2, 18.0, first=1.0, n=25, gap=0.2, end=False)
    failed = _row(3, 12.0, error="X: y")
    rows = [before, inside, running, failed]
    got = M.closed_loop(rows, t0, t1)
    # `before`: a token at 6.0, then eight a slice over (6, 10] and
    # (10, 14]; `inside` lies whole in the window; `running`: a token
    # at 19.0 and eight over (19.0, 20.6], of which 1.0 s is inside
    want = 8 + 9 + 1 + 8 * 1.0 / 1.6
    assert got["out_tokens_per_s"] == pytest.approx(want / 10.0)
    assert got["attempted"] == 3 and got["failed"] == 1   # not `running`


@pytest.mark.parametrize("shift", [0.0, 0.3, 0.6, 0.9])
def test_the_rate_does_not_follow_where_the_window_falls_between_deliveries(
        shift):
    """32 lanes deliver eight tokens each at one instant, once a second:
    a count by arrival stamp reads 39 or 40 deliveries in 39.5 s with
    the phase of the window's edges; the rate must not."""
    rows = [{"idx": i, "phase": "window", "due": 0.0, "sent": 0.0,
             "slices": [[0.5, 1]] + [[1.0 + k, 8] for k in range(60)],
             "end": None, "error": None, "error_t": None,
             "prompt_len": 10, "max_new": 481, "id_min": 0, "id_max": 5}
            for i in range(32)]
    t0 = 5.0 + shift
    got = M.closed_loop(rows, t0, t0 + 39.5)
    assert got["out_tokens_per_s"] == pytest.approx(256.0)


def test_what_a_running_request_made_after_its_last_stamp_is_not_counted():
    row = _row(0, 0.0, first=1.0, n=9, gap=0.1, end=False)  # last at 1.8
    assert M.closed_loop([row], 0.0, 5.0)["out_tokens_per_s"] == \
        pytest.approx(9 / 5.0)
    assert M.closed_loop([row], 0.0, 1.4)["out_tokens_per_s"] == \
        pytest.approx((1 + 8 * 0.4 / 0.8) / 1.4)


def test_stream_faults_hold_a_stream_to_its_length_and_ids():
    ok = _row(0, 0.0, first=0.1, n=9)
    short = _row(1, 0.0, first=0.1, n=5, max_new=9)
    bad = dict(_row(2, 0.0, first=0.1, n=9), id_max=600)
    running = _row(3, 0.0, first=0.1, n=3, max_new=9, end=False)
    faults = M.stream_faults([ok, short, bad, running], 512)
    assert len(faults) == 2
    assert "request 1" in faults[0] and "request 2" in faults[1]


def _counters(evictions, reused):
    return [{"prefix_evictions": e, "prefix_tokens_reused": r}
            for e, r in zip(evictions, reused)]


@pytest.mark.parametrize("case,ok", [
    ("all_good", True), ("one_answer_differs", True),
    ("a_stream_was_lost", False), ("an_answer_is_short", False),
    ("no_eviction_where_the_fill_exceeds_the_pool", False),
    ("no_hit_after_the_eviction", False),
    ("small_fill_needs_no_eviction", True)])
def test_served_check_needs_whole_answers_and_a_hit_after_eviction(
        case, ok):
    """The engine's side of the check: four whole answers and the
    counters. Answers that differ are not the engine's fault to find:
    each distinct one goes to the reference (``answers``)."""
    import numpy as np

    import perf_serve_cell

    conf = {"correct": {"repeat_answer": 3},
            "engine": {"prefix_cache": True, "n_pages": 64}}
    mix = {"fill_pages": 80}
    served = [np.array([5, 6, 7]) for _ in range(4)]
    counters = _counters([0, 0, 0, 9, 11], [0, 0, 16, 16, 32])
    if case == "one_answer_differs":
        served[3] = np.array([5, 6, 8])
    elif case == "a_stream_was_lost":
        served.pop()
    elif case == "an_answer_is_short":
        served[1] = np.array([5, 6])
    elif case == "no_eviction_where_the_fill_exceeds_the_pool":
        counters = _counters([0] * 5, [0, 0, 16, 16, 32])
    elif case == "no_hit_after_the_eviction":
        counters = _counters([0, 0, 0, 9, 11], [0, 0, 16, 16, 16])
    elif case == "small_fill_needs_no_eviction":
        mix = {"fill_pages": 40}
        counters = _counters([0] * 5, [0, 0, 16, 32, 48])
    got = perf_serve_cell._served_check(conf, mix, served, counters)
    assert got["engine_ok"] is ok, got
    whole = case not in ("a_stream_was_lost", "an_answer_is_short")
    assert got["complete"] is whole
    assert got["identical"] is (whole and case != "one_answer_differs")
    assert got["answers"] == ([] if not whole else [[5, 6, 7]] + (
        [[5, 6, 8]] if case == "one_answer_differs" else []))


def test_on_the_chip_a_listed_metric_that_reads_nothing_fails_the_run():
    import perf_harness as H
    import run as perf_run

    found = H.find_cell(perf_testlib.benchmark(), "cgpt1b3-chat-steady")
    empty = {"run": {"e2e": {}, "rows": [], "t0": 0.0, "t1": 1.0,
                     "stats_before": {"dispatches": 0,
                                      "avg_occupancy": 0.0},
                     "stats_after": {"dispatches": 0,
                                     "avg_occupancy": 0.0},
                     "stats_delta": {}, "arrivals": {},
                     "conf": {"engine": {"chunk": 8}}}}
    assert perf_run._metrics(found, empty, 1, strict=False) == {}
    with pytest.raises(H.BenchError, match="found nothing to read"):
        perf_run._metrics(found, empty, 1, strict=True)

"""The stratified generator: two seeds offer the same work."""
import collections
import json
import os
import threading
import time

import pytest

import perf_testlib  # noqa: F401 - puts benchmarks/perf on the path

import perf_loadgen
import perf_traffic as T

MIXES = ["chat-steady", "batch-offline", "rag-burst"]
SEEDS = (3, 2 ** 31 + 12345)


def _mix(name):
    """A cell's mix, or the parked rag-burst mix kept with the tests."""
    path = os.path.join(perf_testlib.PERF, "traffic", name + ".json")
    if not os.path.exists(path):
        path = os.path.join(perf_testlib.FIXTURES, name + ".json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-steady", "rag-burst"])
def test_open_loop_same_multiset_under_two_seeds(name):
    mix = _mix(name)
    a, b = (T.open_schedule(mix, s, 40.0) for s in SEEDS)
    pairs = lambda rs: collections.Counter(  # noqa: E731
        (r.phase, r.prompt_len - r.doc_len, r.max_new) for r in rs)
    docs = lambda rs: collections.Counter(  # noqa: E731
        (r.phase, r.doc_len) for r in rs)
    assert pairs(a) == pairs(b) and docs(a) == docs(b)
    # the seed chooses the order, in every mix
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert [r.due_s for r in a] != [r.due_s for r in b]
    def gaps(rs):        # with the gap that follows the last arrival
        due = [r.due_s for r in rs if r.phase == "window"] + [40.0]
        return sorted(y - x for x, y in zip(due, due[1:]))

    if mix["arrivals"]["kind"] == "poisson":
        assert gaps(a) == pytest.approx(gaps(b), abs=1e-5)
    win = [r for r in a if r.phase == "window"]
    assert len(win) == round(mix["rate_rps"] * 40.0)
    assert all(0 <= r.due_s < 40.0 for r in win)
    assert all(r.due_s < 0 for r in a if r.phase == "ramp")


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 12345, 3000000011])
def test_every_stretch_of_an_open_loop_offers_about_the_same(seed):
    """The gaps are ordered in the same blocks as the lengths: no order
    puts the short gaps together, so no eighth of the window gets half
    as many arrivals again as its share (a whole-window shuffle gave 7
    to 25 where 16 are due, and the tails followed the seed)."""
    mix = _mix("chat-steady")
    due = [r.due_s for r in T.open_schedule(mix, seed, 40.0)
           if r.phase == "window"]
    assert len(due) == 128
    block = int(mix["block"])
    starts = due[::block] + [40.0]
    spans = [b - a for a, b in zip(starts, starts[1:])]
    # blocks differ by their share of the longest gaps' stratum only
    assert 4.0 < min(spans) and max(spans) < 6.2, spans
    counts = collections.Counter(int(t // 5.0) for t in due)
    assert max(counts.values()) - min(counts.values()) <= 10, counts


def test_lengths_stay_in_their_ranges_and_fit_the_engine():
    with open(os.path.join(perf_testlib.PERF, "configs",
                           "cerebras-gpt-1.3b-serve.json")) as f:
        eng = json.load(f)["engine"]
    for name in MIXES:
        mix = _mix(name)
        reqs = T.closed_pool(mix, 5) if mix["loop"] == "closed" \
            else T.open_schedule(mix, 5, 40.0)
        reqs += T.fill_requests(mix, 5, eng["page_size"])
        for r in reqs:
            q = r.prompt_len - r.doc_len
            assert mix["prompt"]["min"] <= q <= mix["prompt"]["max"]
            assert r.prompt_len <= eng["prompt_buckets"][-1]
            assert r.prompt_len + r.max_new <= eng["max_len"]


def test_closed_pool_is_one_multiset_and_never_repeats_a_prompt():
    mix = _mix("batch-offline")
    a, b = T.closed_pool(mix, SEEDS[0]), T.closed_pool(mix, SEEDS[1])
    assert sorted((r.prompt_len, r.max_new) for r in a) == \
        sorted((r.prompt_len, r.max_new) for r in b)
    nxt = T.closed_pool(mix, SEEDS[0], cycle=1)
    assert not {r.idx for r in a} & {r.idx for r in nxt}
    # every block of the order spans the range: no stretch is all short
    block = mix["block"]
    means = [sum(r.prompt_len for r in a[i:i + block]) / block
             for i in range(0, len(a) - block + 1, block)]
    assert max(means) - min(means) < 0.15 * (sum(means) / len(means))


def test_shared_documents_are_asked_again_with_the_same_tokens():
    mix = _mix("rag-burst")
    reqs = [r for r in T.open_schedule(mix, 9, 60.0)
            if r.phase == "window"]
    by_doc = collections.defaultdict(list)
    for r in reqs:
        by_doc[r.doc].append(r)
    counts = collections.Counter(len(v) for v in by_doc.values())
    assert set(counts) <= set(mix["shared"]["asks"]) | {1, 2}
    rs = next(v for v in by_doc.values() if len(v) >= 3)
    toks = [T.tokens_for(r, 9, 50257) for r in rs]
    dl = rs[0].doc_len
    assert all((t[:dl] == toks[0][:dl]).all() for t in toks)
    assert not (toks[0][dl:dl + 8] == toks[1][dl:dl + 8]).all()
    assert all(len(t) == r.prompt_len for t, r in zip(toks, rs))
    assert max(int(t.max()) for t in toks) < 50257


def test_fill_requests_cover_the_pool_with_one_token_each():
    for name in MIXES:
        mix = _mix(name)
        fill = T.fill_requests(mix, 1, 16)
        assert all(r.max_new == 1 and r.phase == "fill" for r in fill)
        pages = sum(-(-r.prompt_len // 16) for r in fill)
        assert pages >= 0.9 * mix["fill_pages"]
        if mix.get("shared"):
            assert len({r.doc for r in fill}) == len(fill)


def test_open_loop_stamps_from_the_due_time_and_reports_lateness():
    """A send that blocks makes the NEXT request late; its time to
    first token is counted from when it was due."""
    mix = {"loop": "open", "rate_rps": 20.0,
           "arrivals": {"kind": "poisson"},
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "answer": {"dist": "uniform", "min": 2, "max": 2},
           "block": 4}
    sched = T.open_schedule(mix, 1, 1.0)
    stamps = perf_loadgen.Stamps()

    def send(req, prompt):
        def gen():
            time.sleep(0.01)
            yield [1]
            yield [2]
        return gen()

    t0 = time.monotonic() + 0.05
    perf_loadgen.run_open(send, sched, 1, 100, stamps, lambda r, e: None,
                          t0, drain_s=5.0)
    assert len(stamps.rows) == len(sched) == 20
    for row, req in zip(stamps.rows, sched):
        assert row["due"] == pytest.approx(t0 + req.due_s)
        assert row["sent"] >= row["due"]
        assert row["sent"] - row["due"] < 0.05
        assert row["end"] is not None and row["error"] is None
        assert sum(n for _t, n in row["slices"]) == 2


def test_closed_loop_bounds_in_flight_and_records_failures():
    mix = {"loop": "closed", "clients": 3, "pool": 8,
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "answer": {"dist": "uniform", "min": 2, "max": 2},
           "block": 4}
    stamps = perf_loadgen.Stamps()
    live, peak, fails = [0], [0], []
    lock = threading.Lock()

    def send(req, prompt):
        def gen():
            with lock:
                live[0] += 1
                peak[0] = max(peak[0], live[0])
            try:
                time.sleep(0.01)
                if req.idx == 5:
                    raise RuntimeError("refused")
                yield [1, 2]
            finally:
                with lock:
                    live[0] -= 1
        return gen()

    t0 = time.monotonic()
    perf_loadgen.run_closed(
        send, lambda c: T.closed_pool(mix, 1, c), 1, 100, stamps,
        lambda row, e: fails.append(type(e).__name__), 3, t0, t0 + 0.4,
        drain_s=2.0)
    assert peak[0] == 3
    assert fails == ["RuntimeError"]
    assert len(stamps.rows) > 8            # went on to a second pass
    assert len({r["idx"] for r in stamps.rows}) == len(stamps.rows)

"""The load generator: sends a run's requests through a ``send``
callable and stamps every token slice on the client.

``send(request, prompt)`` returns an iterator of token slices (arrays).
The generator knows nothing of the system behind it; it runs in the
benchmark's own process (never the one that holds the chip), one thread
per request in flight, each blocked on its stream.

Failures are what the program raised or refused, never this file's
clock: no request carries a deadline, a request still running when the
window closes is not failed, and nothing is retried.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List

import numpy as np

import perf_traffic


class Stamps:
    def __init__(self):
        self.rows: List[dict] = []
        self._lock = threading.Lock()

    def new(self, req, due: float) -> dict:
        row = {"idx": req.idx, "phase": req.phase, "due": due,
               "sent": None, "slices": [], "end": None, "error": None,
               "error_t": None, "prompt_len": req.prompt_len,
               "max_new": req.max_new, "id_min": 0, "id_max": 0}
        with self._lock:
            self.rows.append(row)
        return row


def _one(send: Callable, req, prompt, row: dict, on_fail: Callable,
         keep: list = None):
    """Send one request and stamp its stream."""
    row["sent"] = time.monotonic()
    try:
        lo, hi, toks = 0, 0, []
        for item in send(req, prompt):
            t = time.monotonic()
            arr = np.asarray(item).reshape(-1)
            if arr.size:
                lo = min(lo, int(arr.min()))
                hi = max(hi, int(arr.max()))
                row["slices"].append([t, int(arr.size)])
                if keep is not None:
                    toks.append(arr)
        row["id_min"], row["id_max"] = lo, hi
        row["end"] = time.monotonic()
        if keep is not None:
            keep.append(np.concatenate(toks) if toks else np.zeros(0))
    except Exception as e:  # noqa: BLE001 - the program's failure: recorded
        row["error_t"] = time.monotonic()
        row["error"] = f"{type(e).__name__}: {e}"[:500]
        on_fail(row, e)


def run_batch(send, reqs, seed: int, vocab: int, stamps: Stamps,
              on_fail, concurrency: int = 8, keep: list = None):
    """Set-up traffic (cache fill, checks): ``concurrency`` callers work
    through ``reqs``; returns when all have ended."""
    it = iter(reqs)
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                req = next(it, None)
            if req is None:
                return
            prompt = perf_traffic.tokens_for(req, seed, vocab)
            _one(send, req, prompt, stamps.new(req, time.monotonic()),
                 on_fail, keep)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(1, min(concurrency, len(reqs))))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def run_open(send, schedule, seed: int, vocab: int, stamps: Stamps,
             on_fail, t0: float, drain_s: float) -> float:
    """Open loop: every request goes out at ``t0 + due_s`` whether or
    not earlier ones finished, each timed from its due time. Prompts are
    made beforehand. Returns when every stream has ended or ``drain_s``
    after the last due time has passed; returns that closing time."""
    prompts = [perf_traffic.tokens_for(r, seed, vocab) for r in schedule]
    threads = []
    for req, prompt in zip(schedule, prompts):
        due = t0 + req.due_s
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(
            target=_one, args=(send, req, prompt, stamps.new(req, due),
                               on_fail), daemon=True)
        th.start()
        threads.append(th)
    return _join(threads, drain_s)


def run_closed(send, pool_fn, seed: int, vocab: int, stamps: Stamps,
               on_fail, clients: int, t_start: float, t_end: float,
               drain_s: float) -> float:
    """Closed loop: ``clients`` callers, each sending its next request
    when its last one ends, from ``t_start`` until ``t_end``. The
    backlog is bounded by construction. Requests come from
    ``pool_fn(cycle)``, one pass of the mix's multiset after another."""
    lock = threading.Lock()
    state = {"cycle": 0, "it": iter(pool_fn(0))}

    def take():
        with lock:
            req = next(state["it"], None)
            if req is None:
                state["cycle"] += 1
                state["it"] = iter(pool_fn(state["cycle"]))
                req = next(state["it"])
            return req

    def client():
        while time.monotonic() < t_end:
            req = take()
            prompt = perf_traffic.tokens_for(req, seed, vocab)
            row = stamps.new(req, time.monotonic())
            _one(send, req, prompt, row, on_fail)
            if row["error"]:
                time.sleep(0.05)     # a refusing system is not hammered

    wait = t_start - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    wait = t_end - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    return _join(threads, drain_s)


def _join(threads, drain_s: float) -> float:
    deadline = time.monotonic() + drain_s
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    return time.monotonic()

"""From the client's stamp log to the end-to-end metrics: the
arithmetic, kept here where no later PR can change it.

A stamp row is one request as the load generator saw it (all times are
``time.monotonic()`` seconds, one clock for every process of the
machine)::

    {"idx", "phase", "due", "sent", "slices": [[t, n_tokens], ...],
     "end": t or None, "error": str or None, "prompt_len", "max_new"}

``end`` is set when the stream ended cleanly; ``error`` when the program
raised or refused. A row with neither is still running.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from perf_harness import quantile


def n_tokens(row: dict) -> int:
    return sum(n for _t, n in row["slices"])


def ttft_s(row: dict) -> Optional[float]:
    """First token's arrival minus the request's DUE time (an open loop
    is timed from when the request should have been sent)."""
    return row["slices"][0][0] - row["due"] if row["slices"] else None


def tpot_s(row: dict) -> Optional[float]:
    """(last - first token time) / (tokens - 1) of a finished stream."""
    n = n_tokens(row)
    if row.get("end") is None or n < 2:
        return None
    return (row["slices"][-1][0] - row["slices"][0][0]) / (n - 1)


def open_loop(rows: List[dict], t0: float, t1: float, t_close: float
              ) -> Dict[str, object]:
    """Requests due in [t0, t1) are attempted; one that failed, was
    refused, or has not finished when the drain closes (``t_close``)
    counts as failed and, in the tails, as the worst."""
    mine = [r for r in rows if r["phase"] == "window"
            and t0 <= r["due"] < t1]
    def lost(r):
        return bool(r.get("error")) or r.get("end") is None

    n_failed = sum(lost(r) for r in mine)
    worst = t_close - t0
    ttft = [worst if (lost(r) or ttft_s(r) is None) else ttft_s(r)
            for r in mine]
    tpot = [worst if lost(r) else tpot_s(r) for r in mine]
    tpot = [v for v in tpot if v is not None]
    late = [r["sent"] - r["due"] for r in mine if r.get("sent") is not None]
    return {
        "attempted": len(mine), "failed": n_failed,
        "ttft_p50_ms": _ms(quantile(ttft, 0.5)),
        "ttft_p90_ms": _ms(quantile(ttft, 0.9)),
        "tpot_mean_ms": _ms(sum(tpot) / len(tpot)) if tpot else None,
        "tpot_p50_ms": _ms(quantile(tpot, 0.5)),
        "tpot_p90_ms": _ms(quantile(tpot, 0.9)),
        "gen_late_p99_ms": _ms(quantile(late, 0.99)),
        "out_tokens_per_s": _window_tokens(rows, t0, t1) / (t1 - t0),
        "requests": len(mine),
    }


def closed_loop(rows: List[dict], t0: float, t1: float
                ) -> Dict[str, object]:
    """Attempted: requests that finished or failed inside the window. A
    request still running when it closes is neither. The rate counts
    every output token produced in the window (``_window_tokens``),
    whatever request it belongs to, over the whole window."""
    done = [r for r in rows if r.get("end") is not None
            and t0 <= r["end"] < t1]
    failed = [r for r in rows if r.get("error")
              and t0 <= r["error_t"] < t1]
    return {
        "attempted": len(done) + len(failed), "failed": len(failed),
        "out_tokens_per_s": _window_tokens(rows, t0, t1) / (t1 - t0),
        "ttft_p50_ms": _ms(quantile(
            [ttft_s(r) for r in done if r["slices"]], 0.5)),
        "requests": len(done),
    }


def _window_tokens(rows: List[dict], t0: float, t1: float) -> float:
    """Output tokens produced in [t0, t1). The engine hands a stream its
    tokens a chunk at a time, every lane's at one instant, so a count by
    arrival stamp jumps by a whole delivery (32 lanes x 8 tokens: 2.5%
    of a 40 s window) with where the window's edges fall between two
    deliveries, which follows the seed and not the system (PERF.md,
    section 6, PR 29). So a slice's tokens are laid evenly over the time
    since the same request's previous slice, in which the program made
    them one a step, and the window is credited the part that lies in
    it. A request's first slice (the prefill's token) has no earlier
    stamp and counts at its own. What a request that is still running
    made after its last stamp is not counted: the mix's ``drain_s``
    keeps the stamps coming past the window's end."""
    total = 0.0
    for r in rows:
        prev = None
        for t, n in r["slices"]:
            if prev is None or t <= prev:
                total += n if t0 <= t < t1 else 0
            else:
                total += n * max(0.0, min(t, t1) - max(prev, t0)) \
                    / (t - prev)
            prev = t
    return total


def _ms(v: Optional[float]) -> Optional[float]:
    return None if v is None else v * 1e3


def stream_faults(rows: List[dict], vocab: int) -> List[str]:
    """What ``correct`` holds every finished stream to: exactly
    ``max_new`` tokens, every id a row of the embedding table
    (``vocab`` is the rows held: with random weights the program can
    sample a padding row, which it does not mask; see PERF.md)."""
    out = []
    for r in rows:
        if r.get("end") is None:
            continue
        if n_tokens(r) != r["max_new"]:
            out.append(f"request {r['idx']}: {n_tokens(r)} tokens, "
                       f"wanted {r['max_new']}")
        if r.get("id_max", 0) >= vocab or r.get("id_min", 0) < 0:
            out.append(f"request {r['idx']}: token id outside "
                       f"[0, {vocab})")
    return out

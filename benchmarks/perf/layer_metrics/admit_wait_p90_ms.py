"""90th percentile of (first slice out of the engine - arrival at the
replica), over requests sent in the window: the wait for a free slot at a
chunk boundary and then the prefill. Stamped by the benchmark's own
deployment class around the engine's stream; the engine.admission span
gives the wait alone and is the tracing issue's to make readable. It is
nearly all of first_token_p90_ms; the prefill in it stops every running
lane, which is how it reaches tpot_mean_ms, the cell's end-to-end time per token.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_mean_ms"


from perf_harness import quantile


def read(run):
    arr = run.get("arrivals") or {}
    t0, t1 = run["t0"], run["t1"]
    vals = [arr[r["idx"]][1] - arr[r["idx"]][0] for r in run["rows"]
            if r["idx"] in arr and arr[r["idx"]][1] is not None
            and r.get("sent") is not None and t0 <= r["sent"] < t1]
    v = quantile(vals, 0.9)
    return None if v is None else v * 1e3

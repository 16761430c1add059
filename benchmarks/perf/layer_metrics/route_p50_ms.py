"""Median of (arrival at the replica's __call__ - client send): the handle,
the router's pick and the hop to the replica. Both stamps are
time.monotonic(), one clock for the machine. It is part of the first
token's wait (first_token_p90_ms), which no cell reports end to end; MOVES
names the cell's remaining end-to-end tail, as the contract asks (PERF.md,
section 2).
"""
LAYER = "router and handle"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_mean_ms"


from perf_harness import quantile


def read(run):
    arr = run.get("arrivals") or {}
    t0, t1 = run["t0"], run["t1"]
    vals = [arr[r["idx"]][0] - r["sent"] for r in run["rows"]
            if r["idx"] in arr and r.get("sent") is not None
            and t0 <= r["sent"] < t1]
    v = quantile(vals, 0.5)
    return None if v is None else v * 1e3

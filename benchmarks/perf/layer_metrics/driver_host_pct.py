"""The share of the engine driver's working time in which no dispatch
of its own is in flight: its phases admit + cover + deliver + other over
everything but idle (``engine.stats()["driver_ns_<phase>"]``, self
times that sum to ``driver_ns_total``). The driver blocks on every
dispatch, so this is the host's part of the loop: what
``device_idle_pct`` shows for the traced slice, over the whole window.
"""
LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"

HOST = ("admit", "cover", "deliver", "other")


def read(run):
    d = run.get("stats_delta") or {}
    keys = [f"driver_ns_{p}" for p in HOST + ("idle", "total")]
    if any(k not in d for k in keys):
        return None
    working = d["driver_ns_total"] - d["driver_ns_idle"]
    if working <= 0:
        return None
    return 100.0 * sum(d[f"driver_ns_{p}"] for p in HOST) / working

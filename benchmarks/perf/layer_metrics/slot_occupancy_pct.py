"""Mean share of the engine's slots that were active, over the chunk
dispatches between the counters' two readings (before the ramp, at the
window's close): engine.stats() avg_occupancy times dispatches,
differenced.
"""
LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    a, b = run["stats_before"], run["stats_after"]
    d = b["dispatches"] - a["dispatches"]
    if d <= 0:
        return None
    occ = b["avg_occupancy"] * max(b["dispatches"], 1) \
        - a["avg_occupancy"] * max(a["dispatches"], 1)
    return 100.0 * occ / d

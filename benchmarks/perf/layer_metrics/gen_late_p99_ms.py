"""99th percentile of (time a request was sent - time it was due), over
requests due in the window. A late generator flatters the time to first
token's queueing and bunches arrivals, which changes how full the slots
are and so the time per token: a guard more than a lever.
"""
LAYER = "load generator"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_mean_ms"


def read(run):
    return run["e2e"].get("gen_late_p99_ms")

"""1 - (union of the intervals in which an operation ran on the device) /
(traced slice), mean over the chips used.
"""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_mean_ms"


def read(run):
    tr = run.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

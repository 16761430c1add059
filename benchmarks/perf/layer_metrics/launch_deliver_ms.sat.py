"""Host time a launch spends handing its tokens to the lanes: the
driver's phase ``deliver`` (routing, trimming, one queue put a lane,
frees) over ``dispatches``. Every running lane waits for it before its
next step can start.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

KEYS = ("driver_ns_deliver",)


def read(run):
    d = run.get("stats_delta") or {}
    if any(k not in d for k in KEYS) or d.get("dispatches", 0) <= 0:
        return None
    return sum(d[k] for k in KEYS) / d["dispatches"] / 1e6

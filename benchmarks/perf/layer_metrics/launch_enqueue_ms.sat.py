"""Host time of a decode (or verify) launch before the device can have
all of it: from the ``decode`` phase's start to the return of the call
into the compiled program (the lane arrays handed up, the dispatch),
the step ``decode.enqueue`` of the engine's clock over ``dispatches``.
Lane state kept on the device would shorten it.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

KEYS = ("driver_ns_decode_enqueue",)


def read(run):
    d = run.get("stats_delta") or {}
    if any(k not in d for k in KEYS) or d.get("dispatches", 0) <= 0:
        return None
    return sum(d[k] for k in KEYS) / d["dispatches"] / 1e6

"""Host time of a decode (or verify) launch after the device is done:
tokens, PRNG lanes and the model's counters copied to the host, the
step ``decode.read`` of the engine's clock over ``dispatches``. Reading
launch n after enqueuing launch n+1 would hide it.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

KEYS = ("driver_ns_decode_read",)


def read(run):
    d = run.get("stats_delta") or {}
    if any(k not in d for k in KEYS) or d.get("dispatches", 0) <= 0:
        return None
    return sum(d[k] for k in KEYS) / d["dispatches"] / 1e6

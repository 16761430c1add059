"""The host's part of a prefill before the device can start: making
the request's key and the call into the compiled program, the steps
``prefill.key`` and ``prefill.dispatch`` of the engine's clock over
``prefills``. One launch for several prompts would share it.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

KEYS = ("driver_ns_prefill_key", "driver_ns_prefill_dispatch")


def read(run):
    d = run.get("stats_delta") or {}
    if any(k not in d for k in KEYS) or d.get("prefills", 0) <= 0:
        return None
    return sum(d[k] for k in KEYS) / d["prefills"] / 1e6

"""The driver's wait for a prefill's first token: the step
``prefill.read`` of the engine's clock over ``prefills``. Beside
``prefill_prog_dev_ms`` (the program's device time in the traced slice)
it says over the whole window that the wait IS the device, and with
``prefill_enqueue_ms`` it is ``prefill_host_mean_ms`` taken apart.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    if "driver_ns_prefill_read" not in d or d.get("prefills", 0) <= 0:
        return None
    return d["driver_ns_prefill_read"] / d["prefills"] / 1e6

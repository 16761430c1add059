"""Of the driver's phase ``deliver``, the share its thread spent OFF the
processor: 1 - ``driver_cpu_ns_deliver`` / ``driver_ns_deliver`` (the
phase's self time on the thread's CPU clock and on the wall's). The
phase holds no dispatch, so this is waiting for the interpreter lock
the woken consumers took, or a descheduled thread: above a half,
delivery is a matter of how consumers are woken, not of the walk.
"""
LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    if "driver_cpu_ns_deliver" not in d or \
            d.get("driver_ns_deliver", 0) <= 0:
        return None
    return 100.0 * (1 - d["driver_cpu_ns_deliver"]
                    / d["driver_ns_deliver"])

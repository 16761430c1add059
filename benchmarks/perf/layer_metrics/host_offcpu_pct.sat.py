"""Of the driver's four host phases (``admit``, ``cover``, ``deliver``,
``other``: it holds no dispatch in them), the share its thread spent OFF
the processor: 1 - sum ``driver_cpu_ns_<phase>`` / sum
``driver_ns_<phase>``. Delivery's ``q.put``s make consumers runnable
and the driver loses the interpreter lock wherever it stands when they
take it, so the waiting is read over the four together: above a half,
the host between launches is a matter of how consumers are woken and
what they do under the lock, not of the driver's own work.
"""
LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

PHASES = ("admit", "cover", "deliver", "other")


def read(run):
    d = run.get("stats_delta") or {}
    if any(f"driver_cpu_ns_{p}" not in d or f"driver_ns_{p}" not in d
           for p in PHASES):
        return None
    wall = sum(d[f"driver_ns_{p}"] for p in PHASES)
    if wall <= 0:
        return None
    return 100.0 * (1 - sum(d[f"driver_cpu_ns_{p}"] for p in PHASES)
                    / wall)

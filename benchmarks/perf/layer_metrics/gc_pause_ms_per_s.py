"""Milliseconds of garbage collection a second of the window, in the
replica's process: ``gc_pause_ns_sum`` (every generation, every thread;
one ``gc.callbacks`` listener) over the seconds between the counters'
two readings on the driver's own clock (``driver_ns_total``). A
collection holds the interpreter lock, so this is what collection costs
a replica that keeps every stream's queue; ``gc2_pause_ns_sum`` in
``run.json`` is the full collections' part.
"""
LAYER = "admission and batching"
UNIT = "ms/s"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    d = run.get("stats_delta") or {}
    if "gc_pause_ns_sum" not in d or d.get("driver_ns_total", 0) <= 0:
        return None
    return 1e3 * d["gc_pause_ns_sum"] / d["driver_ns_total"]

"""Seconds the window lost to STALLED launches: the engine classifies
each decode or verify launch as its record closes (``engine._stall``).
A launch is judged by the launches of half to twice its lanes among the
32 before it, by its period (from the previous launch's read to its
own) outside other requests' prefills, which count only beyond what
their prompts usually take; above four times the usual it adds its
excess to ``launch_stall_ns_sum``. A ramp and a burst of admissions
read 0.0, a standstill its length, in a prefill's read too; a traced
run reads the time every launch waits while the profiler stops. 0.0 is
a reading: the counter is there and no launch stalled. The first thing
to look at when a cell's runs spread widely; in ``run.json``
``launch_stall_ns_<part>`` says where the excess fell and
``launch_stall_cpu_ns_sum`` over ``launch_stall_wall_ns_sum`` whether
the replica was on a processor through it.
"""
LAYER = "admission and batching"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    d = run.get("stats_delta") or {}
    if "launch_stall_ns_sum" not in d:
        return None
    return d["launch_stall_ns_sum"] / 1e9

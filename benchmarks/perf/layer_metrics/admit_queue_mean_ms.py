"""Mean wait for a slot: from a request's arrival in the engine's queue
to the moment a slot and its pages were granted, over the requests
admitted between the counters' two readings (before the ramp, at the
window's close). The engine stamps both ends on its driver thread
(``time.monotonic_ns()``) and sums the differences:
``engine.stats()["admission_wait_ns_sum"]`` over ``admitted``. It is
the wait alone: the prefill that follows is ``prefill_host_mean_ms``,
and ``admit_wait_p90_ms`` is the p90 of the two together.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    d = run.get("stats_delta") or {}
    if "admission_wait_ns_sum" not in d or d.get("admitted", 0) <= 0:
        return None
    return d["admission_wait_ns_sum"] / d["admitted"] / 1e6

"""Mean time of a prefill as the engine's driver sees it: slot granted
to the first token read on the host (making the request's key, the
dispatch, the blocking read), over the prefills between the counters'
two readings: ``engine.stats()["prefill_ns_sum"]`` over ``prefills``.
Every running lane waits this long for each admission.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    d = run.get("stats_delta") or {}
    if "prefill_ns_sum" not in d or d.get("prefills", 0) <= 0:
        return None
    return d["prefill_ns_sum"] / d["prefills"] / 1e6

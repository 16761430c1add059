"""Of the time lanes were running, the share in which no decode step
was: before every decode (or verify) dispatch the engine adds the time
since the previous one's tokens were read, if a lane stayed occupied
all the while (``decode_gap_ns_sum``: delivery, admission, other
requests' prefills); ``driver_ns_decode`` is the time in the dispatches
themselves. The measured form of "a prefill stops every lane", over the
whole window, not the traced slice.
"""
LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    d = run.get("stats_delta") or {}
    if "decode_gap_ns_sum" not in d or "driver_ns_decode" not in d:
        return None
    whole = d["decode_gap_ns_sum"] + d["driver_ns_decode"]
    if whole <= 0:
        return None
    return 100.0 * d["decode_gap_ns_sum"] / whole

"""Of the time lanes were running (``decode_gap_ns_sum`` +
``driver_ns_decode``), the share that was the HOST's: the gap between
decode dispatches less the prefills in it (delivery, admission,
coverage, the loop), plus the launch's own host steps, its enqueue and
its reads (``driver_ns_decode_enqueue``, ``driver_ns_decode_read``).
With ``lane_prefill_stall_pct`` and the wait for the device
(``driver_ns_decode_wait``) it sums to the whole. What a leaner or
overlapped host loop could win back, and no more.
"""
LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    keys = ("decode_gap_prefill_ns_sum", "decode_gap_ns_sum",
            "driver_ns_decode", "driver_ns_decode_enqueue",
            "driver_ns_decode_read")
    if any(k not in d for k in keys):
        return None
    lane = d["decode_gap_ns_sum"] + d["driver_ns_decode"]
    if lane <= 0:
        return None
    host = (d["decode_gap_ns_sum"] - d["decode_gap_prefill_ns_sum"]
            + d["driver_ns_decode_enqueue"] + d["driver_ns_decode_read"])
    return 100.0 * host / lane

"""Of the time lanes were running (``decode_gap_ns_sum`` +
``driver_ns_decode``), the share in which the driver was in ANOTHER
request's prefill phase: before every decode (or verify) dispatch the
engine adds what ``driver_ns_prefill`` grew by since the previous one's
tokens were read (``decode_gap_prefill_ns_sum``). The part of
``decode_stall_pct`` that running prefills beside decodes could win
back, and no more.
"""
LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    keys = ("decode_gap_prefill_ns_sum", "decode_gap_ns_sum",
            "driver_ns_decode")
    if any(k not in d for k in keys):
        return None
    lane = d["decode_gap_ns_sum"] + d["driver_ns_decode"]
    if lane <= 0:
        return None
    return 100.0 * d["decode_gap_prefill_ns_sum"] / lane

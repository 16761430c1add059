"""The chunked state-space prefill's share of busy device time: the
self time of the operations under the scope ``ssm.prefill``
(``models/ssm_hybrid.py _ssd_chunked``: a prompt's recurrence in
chunks of 128 tokens from a zero state, the masked quadratic form
inside a chunk and the state passed from chunk to chunk; it rebuilds
the slot's state) over the seconds in which any operation ran. What a
prefill costs stops every lane (``decode_stall_pct.sat``).
"""
from perf_harness import load_reader

LAYER = "state-space state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "ssm.prefill"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

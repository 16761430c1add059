"""The chunked linear-attention prefill's share of busy device time:
the self time of the operations under the scope ``kda.prefill``
(``models/kda_moe.py _kda_chunked``: a prompt's recurrence in chunks of
64 tokens from a zero state, the within-chunk triangular solve and the
state passed from chunk to chunk; it rebuilds the slot's state) over
the seconds in which any operation ran. What a prefill costs stops
every lane (``decode_stall_pct.sat``).
"""
from perf_harness import load_reader

LAYER = "linear-attention state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "kda.prefill"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

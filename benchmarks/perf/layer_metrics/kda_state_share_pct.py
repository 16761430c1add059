"""The linear-attention recurrence's share of busy device time: the
self time of the operations under the scope ``kda.state`` (``models/
kda_moe.py _slot_decode_step_paged``: every live lane's ``[64, 128,
128]`` float32 state a KDA layer, decayed a channel, read for ``S^T
(alpha k)`` and ``S^T (alpha q)``, updated by a rank-one term and
written back in place) over the seconds in which any operation ran.
Plain XLA, no Pallas kernel.
"""
from perf_harness import load_reader

LAYER = "linear-attention state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "kda.state"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

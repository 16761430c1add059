"""The shared MLP's share of busy device time in the state-space expert
decoder's cell: the self time of the operations under the scope
``moe.shared`` (``models/ssm_moe.py _ffn``: the gated MLP 1,536 wide
that every token takes beside its routed experts, ten layers; decode
and prefill alike) over the seconds in which any operation ran.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "moe.shared"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

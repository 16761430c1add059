"""The dense MLP's share of busy device time: the self time of the
operations under the scope ``hybrid.mlp`` (``models/ssm_hybrid.py
_mlp``: a gated SiLU MLP 21,504 wide with its two multipliers, every
layer, every token; decode and prefill programs alike) over the
seconds in which any operation ran. 3.30 GB of weights a decode step
of five layers, read whatever is live: the part of the step that a
faster recurrence cannot shorten.
"""
from perf_harness import load_reader

LAYER = "dense MLP and head"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "hybrid.mlp"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

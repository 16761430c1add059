"""The dense path's share of busy device time: the self time of the
operations under the scope ``scmoe.dense`` (``models/scmoe.py _dense``:
a layer's TWO gated SiLU FFNs 12288 wide, which every token takes
beside the shortcut-connected expert layer; decode and prefill programs
alike) over the seconds in which any operation ran. 3.62 GB of
weights a decode step of four layers, read whatever the router chose:
the part of the step the expert layer's free choices cannot shorten.
"""
from perf_harness import load_reader

LAYER = "dense path beside the experts"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "scmoe.dense"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

"""The routed experts' grouped products' share of busy device time in a
model whose router also scores identity experts: the self time of the
operations under the scope ``moe.experts`` (``models/moe.py
dropless_experts``: the loop over the blocks that exist, each block of
rows times its expert's three matrices; decode and prefill programs
alike) over the seconds in which any operation ran. Only the choices
that fell on a routed expert HELD here reach that scope: at 128 lanes
and 16 of 512 experts about 2 tokens an expert a step, yet nearly
every held expert's 75 MB are read in a step.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "moe.experts"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

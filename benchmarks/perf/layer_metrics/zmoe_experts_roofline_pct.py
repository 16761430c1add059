"""Roofline share of the routed experts' grouped products in a decode
step of a model with zero-computation experts: as
``moe_experts_roofline_pct`` reads it (the scope
``decode_step/moe.experts``'s device time a step against the larger of
the bytes over the peak bytes/s and the operations over the peak
FLOP/s), with the bytes and operations the configuration's
ARCHITECTURE file counts (``moe_experts_cost``): the touched experts'
three matrices once FROM THE COUNTER ``moe_experts_touched_sum /
moe_steps``, and 2 x 3 x h x f operations a choice that landed on a
held routed expert (``moe_tokens_here_sum``); a choice of an identity
expert is no part of the scope and counts nothing. Bound by bandwidth
at 2 tokens an expert.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(run):
    return load_reader("moe_experts_roofline_pct").read(run)

"""moe_experts_roofline_pct for the state-space expert decoder's cell: the
least time the chip could take for the scope ``decode_step/moe.experts``
over the time it took a step, by THIS configuration's architecture file
(``granite_moe_hybrid.moe_experts_cost``: the touched experts FROM THE
COUNTER x 9,437,184 parameters once in ``numerics.compute_dtype``; 2 x 3
x 4096 x 768 operations a choice that landed here). Bound by bandwidth
at 17.8 tokens an expert.
"""
from perf_harness import twin

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("moe_experts_roofline_pct")

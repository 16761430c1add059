"""moe_experts_share_pct for the state-space expert decoder's cell (the
list of ``moe_experts_share_pct`` is pinned to the cell that brought it,
so a model that shares the scope has a twin): the self time under
``moe.experts`` (``moe.dropless_experts``: the blocks that exist, each
times its expert's three 4096 x 768 matrices, ten layers) over the
seconds in which any operation ran.
"""
from perf_harness import twin

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("moe_experts_share_pct")

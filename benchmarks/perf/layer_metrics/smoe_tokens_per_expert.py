"""moe_tokens_per_expert for the state-space expert decoder's cell: the
engine's counters ``moe_tokens_here_sum`` over
``moe_experts_touched_sum``. 128 lanes x 10 choices / 72 experts = 17.8
where every lane is full, what a held expert sees when the two chips
that share the layer bring 64 lanes each.
"""
from perf_harness import twin

LAYER = "expert layer"
UNIT = "tokens"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("moe_tokens_per_expert")

"""How uneven the routing is over the experts held: the fullest held
expert's tokens over the mean of the touched ones, a decode step a
layer (the engine's counters ``moe_expert_peak_sum`` over
``moe_tokens_here_sum / moe_experts_touched_sum``). 1 is even. The
layer is dropless, so an uneven step costs the fullest expert's blocks
and drops nothing; at 5.3 tokens an expert the chance spread alone
reads about 2.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    per_step = load_reader("moe_experts_touched_pct").per_step
    peak = per_step(run, "moe_expert_peak_sum")
    mean = load_reader("moe_tokens_per_expert").read(run)
    if not peak or not mean:
        return None
    return peak / mean

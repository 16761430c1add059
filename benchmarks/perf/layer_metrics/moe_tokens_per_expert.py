"""Tokens a touched expert sees, a decode step a layer: the engine's
counters ``moe_tokens_here_sum`` (token-choices that landed on held
experts) over ``moe_experts_touched_sum``. The rows of each grouped
product: 128 lanes x 8 choices / 192 experts = 5.3 where every lane is
full, what an expert sees in the deployment of 16 chips with 8 lanes
each.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "tokens"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    per_step = load_reader("moe_experts_touched_pct").per_step
    here = per_step(run, "moe_tokens_here_sum")
    touched = per_step(run, "moe_experts_touched_sum")
    if not here or not touched:
        return None
    return here / touched

"""Tokens a touched expert sees, a decode step a layer, where part of
the router's choices fall on identity experts: the engine's counters
``moe_tokens_here_sum`` over ``moe_experts_touched_sum``, as
``moe_tokens_per_expert`` reads them. 128 lanes x 12 choices / 768
scores = 2.0 a held expert where every lane is full (a touched one sees
a little more: it has at least one), what an expert sees in the
deployment of 32 chips with 4 lanes each.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "tokens"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    return load_reader("moe_tokens_per_expert").read(run)

"""moe_imbalance for the state-space expert decoder's cell: the fullest
held expert's tokens over the mean of the touched ones, a decode step a
layer, from the engine's counters. 1 is even; at 17.8 tokens an expert
the chance spread alone reads about 1.6.
"""
from perf_harness import twin

LAYER = "expert layer"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("moe_imbalance")

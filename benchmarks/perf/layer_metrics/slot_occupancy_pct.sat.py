"""slot_occupancy_pct in the saturated (closed-loop) cells, where it moves
the tokens per second.
"""
from perf_harness import twin

LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("slot_occupancy_pct")

"""launch_stall_s in the saturated cells.
"""
from perf_harness import twin

LAYER = "admission and batching"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("launch_stall_s")

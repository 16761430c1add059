"""deliver_hold_mean_ms in the saturated cells.
"""
from perf_harness import twin

LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("deliver_hold_mean_ms")

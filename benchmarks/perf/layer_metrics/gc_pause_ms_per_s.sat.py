"""gc_pause_ms_per_s in the saturated cells.
"""
from perf_harness import twin

LAYER = "admission and batching"
UNIT = "ms/s"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("gc_pause_ms_per_s")

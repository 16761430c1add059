"""compiles_in_window in the saturated cells.
"""
from perf_harness import twin

LAYER = "model step"
UNIT = "1"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("compiles_in_window")

"""decode_stall_pct in the saturated cells.
"""
from perf_harness import twin

LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("decode_stall_pct")

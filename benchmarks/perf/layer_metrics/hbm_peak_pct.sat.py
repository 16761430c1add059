"""hbm_peak_pct in the saturated cells.
"""
from perf_harness import twin

LAYER = "device"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("hbm_peak_pct")

"""hbm_peak_pct in the training cells.
"""
from perf_harness import twin

LAYER = "device"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_tokens_per_s"

read = twin("hbm_peak_pct")

"""device_idle_pct in the training cells.
"""
from perf_harness import twin

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"

read = twin("device_idle_pct")

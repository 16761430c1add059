"""device_idle_pct in the saturated cells.
"""
from perf_harness import twin

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("device_idle_pct")

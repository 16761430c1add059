"""prefill_prog_dev_ms in the saturated cells.
"""
from perf_harness import twin

LAYER = "model step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("prefill_prog_dev_ms")

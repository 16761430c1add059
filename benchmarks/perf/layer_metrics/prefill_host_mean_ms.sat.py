"""prefill_host_mean_ms in the saturated (closed-loop) cells, where it
moves the tokens per second.
"""
from perf_harness import twin

LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("prefill_host_mean_ms")

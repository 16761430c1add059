"""attn_kernel_share_pct in the saturated cells.
"""
from perf_harness import twin

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("attn_kernel_share_pct")

"""state_hbm_pct for the state-space expert decoder's cell
(``state_hbm_pct``'s own list is pinned to the cell that brought it):
what the cache manager gives to per-slot state rather than to pages,
here a ``[128, 64, 128]`` float32 state and a convolution's tail in 9
layers of 10, over the chip's memory.
"""
from perf_harness import twin

LAYER = "KV page manager"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

read = twin("state_hbm_pct")

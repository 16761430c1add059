"""ssm_prefill_share_pct for the state-space expert decoder's cell: the
self time under the scope ``ssm.prefill`` (``ssm_hybrid.ssd_chunked``: a
prompt's recurrence in chunks of 256 tokens from a zero state, 9
layers of 128 heads) over the seconds in which any operation ran.
"""
from perf_harness import twin

LAYER = "state-space state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("ssm_prefill_share_pct")

"""ssm_proj_share_pct for the state-space expert decoder's cell: the self
time under the scope ``ssm.proj`` (the shared mixer outside its
recurrence: the input projection 4096 -> 16,768, the width-4
convolution over ``x | B | C`` with its tail, the gates, the gated norm
over 8,192 channels and the output projection; decode and prefill
alike) over the seconds in which any operation ran.
"""
from perf_harness import twin

LAYER = "state-space state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("ssm_proj_share_pct")

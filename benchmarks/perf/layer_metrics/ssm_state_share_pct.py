"""The state-space recurrence's share of busy device time: the self
time of the operations under the scope ``ssm.state`` (``models/
ssm_hybrid.py _slot_decode_step_paged``: every live lane's ``[32, 128,
256]`` float32 state a layer, decayed a head, updated by the rank-one
term ``dt x (x) B``, read for ``S C`` and written back in place) over
the seconds in which any operation ran. Plain XLA, no Pallas kernel:
the state is read for ``y`` and read again to be written.
"""
from perf_harness import load_reader

LAYER = "state-space state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "ssm.state"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

"""ssm_state_share_pct for the state-space expert decoder's cell (the list
of ``ssm_state_share_pct`` is pinned to the cell that brought it, so a
model that shares the scope has a twin): the self time under the scope
``ssm.state`` (``models/ssm_hybrid.py ssm_decode``, called by
``models/ssm_moe.py``: every live lane's ``[128, 64, 128]`` float32
state in each of the 9 Mamba-2 layers, through the ``ssm_state`` kernel)
over the seconds in which any operation ran.
"""
from perf_harness import twin

LAYER = "state-space state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("ssm_state_share_pct")

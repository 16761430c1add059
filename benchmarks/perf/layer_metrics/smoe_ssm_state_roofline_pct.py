"""ssm_state_roofline_pct for the state-space expert decoder's cell: the
least time the chip could take for the scope ``decode_step/ssm.state``
over the time it took a step, the bytes and operations counted by THIS
configuration's architecture file
(``granite_moe_hybrid.ssm_state_cost``: the live lanes FROM
``state_lanes_sum``, 9 Mamba-2 layers, 4,194,304 B a lane a layer once
in and once out; five operations an element).
"""
from perf_harness import twin

LAYER = "state-space state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("ssm_state_roofline_pct")

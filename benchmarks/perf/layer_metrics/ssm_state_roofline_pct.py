"""Roofline share of the state-space recurrence in a decode step: the
least time the chip could take for it, the larger of its bytes over the
peak bytes/s and its operations over the peak FLOP/s, over the device
time the scope ``decode_step/ssm.state`` took a step
(``moe_experts_roofline_pct`` says how a scope's time a step is read).
Bytes and operations are the configuration's ARCHITECTURE file's to
count (``ssm_state_cost``: the lanes whose state a step read and wrote,
FROM THE ENGINE'S COUNTER ``state_lanes_sum`` over the decode steps and
never all slots by assumption, each lane's state in every layer once in
and once out in ``numerics.state_dtype``; five operations an element of
it). Bound by bandwidth: 8.4 MB a lane a layer against 5 MFLOP. Plain
XLA reads the state once for ``y`` and once more to write it, so this
share says how far a kernel that holds a lane's state in fast memory
for the whole step could go.
"""
import os

from perf_harness import load_architecture, load_reader

LAYER = "state-space state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "decode_step/ssm.state"
_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    if not (run.get("trace") or {}).get("scopes"):
        return None
    count = getattr(load_architecture(run["conf"], _HERE),
                    "ssm_state_cost", None)
    if count is None:
        return None
    return load_reader("moe_experts_roofline_pct").share_of_least(
        run, SCOPE, count(run["conf"], run.get("stats_delta") or {}))

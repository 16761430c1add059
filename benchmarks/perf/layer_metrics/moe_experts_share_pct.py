"""The routed experts' grouped products' share of busy device time:
the self time of the operations under the scope ``moe.experts``
(``models/moe.py dropless_moe``: the loop over the blocks that exist,
each block of rows times its expert's three matrices; decode and
prefill programs alike) over the seconds in which any operation ran.
The layer that a deployment by experts exists for: at 128 lanes every
held expert's 88 MB are read in nearly every step.
"""
from trace_reduce import scope_seconds

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "moe.experts"


def share(run, scope):
    tr = run.get("trace") or {}
    seconds = scope_seconds(run, scope)
    if seconds is None or not tr.get("busy_s"):
        return None
    return 100.0 * seconds / tr["busy_s"]


def read(run):
    return share(run, SCOPE)

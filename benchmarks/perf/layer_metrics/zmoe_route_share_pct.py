"""Routing's share of busy device time where some choices cost
nothing: the self time of the operations under the scopes ``moe.route``
(the router product over all 768 scores, the float32 softmax, the
selection bias and the top 12, the sort of the token-choices by expert,
their rows gathered into blocks, and the combine) and ``moe.zero`` (the
identity experts' part: the chosen zero experts' weights summed, times
the token's own state) over the seconds in which any operation ran.
None of it multiplies by an expert's matrices: what dropless dispatch
and the zero-computation experts cost together.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPES = ("moe.route", "moe.zero")


def read(run):
    share = load_reader("moe_experts_share_pct").share
    parts = [share(run, scope) for scope in SCOPES]
    if parts[0] is None:
        return None
    return sum(p or 0.0 for p in parts)

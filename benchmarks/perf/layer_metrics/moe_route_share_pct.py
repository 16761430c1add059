"""Routing's share of busy device time: the self time of the operations
under the scope ``moe.route`` (``models/moe.py dropless_moe``: the
router product over all 192 experts, sigmoid, the group and expert
selection, the sort of the token-choices by expert, their rows
gathered into blocks, and the combine) over the seconds in which any
operation ran. None of it multiplies by an expert: what it costs is
what dropless dispatch costs.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "moe.route"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

"""moe_route_share_pct for the state-space expert decoder's cell: the self
time under ``moe.route`` (the router's product over all 72 outputs, the
top 10 logits and the softmax over them, the sort of the token-choices
by expert, their rows gathered into blocks, and the combine) over the
seconds in which any operation ran.
"""
from perf_harness import twin

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

read = twin("moe_route_share_pct")

"""The head's share of busy device time: the self time of the
operations under the scope ``lm.head`` (``models/ssm_hybrid.py _head``:
the final norm and the untied 261,120-row head's product, 2.67 GB a
decode step, float32 logits ``[128, 261120]``; decode and prefill
programs alike; the argmax over them is the frame's and lies outside)
over the seconds in which any operation ran.
"""
from perf_harness import load_reader

LAYER = "dense MLP and head"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "lm.head"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

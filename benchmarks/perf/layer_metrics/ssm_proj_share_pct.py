"""The state-space mixer's share of busy device time OUTSIDE the
recurrence: the self time of the operations under the scope
``ssm.proj`` (``models/ssm_hybrid.py``: the input projection 5120 ->
9248 and its multipliers, the width-4 convolution over ``x | B | C``
with its tail, the gates ``dt`` and ``a``, the gated grouped norm and
the output projection; decode and prefill programs alike) over the
seconds in which any operation ran. 0.68 GB of weights a decode step
of five layers.
"""
from perf_harness import load_reader

LAYER = "state-space state"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "ssm.proj"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

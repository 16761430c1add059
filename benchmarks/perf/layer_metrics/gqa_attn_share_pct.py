"""Decode's grouped-query attention's share of busy device time: the
self time of the operations under the scope ``gqa.attention``
(``models/kda_moe.py _gqa_attention_gather``: the gather of a lane's
key and value pages through the page table, 64 query heads' scores
against 8 KV heads, the softmax and the weighted sum; one layer in
four) over the seconds in which any operation ran. Plain XLA, no Pallas
kernel (``attn_kernel_share_pct`` is not listed for this model's
cells).
"""
from perf_harness import load_reader

LAYER = "GQA attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "gqa.attention"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

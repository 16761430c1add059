"""Decode's rotary grouped-query attention's share of busy device
time: the self time of the operations under the scope
``hgqa.attention`` (``models/ssm_hybrid.py _slot_decode_step_paged``
through ``models/kda_moe.py gqa_decode_attention``: 20 query heads over
4 KV heads of 128 on each lane's live pages, in every layer BESIDE the
state-space mixer) over the seconds in which any operation ran.
"""
from perf_harness import load_reader

LAYER = "parallel GQA attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "hgqa.attention"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

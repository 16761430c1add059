"""Decode's NoPE grouped-query attention's share of busy device time in
the state-space expert decoder's cell: the self time of the operations
under the scope ``smoe.attention`` (``models/ssm_moe.py
_slot_decode_step_paged`` through ``models/kda_moe.py
gqa_decode_attention``: 32 query heads over 8 KV heads of 128 on each
lane's live pages, ONE layer in ten) over the seconds in which any
operation ran.
"""
from perf_harness import load_reader

LAYER = "GQA attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "smoe.attention"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

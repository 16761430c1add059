"""The lightning indexer's share of busy device time: the self time of
the operations under the scope ``dsa.index`` (``models/dsa_moe.py
decode_attention``: a lane's cached index keys gathered through the
page table, 64 small heads' scores against ONE 128-wide key a token,
ReLU, the heads' weighted sum) over the seconds in which any operation
ran. Paid for EVERY cached token, where the attention behind it pays
for the 2,048 picked ones: the part of the mechanism that still grows
with the context.
"""
from perf_harness import load_reader

LAYER = "sparse latent attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "dsa.index"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

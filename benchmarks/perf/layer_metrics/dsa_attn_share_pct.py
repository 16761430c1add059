"""The picked tokens' attention's share of busy device time: the self
time of the operations under the scope ``dsa.attention`` (``models/
dsa_moe.py decode_attention``: the absorbed latent attention over a
lane's live pages with every token outside the selection masked out of
the softmax, ``mla_moe``'s kernel; 128 heads against ONE 576-wide key a
token) over the seconds in which any operation ran.
"""
from perf_harness import load_reader

LAYER = "sparse latent attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "dsa.attention"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

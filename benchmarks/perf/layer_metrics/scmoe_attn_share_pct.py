"""Decode's latent attention's share of busy device time in a model
with TWO attentions a layer: the self time of the operations under the
scope ``mla.attention`` (``models/mla_moe.py _decode_attention``, which
``models/scmoe.py`` imports: the Pallas kernel over each lane's live
latent pages, eight calls a step of four layers) over the seconds in
which any operation ran.
"""
from perf_harness import load_reader

LAYER = "latent attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "mla.attention"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

"""Roofline share of decode's latent attention in a model with TWO
attentions a layer: as ``mla_attn_roofline_pct`` reads it (the scope
``mla.attention``'s device time a step against the larger of the bytes
over the peak bytes/s and the operations over the peak FLOP/s), with
the bytes and operations the configuration's ARCHITECTURE file counts
(``mla_attention_cost``: every live token's 576-wide latent row once
an ATTENTION, eight of them, in the engine's ``kv_dtype``; for each of
the 64 heads a 576-wide score and a 512-wide weighted sum a live token
an attention). The reused kernel's share of its roofline: it reads 640
lanes a token, so it cannot pass 90.
"""
from perf_harness import load_reader

LAYER = "latent attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(run):
    return load_reader("mla_attn_roofline_pct").read(run)

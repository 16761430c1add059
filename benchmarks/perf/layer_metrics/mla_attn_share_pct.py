"""Decode's latent attention's share of busy device time: the self time
of the operations under the scope ``mla.attention`` (``models/
mla_moe.py _slot_decode_step_paged``: the gather of a lane's latent
pages through the page table, the 64 heads' scores against ONE
576-wide key a token, the softmax and the weighted sum of the 512-wide
latents) over the seconds in which any operation ran. Plain XLA, no
Pallas kernel (``attn_kernel_share_pct`` sums every ``pallas_call`` and
is not listed for this model's cells).
"""
from perf_harness import load_reader

LAYER = "latent attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "mla.attention"


def read(run):
    return load_reader("moe_experts_share_pct").share(run, SCOPE)

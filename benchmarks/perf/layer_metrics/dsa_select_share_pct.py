"""The selection's share of busy device time: the self time of the
operations under the scope ``dsa.select`` (``models/dsa_moe.py
pick_top``: the 2,048th largest index score a lane, found by bisection
on the scores' bits in 32 counting passes, and the set above it) over
the seconds in which any operation ran. ``pick_top`` is ONE kernel, so
every layer's selection lands in one row of the trace's table
(``.../dsa.select/pick_top/pallas_call``); the two or three small
operations around it (the padding, the mask's compare) may lie under
the table's ``other``, which is not searched. What a sort of every
lane's scores would cost instead is in PERF.md section 6 (PR 63). A
program without the indexer's scope (the parent) reads nothing; one
with it whose selection has no row among the table's 40 reads 0.0
(under the smallest row kept: it was so while the bisection was 32
small XLA operations a layer, PERF.md section 6).
"""
from perf_harness import load_reader

LAYER = "sparse latent attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "dsa.select"


def read(run):
    share = load_reader("moe_experts_share_pct").share
    if share(run, "dsa.index") is None:
        return None
    return share(run, SCOPE) or 0.0

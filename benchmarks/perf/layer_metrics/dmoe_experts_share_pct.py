"""``moe_experts_share_pct`` in the cell of the sparse-attention expert decoder
(``models/dsa_moe.py``: the same dispatch, ``moe.dropless_experts``,
behind a router with a selection bias; 8 of 256 experts held, 4.0
tokens an expert a step at 128 lanes): a thin twin, because
``tests/perf/test_perf_axk1.py`` pins that reader's list to its own
cell. The counts are the configuration's architecture file's.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(run):
    return load_reader("moe_experts_share_pct").read(run)

"""Roofline share of the sparse attention MECHANISM in a decode step:
the least time the chip could take for what the mechanism needs, the
larger of its bytes over the peak bytes/s and its operations over the
peak FLOP/s, over the device time the three scopes ``dsa.index``,
``dsa.select`` and ``dsa.attention`` took a step together
(``moe_experts_roofline_pct`` says how a scope's time a step is read).
Bytes and operations are the configuration's ARCHITECTURE file's to
count (``dsa_attention_cost``), FROM THE ENGINE'S COUNTERS: every
cached token's 128-wide index key once a layer
(``dsa_tokens_scanned_sum``) and every PICKED token's 576-wide latent
row once a layer (``dsa_tokens_selected_sum``); a 128-wide score an
index head a cached token, a 576-wide score and a 512-wide weighted
sum an attention head a picked token. The same work whatever
implements it: a program that reads the rows it masks, or sorts where
a threshold would do, shows the difference as headroom, and no reading
can pass 100 by a miscounted live length.
"""
import os

from perf_harness import load_architecture, load_reader

LAYER = "sparse latent attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPES = ("dsa.index", "dsa.select", "dsa.attention")
_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    if not (run.get("trace") or {}).get("scopes") or not run.get("peaks"):
        return None
    count = getattr(load_architecture(run["conf"], _HERE),
                    "dsa_attention_cost", None)
    if count is None:
        return None
    kb = {"fp": 2, "int8": 1}[run["conf"]["engine"]["kv_dtype"]]
    cost = count(run["conf"], kb, run.get("stats_delta") or {})
    step = load_reader("moe_experts_roofline_pct").scope_step_seconds
    index, select, attention = (step(run, s) for s in SCOPES)
    if cost is None or not index or not attention:
        return None
    # the selection's rows may all lie under the table's ``other``
    # (``dsa_select_share_pct``): then it adds nothing here
    took = index + (select or 0.0) + attention
    least = max(cost[0] / run["peaks"]["hbm_bytes_per_s"],
                cost[1] / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / took

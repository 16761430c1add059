"""Roofline share of decode's rotary grouped-query attention: the least
time the chip could take for it, the larger of its bytes over the peak
bytes/s and its operations over the peak FLOP/s, over the device time
the scope ``decode_step/hgqa.attention`` took a step
(``moe_experts_roofline_pct`` says how a scope's time a step is read).
Bytes and operations are the configuration's ARCHITECTURE file's to
count (``hgqa_attention_cost``: every live token's 4 x 128 keys and
values once a layer in the engine's ``kv_dtype``; for each of the 20
query heads a 128-wide score and a 128-wide weighted sum a live token):
it counts live tokens whatever implements the scope. Live tokens are
read off the client's stamps at the middle of the traced slice, as
``decode_roofline_pct`` reads them (low, never high).
"""
import os

from perf_harness import load_architecture, load_reader

LAYER = "parallel GQA attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "decode_step/hgqa.attention"
_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    if not (run.get("trace") or {}).get("scopes"):
        return None
    count = getattr(load_architecture(run["conf"], _HERE),
                    "hgqa_attention_cost", None)
    live = load_reader("decode_roofline_pct").live_tokens(run)
    if count is None or live is None:
        return None
    kb = {"fp": 2, "int8": 1}[run["conf"]["engine"]["kv_dtype"]]
    return load_reader("moe_experts_roofline_pct").share_of_least(
        run, SCOPE, count(run["conf"], kb, live))

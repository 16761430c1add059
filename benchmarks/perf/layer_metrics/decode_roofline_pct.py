"""Bandwidth roofline share of a decode step: the fewest bytes a step
can move (every weight it multiplies by once in numerics.compute_dtype,
and the keys and values of the live tokens in the engine's kv_dtype)
over the chip's peak bytes/s, over the step's device time. The bytes
are the configuration's ARCHITECTURE file's to count (``decode_step_
bytes`` in ``architectures/<name>.py``, found beside this reader as
``perf_harness.load_architecture`` finds it; the contract and its rule
are in ``architectures/gpt2.py``'s docstring, and gpt2's are
``kernel_costs.decode_step_bytes``): a model of another shape joins
this share, and is bounded by it, without a second reader. An
architecture file without the function has no such count, and the
reader returns nothing. The bytes are what ANY program with these
numerics moves, never how this one holds its weights: a cast from
float32 masters is overhead and reads as distance from 100. Live tokens
are read off the client's stamps at the middle of the traced slice
(prefilled but undelivered requests left out, a chunk stamped at its
end: low, never high). Bound by bandwidth, not by compute: at 32 lanes
the 1.3B step's 2 x 1.31e9 x 32 FLOP need 0.43 ms of the MXU against
3.2 ms of HBM.
"""
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_mean_ms"


import os

from perf_harness import load_architecture, load_reader

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def live_tokens(run):
    mid = run.get("trace_mid")
    if mid is None:
        return None
    live = 0
    for r in run["rows"]:
        if not r["slices"] or r["slices"][0][0] > mid:
            continue
        if r.get("end") is not None and r["end"] <= mid:
            continue
        live += r["prompt_len"] + sum(n for t, n in r["slices"]
                                      if t <= mid)
    return live


def read(run):
    step_ms = load_reader("decode_step_dev_ms").read(run)
    if not step_ms or not run.get("peaks"):
        return None
    live = live_tokens(run)
    count = getattr(load_architecture(run["conf"], _HERE),
                    "decode_step_bytes", None)
    if live is None or count is None:
        return None
    wb = {"float32": 4, "bfloat16": 2}[
        run["conf"]["numerics"]["compute_dtype"]]
    kb = {"fp": 2, "int8": 1}[run["conf"]["engine"]["kv_dtype"]]
    need = count(run["conf"], wb, kb, live, run.get("stats_delta") or {})
    least_s = need / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (step_ms / 1e3)

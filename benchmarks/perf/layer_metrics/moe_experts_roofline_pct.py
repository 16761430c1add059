"""Roofline share of the routed experts' grouped products in a decode
step: the least time the chip could take for them, the larger of
their bytes over its peak bytes/s and their operations over its peak
FLOP/s, over the device time the scope ``decode_step/moe.experts``
took a step. Bytes and operations are the configuration's
ARCHITECTURE file's to count (``moe_experts_cost``: the experts that at
least one token was routed to, FROM THE ENGINE'S COUNTER
``moe_experts_touched_sum / moe_steps`` and never all held by
assumption, each expert's three matrices once in
``numerics.compute_dtype``; 2 x 3 x h x f operations a token-choice
that landed here, ``moe_tokens_here_sum / moe_steps``). Plain XLA, so
this is the share of the scope, as a kernel's would be of the kernel.
Bound by bandwidth at 128 lanes (5.3 tokens an expert: 0.9 GFLOP
against 88 MB).

The scope's time a STEP: its self time over the slice (the decode
program's alone: the step's scope ``decode_step`` encloses it there
and not in prefill), times the chunk program's device time a step
(``decode_prog_dev_ms``, whole launches), over the chunk program's
device time in the slice: launches cut by the slice's edges count for
the part that is there.
"""
import os

from perf_harness import load_architecture, load_reader
from trace_reduce import scope_seconds

LAYER = "expert layer"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SCOPE = "decode_step/moe.experts"
PROGRAM = "jit_decode_chunk_slots_paged("
_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def scope_step_seconds(run, scope):
    """Device seconds one decode step spends under ``scope``."""
    trace = run.get("trace") or {}
    step_ms = load_reader("decode_prog_dev_ms").read(run)
    seconds = scope_seconds(run, scope)
    in_slice = sum(p["seconds"] for name, p in
                   (trace.get("programs") or {}).items()
                   if name.startswith(PROGRAM))
    if not step_ms or seconds is None or not in_slice:
        return None
    return seconds * (step_ms / 1e3) / in_slice


def share_of_least(run, scope, cost):
    """``cost`` = (bytes, FLOPs) a step -> percent of the roofline."""
    took = scope_step_seconds(run, scope)
    if not took or cost is None or not run.get("peaks"):
        return None
    least = max(cost[0] / run["peaks"]["hbm_bytes_per_s"],
                cost[1] / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / took


def weight_bytes(run):
    return {"float32": 4, "bfloat16": 2}[
        run["conf"]["numerics"]["compute_dtype"]]


def read(run):
    if not (run.get("trace") or {}).get("scopes"):
        return None
    count = getattr(load_architecture(run["conf"], _HERE),
                    "moe_experts_cost", None)
    if count is None:
        return None
    return share_of_least(run, SCOPE, count(
        run["conf"], weight_bytes(run), run.get("stats_delta") or {}))

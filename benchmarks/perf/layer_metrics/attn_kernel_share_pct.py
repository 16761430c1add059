"""The paged-attention kernel's share of busy device time: the self
time of the operations whose recorded name-scope path ends in
``pallas_call`` (``run["trace"]["scopes"]``, summed by
``trace_reduce.scope_seconds``) over the seconds in which any operation
ran. The serving programs hold one Pallas kernel, ``gpt_decode.
_paged_attention_pallas`` (since PR 35 the path ends ``while/body/
closed_call/while/body/closed_call/paged_attention/paged_attention/
pallas_call`` under ``jit(decode_chunk_slots_paged)``); the path does
not depend on the compiler's numbering, as the operation's own name
does (``paged_attention.3``). A second kernel would count too, until
the program gives each a ``jax.named_scope`` of its own. Lower is
better. Since PR 35 the kernel is 4% of busy time in chat-steady and
28% in batch-offline (it was 80 / 89% and the bottleneck before):
fourth in PERF.md section 5's order, behind the prefill that stops
every lane, the cast of the weights once a launch and the host between
launches.
"""
from trace_reduce import scope_seconds

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_mean_ms"

SCOPE = "pallas_call"


def read(run):
    tr = run.get("trace") or {}
    seconds = scope_seconds(run, SCOPE)
    if seconds is None or not tr.get("busy_s"):
        return None
    return 100.0 * seconds / tr["busy_s"]

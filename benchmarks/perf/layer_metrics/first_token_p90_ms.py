"""90th percentile, over requests due in the window, of the first token's
arrival at the client minus the request's due time; a failed, refused or
unfinished request counts as the worst (perf_metrics.open_loop). What an
interactive user waits for. Not end to end: over the 128 requests of a
window it spreads 3-6% between seeds, more than half the widest bound the
contract allows (PERF.md, section 6), so it stands here, unbounded, and
MOVES names the cell's end-to-end time per token as the contract asks.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_mean_ms"


def read(run):
    return run["e2e"].get("ttft_p90_ms")

"""90th percentile, over requests due in the window, of (last - first
token time) / (tokens - 1); a failed, refused or unfinished request
counts as the worst (perf_metrics.open_loop). The slowest tenth of the
streams: those that met a surge of lanes and other requests' prefills.
It was the cell's end-to-end metric ``tpot_p90_ms`` until PR 29: over
the 128 requests of a window it follows the ORDER the seed gives the
arrivals (the step lengthens with the lanes in use, so a bunch of short
gaps lifts it), spreading 4-6% between seeds, more than half the widest
bound the contract allows (PERF.md, sections 2 and 6). It stands here,
unbounded; the mean over the same requests is end to end.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_mean_ms"


def read(run):
    return run["e2e"].get("tpot_p90_ms")

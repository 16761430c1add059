"""Prefix-cache entries evicted per request admitted, between the
counters' two readings: each eviction is a scan on the engine's driver
thread.
"""
LAYER = "KV page manager"
UNIT = "1/req"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run["stats_delta"]
    if d.get("admitted", 0) <= 0 or "prefix_evictions" not in d:
        return None
    return d["prefix_evictions"] / d["admitted"]

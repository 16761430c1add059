"""Device dispatches (chunk programs and prefills) per output token
between the counters' two readings: how well lanes are batched.
"""
LAYER = "admission and batching"
UNIT = "1/token"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run["stats_delta"]
    if d.get("tokens", 0) <= 0:
        return None
    return (d["dispatches"] + d["prefills"]) / d["tokens"]

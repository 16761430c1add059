"""Of the cached tokens a decode step's lanes hold, the share their
attention reads: the engine's counters ``dsa_tokens_selected_sum`` over
``dsa_tokens_scanned_sum`` (summed over the active lanes of every step
on the device; they come out of the chunk program with the tokens).
100 while every lane is at most ``index_topk`` long; at a mean of 2.8k
cached tokens a lane about 70.
"""
LAYER = "sparse latent attention"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    if not d.get("dsa_tokens_scanned_sum"):
        return None
    return 100.0 * d["dsa_tokens_selected_sum"] / d["dsa_tokens_scanned_sum"]

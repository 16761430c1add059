"""Of the lane-steps in the window, the share that ran with more than
``index_topk`` tokens cached, so that the indexer's choice was a real
one: the engine's counters ``dsa_lane_steps_selecting_sum`` over
``dsa_lane_steps_sum`` (counted on the device, a lane a step). In the
others the selection is every cached token and the attention is the
dense one.
"""
LAYER = "sparse latent attention"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    if not d.get("dsa_lane_steps_sum"):
        return None
    return 100.0 * d["dsa_lane_steps_selecting_sum"] / d["dsa_lane_steps_sum"]

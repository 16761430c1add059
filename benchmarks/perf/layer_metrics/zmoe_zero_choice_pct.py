"""Of the choices the router made for live tokens in decode, the share
that fell on identity experts and cost no expert's matrices: the
engine's counters ``moe_zero_choices_sum`` over ``moe_topk`` x
``moe_tokens_sum`` (live rows routed, summed over steps and expert
layers; both come out of the chunk program with the tokens). With
seeded weights a choice falls on each of the 768 scores alike, so
about 256 / 768 = 33%; the published model's average is 4 of 12. The
rest lands on routed experts, 16 of 512 of them here.
"""
LAYER = "expert layer"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    if not d.get("moe_tokens_sum") or "moe_zero_choices_sum" not in d:
        return None
    return 100.0 * d["moe_zero_choices_sum"] \
        / (run["conf"]["moe_topk"] * d["moe_tokens_sum"])

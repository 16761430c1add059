"""Of the routed experts held here, the share that at least one token
was routed to, a decode step a layer: the engine's counters
``moe_experts_touched_sum`` over ``moe_steps`` (decode steps x expert
layers; they come out of the chunk program with the tokens), over the
configuration's ``n_routed_experts``. What a step has to read of the
routed experts follows it: the decode roofline's numerator takes the
same counter. At 128 lanes and 12 of 192 experts (23/24)^128 = 0.4% of
experts go untouched in a step; at a few lanes most do.
"""
LAYER = "expert layer"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def per_step(run, key):
    """A counter's mean over the window's decode steps x expert
    layers; None where the program keeps no such counters."""
    d = run.get("stats_delta") or {}
    if not d.get("moe_steps") or key not in d:
        return None
    return d[key] / d["moe_steps"]


def read(run):
    touched = per_step(run, "moe_experts_touched_sum")
    if touched is None:
        return None
    return 100.0 * touched / run["conf"]["n_routed_experts"]

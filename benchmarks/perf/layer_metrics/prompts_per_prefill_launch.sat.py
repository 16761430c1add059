"""Prompts a prefill launch: ``prefills`` (prompts) over
``prefill_launches`` between the counters' two readings. 1 where every
prompt went alone; towards the engine's ``PREFILL_GROUP`` where the head
requests of a chunk boundary share one launch, one read of the weights
and one blocking read (what the lanes wait for a prompt falls with it).
"""
LAYER = "admission and batching"
UNIT = "prompts/launch"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    if "prefills" not in d or d.get("prefill_launches", 0) <= 0:
        return None
    return d["prefills"] / d["prefill_launches"]

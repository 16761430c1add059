"""What the cache manager gives to per-slot state rather than to pages:
``engine.stats()["state_bytes"]`` (slots x the bytes a sequence keeps
in its slot whatever its length: a recurrent state and a convolution's
tail a linear-attention layer, from the model's ONE cache description)
over the chip's memory in peaks.json. 0 for a model that keeps all in
pages; nothing where the program's ``stats()`` has no such key.
"""
LAYER = "KV page manager"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    held = (run.get("stats_after") or {}).get("state_bytes")
    if held is None or not run.get("peaks"):
        return None
    return 100.0 * held / run["peaks"]["hbm_bytes"]

"""Of the messages the deferred delivery handed to their lanes (slices,
ends, deadline errors: ``deliver_puts``), the share handed over with a
decode or verify program in flight (``deliver_puts_overlapped``): the
consumers those woke ran while the device did, not between two
launches. The rest went with no launch to ride behind (no lane left or
none runnable, the loop going idle), as delivery the device does not
hide. 100 at full slots; what a change to when a slice reaches its
client moves first.
"""
LAYER = "admission and batching"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    d = run.get("stats_delta") or {}
    if "deliver_puts_overlapped" not in d or d.get("deliver_puts", 0) <= 0:
        return None
    return 100.0 * d["deliver_puts_overlapped"] / d["deliver_puts"]

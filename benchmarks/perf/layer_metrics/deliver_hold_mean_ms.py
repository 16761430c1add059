"""A slice's wait between the device and its lane's queue: how long a
message the state pass kept (a slice, an end, a deadline error) had
been held when it was handed over, from the read of the launch that
made it to the start of the hand-over behind the next launch's enqueue
(or at once, where no launch followed): ``deliver_hold_ns_sum`` over
``deliver_puts``. What a client's inter-token time pays for the
deferred delivery, and the number a change to that interval moves.
"""
LAYER = "admission and batching"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    d = run.get("stats_delta") or {}
    if "deliver_hold_ns_sum" not in d or d.get("deliver_puts", 0) <= 0:
        return None
    return d["deliver_hold_ns_sum"] / d["deliver_puts"] / 1e6

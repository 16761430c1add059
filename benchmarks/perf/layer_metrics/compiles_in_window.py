"""Programs XLA built in the replica's process between the counters' two
readings (``engine.stats()["compiles"]``, one ``jax.monitoring``
listener a process). Everything is warmed up before the window, so
anything but 0 is a shape that escaped the warm-up and shows as an
unexplained tail.
"""
LAYER = "model step"
UNIT = "1"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    return (run.get("stats_delta") or {}).get("compiles")

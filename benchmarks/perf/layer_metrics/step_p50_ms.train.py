"""Median time of one training step in the window, by the host's clock
between loss read-backs.
"""
LAYER = "training step"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


from perf_harness import quantile


def read(run):
    return quantile(run.get("step_ms") or [], 0.5)

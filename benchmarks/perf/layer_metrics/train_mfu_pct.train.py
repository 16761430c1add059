"""Model FLOP/s utilization: (6 N + 12 L S d) operations a token
(kernel_costs.train_flops_per_token; recomputation does not count)
times tokens per second, over chips x peak bf16 FLOP/s. Tokens per
second here are a step's tokens over the MEDIAN step time, because the
traced run stops twice to start and stop the profiler.
"""
LAYER = "training step"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


import kernel_costs


def read(run):
    steps = sorted(run.get("step_ms") or [])
    if not steps or not run.get("peaks"):
        return None
    tps = run["tokens_per_step"] / (steps[len(steps) // 2] / 1e3)
    f = kernel_costs.train_flops_per_token(
        run["conf"]["model"], run["conf"]["train"]["seq"])
    return 100.0 * tps * f / (run["chips"]
                              * run["peaks"]["bf16_flops_per_s"])

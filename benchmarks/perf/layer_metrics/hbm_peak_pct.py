"""Peak memory of the fullest chip over the chip's memory in peaks.json:
device.memory_stats() peak_bytes_in_use (arrays: weights, cache, state)
plus peak_bytes_reserved (the pool the runtime keeps for compiled
programs' temporaries), as perf_deployment.device_peak_bytes adds them.
"""
LAYER = "device"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    if not run.get("peaks") or not run.get("memory_peak_bytes"):
        return None
    return 100.0 * run["memory_peak_bytes"] / run["peaks"]["hbm_bytes"]

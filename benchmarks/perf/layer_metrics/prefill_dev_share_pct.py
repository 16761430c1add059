"""The prefill programs' share of the device's busy time in the traced
slice: device seconds of the whole launches that ran while the engine's
driver waited for a prefill (program_names.PREFILL_WAIT). A slice in
which the driver was never seen waiting for one reads nothing, not 0:
at the cells' rates a slice holds tens of prefills, so none seen means
the function was renamed. A prefill holds the device while every running
lane waits, so its share stretches the time per output token.
"""
LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_mean_ms"


from program_names import PREFILL_WAIT


def read(run):
    tr = run.get("trace") or {}
    p = (tr.get("launches_by_host") or {}).get(PREFILL_WAIT)
    if not tr.get("busy_s") or not p:
        return None
    return 100.0 * p["seconds"] / tr["busy_s"]

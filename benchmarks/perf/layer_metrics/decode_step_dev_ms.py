"""Device time of the chunk program per token step: the device seconds of
the program launches that lie wholly inside the traced slice and ran
while the engine's driver waited for a chunk (program_names.CHUNK_WAIT),
over (launches x chunk). The programs carry no names of their own yet
(jit__unknown).
"""
LAYER = "model step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_mean_ms"


from program_names import CHUNK_WAIT


def read(run):
    by = (run.get("trace") or {}).get("launches_by_host") or {}
    group = by.get(CHUNK_WAIT)
    if not group or not group["programs"]:
        return None
    # the chunk program: the one with most device time while the
    # driver waited for a chunk
    p = max(group["programs"].values(), key=lambda v: v["seconds"])
    return 1e3 * p["seconds"] / (p["launches"]
                                 * run["conf"]["engine"]["chunk"])

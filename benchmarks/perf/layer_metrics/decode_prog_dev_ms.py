"""Device time of the chunk program per token step, found by the
program's own name: the launches of ``jit_decode_chunk_slots_paged``
(``models/gpt_decode.py`` names every program after its factory) that
lie wholly inside the traced slice, seconds over (launches x chunk).
The same quantity as ``decode_step_dev_ms`` without asking where the
engine's driver was waiting. Whole launches are kept by the reduction
under ``launches_by_host`` (summed here over every host label); where
that is empty the slice's clipped ``programs`` stand in, which cuts the
launch at each edge short.
"""
LAYER = "model step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_mean_ms"

PROGRAM = "jit_decode_chunk_slots_paged("


def read(run):
    trace = run.get("trace") or {}
    groups = [g["programs"] for g in
              (trace.get("launches_by_host") or {}).values()]
    seconds = launches = 0.0
    for programs in groups or [trace.get("programs") or {}]:
        for name, p in programs.items():
            if name.startswith(PROGRAM):
                seconds += p["seconds"]
                launches += p["launches"]
    if not launches:
        return None
    return 1e3 * seconds / (launches * run["conf"]["engine"]["chunk"])

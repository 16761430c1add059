"""Device time of one prefill launch, found by the program's own name:
the launches of ``jit_prefill_into_slot_paged`` that lie wholly inside
the traced slice, seconds over launches (every bucket together). Beside
``prefill_host_mean_ms`` it says what part of a prefill is the device's.
Whole launches are kept under ``launches_by_host`` (summed over every
host label); where that is empty the slice's clipped ``programs`` stand
in, as for ``decode_prog_dev_ms``.
"""
LAYER = "model step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_mean_ms"

PROGRAM = "jit_prefill_into_slot_paged("


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
    return 1e3 * seconds / launches

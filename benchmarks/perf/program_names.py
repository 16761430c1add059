"""The one place where the benchmark names private pieces of the
program's serving engine.

The compiled programs carry no names of their own yet (every one is
``jit__unknown`` in the trace), so a traced run tells a chunk launch
from a prefill launch by where the engine's driver thread was waiting
when the launch ran: the benchmark samples that thread's innermost
frame inside ``ENGINE_FILE`` while the trace is on. A PR that renames
one of these functions makes the readers that depend on it return
nothing, and ``run.py`` then fails a traced run on the chip instead of
dropping the metric. Named programs and spans (the ``tracing`` issue)
replace this file.
"""
#: The file the engine's driver loop lives in, and the function a thread
#: has to be inside to count as the driver.
ENGINE_FILE = "serve/engine.py"
DRIVER_ENTRY = "_run"

#: Where the driver waits while each kind of program runs (it blocks on
#: every dispatch's result), as the sampler labels a frame.
CHUNK_WAIT = "engine.py:_dispatch_chunk"
PREFILL_WAIT = "engine.py:_prefill_paged"

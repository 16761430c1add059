"""The engine's driver tells its own time (ISSUE 24): named programs,
driver phases in ``engine.stats()``, the request lifecycle counted where
it happens, compiles counted, and spans on the monotonic clock. And a
decode launch accounts for itself (ISSUE 42): counted steps, the
thread's CPU time beside the wall's, the prefills inside a gap. Since
ISSUE 43 a launch has a fourth step, ``flush``: the previous launch's
tokens handed to their lanes behind the enqueue. Since ISSUE 57 a launch
keeps its own record: a ring of per-launch records, stalls classified
as each closes, collections stamped.

CPU, ``nano``: these are counts, names and orderings, never a speed.
"""
import inspect
import threading
import time

import numpy as np
import pytest

from ray_tpu.util import tracing

PHASES = ("idle", "admit", "prefill", "cover", "decode", "deliver",
          "other")
KINDS = {
    "int8": dict(page_size=8, prefix_cache=True, kv_dtype="int8"),
    "paged": dict(page_size=8, prefix_cache=True),
    "spec": dict(page_size=8, spec_decode="ngram", draft_k=2),
}


@pytest.fixture(scope="module")
def nano():
    from ray_tpu.models import gpt

    return gpt.CONFIGS["nano"]


@pytest.fixture(scope="module")
def nano_params(nano):
    import jax

    from ray_tpu.models import gpt

    return gpt.init_params(jax.random.PRNGKey(0), nano)


@pytest.fixture
def make(nano, nano_params):
    from ray_tpu.serve.engine import DecodeEngine

    made = []

    def _make(kind="paged", **kw):
        kw = {**KINDS[kind], **kw}
        kw.setdefault("slots", 2)
        kw.setdefault("chunk", 4)
        kw.setdefault("max_len", 64)
        kw.setdefault("prompt_buckets", (8, 16))
        made.append(DecodeEngine(nano_params, nano, **kw))
        return made[-1]

    yield _make
    for eng in made:
        eng.shutdown()


def _prompt(nano, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, nano.vocab_size, (n,)).astype(np.int32)


def _run_all(eng, nano, n, max_new=9, **kw):
    """``max_new``: a count, or a function of the request's index."""
    new = max_new if callable(max_new) else lambda i: max_new
    threads = [threading.Thread(
        target=lambda i=i: list(eng.stream(
            _prompt(nano, 3 + i % 5, i), new(i), **kw)))
        for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _delta(a, b):
    return {k: b[k] - a[k] for k in b
            if isinstance(b[k], int) and not isinstance(b[k], bool)
            and isinstance(a.get(k), int)}


# ------------------------------------------------------- program names
def _factories():
    from ray_tpu.models import gpt_decode as gd

    out = []
    for name, fn in sorted(vars(gd).items()):
        if not name.startswith("jit_") or not callable(fn):
            continue
        params = inspect.signature(fn).parameters
        for tp in (1, 2) if "tp" in params else (1,):
            out.append(pytest.param(name, tp, id=f"{name}-tp{tp}"))
    return out


@pytest.mark.parametrize("factory,tp", _factories())
def test_program_carries_its_factorys_name(nano, factory, tp):
    """Every ``jit_<x>`` factory returns a program jax calls ``<x>``
    (the XLA module is ``jit_<x>``), on one chip and under shard_map."""
    from ray_tpu.models import gpt_decode as gd

    fn = getattr(gd, factory)
    given = {"cfg": nano, "k": 4, "page_size": 8}
    args = {p: given[p] for p in inspect.signature(fn).parameters
            if p in given}
    if tp > 1:
        args["tp"] = tp
    assert fn(**args).__name__ == factory[len("jit_"):]


@pytest.mark.parametrize("tp", [1, 2])
def test_lowered_module_is_named(make, tp):
    """What a profile shows: the module name of the lowered program."""
    eng = make("paged", tp=tp)
    active = np.zeros((eng.slots,), bool)
    text = eng._step.lower(eng._params_dev, eng._cache, eng._token,
                           eng._rngs, active, eng._pt).as_text()
    assert "module @jit_decode_chunk_slots_paged" in text
    assert eng._prefill.__name__ == "prefill_into_slot_paged"


# --------------------------------------- what the benchmark reads by name
def _benchmark_file(rel):
    """A file under ``benchmarks/perf`` as a module, so each case below
    holds the engine to the names its consumer itself holds."""
    import importlib.util
    import os
    import sys

    perf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "perf")
    sys.path.insert(0, perf)        # layer metrics import trace_reduce
    try:
        spec = importlib.util.spec_from_file_location(
            "_bench_" + os.path.basename(rel).replace(".", "_"),
            os.path.join(perf, rel))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(perf)


#: ``engine.stats()`` keys that ``benchmarks/perf`` (the cell, its layer
#: metrics, the poller) and ``serve/_controller.py`` index by name.
_STATS_READ = (
    "abandoned", "active_slots", "admission_wait_ns_sum", "admitted",
    "avg_occupancy", "compiles", "completed", "decode_gap_ns_sum",
    "dispatches", "driver_ns_decode", "driver_ns_idle",
    "driver_ns_total", "driver_restarts", "expired", "n_pages",
    "paged", "pages_used", "preempted", "prefill_ns_sum", "prefills",
    "prefix_evictions", "queued", "resumed", "tokens",
    # the launch's own account (ISSUE 42): layer_metrics/lane_*_stall_pct,
    # launch_*_ms, deliver_offcpu_pct, host_offcpu_pct, prefill_enqueue_ms,
    # prefill_wait_ms
    "decode_gap_prefill_ns_sum", "driver_cpu_ns_admit",
    "driver_cpu_ns_cover", "driver_cpu_ns_deliver", "driver_cpu_ns_other",
    "driver_ns_admit", "driver_ns_cover", "driver_ns_decode_enqueue",
    "driver_ns_decode_read", "driver_ns_deliver", "driver_ns_other",
    "driver_ns_prefill_dispatch", "driver_ns_prefill_key",
    "driver_ns_prefill_read",
    # what PRs 43 and 54 counted (ISSUE 57 lists their readers):
    # layer_metrics/deliver_overlap_pct, prompts_per_prefill_launch
    "deliver_puts", "deliver_puts_overlapped", "prefill_launches",
    # the launch's own record (ISSUE 57): layer_metrics/launch_stall_s,
    # gc_pause_ms_per_s, deliver_hold_mean_ms
    "launch_stall_ns_sum", "gc_pause_ns_sum", "deliver_hold_ns_sum")


@pytest.mark.parametrize("consumer", ["program_names", "program_readers",
                                      "stats", "architecture"])
def test_names_the_benchmark_reads(make, nano, consumer):
    """``benchmarks/perf`` finds pieces of ``ray_tpu`` by name; a
    rename shows here, on the CPU, and not as ``output_malformed`` on
    the chip. One case per consumer."""
    from ray_tpu.models import gpt_decode as gd
    from ray_tpu.serve.engine import DecodeEngine

    if consumer == "program_names":
        # the sampler labels the driver thread by frame: the driver's
        # entry, and the two functions it blocks in on a result
        names = _benchmark_file("program_names.py")
        # (unwrap: under the suite rtsan wraps the annotated methods)
        entry = inspect.unwrap(getattr(DecodeEngine, names.DRIVER_ENTRY))
        assert entry.__code__.co_filename.endswith(names.ENGINE_FILE)
        for wait, read in ((names.CHUNK_WAIT, "np.asarray(toks)"),
                           (names.PREFILL_WAIT, "np.asarray(tok)")):
            file, func = wait.split(":")
            assert names.ENGINE_FILE.endswith(file)
            fn = inspect.unwrap(getattr(DecodeEngine, func))
            assert fn.__code__.co_filename.endswith(names.ENGINE_FILE)
            assert read in inspect.getsource(fn), (func, read)
    elif consumer == "program_readers":
        # decode_prog_dev_ms finds the chunk program by the module's
        # name, attn_kernel_share_pct the kernel by its scope under it
        import jax

        eng = make("paged", attn_kernel="pallas")
        args = (eng._params_dev, eng._cache, eng._token, eng._rngs,
                np.zeros((eng.slots,), bool), eng._pt)
        text = eng._step.lower(*args).as_text()
        program = _benchmark_file(
            "layer_metrics/decode_prog_dev_ms.py").PROGRAM
        assert program == "jit_decode_chunk_slots_paged("
        assert f"module @{program[:-1]} " in text
        assert eng._step.__name__ == "decode_chunk_slots_paged"
        assert eng._prefill.__name__ == "prefill_into_slot_paged"
        scope = _benchmark_file(
            "layer_metrics/attn_kernel_share_pct.py").SCOPE
        assert scope == "pallas_call"
        assert scope in str(jax.make_jaxpr(eng._step)(*args))
    elif consumer == "stats":
        eng = make("paged")
        list(eng.stream(_prompt(nano, 5), 6))
        st = eng.stats()
        assert not [k for k in _STATS_READ if k not in st]
        assert st["paged"] is True
        assert all(f"driver_ns_{p}" in st for p in PHASES + ("total",))
    else:
        # architectures/gpt2.py: the constructor's keywords, the
        # attributes it reads back, and the gpt_decode names it calls
        eng = make("paged", paged=True, page_size=8, n_pages=16,
                   prefix_cache=True, attn_kernel="gather",
                   kv_dtype="fp")
        for attr in ("params", "kv_dtype", "attn_kernel", "page_size",
                     "prompt_buckets"):
            assert hasattr(eng, attr), attr
        assert eng.page_size == 8 and eng.prompt_buckets == [8, 16]
        for name in ("init_paged_cache", "jit_prefill_into_slot_paged",
                     "_slot_decode_step_paged"):
            assert callable(getattr(gd, name)), name
        assert gd.PT_SENTINEL == 2 ** 30
        want = ["params", "cache", "token", "active", "pt", "cfg",
                "page_size", "kv_dtype", "attn_kernel"]
        assert list(inspect.signature(
            gd._slot_decode_step_paged).parameters)[:len(want)] == want
        assert list(inspect.signature(
            gd.init_paged_cache).parameters) == [
                "cfg", "slots", "n_pages", "page_size", "kv_dtype", "tp"]


def test_train_programs_are_named(nano):
    import jax
    from jax.sharding import Mesh

    from ray_tpu.models import gpt

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("dp", "fsdp"))
    init, step, _s, _b = gpt.make_train_step(nano, mesh)
    assert (init.__name__, step.__name__) == ("train_init", "train_step")


# ------------------------------------------------------- driver phases
@pytest.mark.parametrize("kind", list(KINDS))
def test_phases_sum_to_total(make, nano, kind):
    eng = make(kind)
    a = eng.stats()
    _run_all(eng, nano, 20)
    time.sleep(0.12)          # let the last iteration close its phase
    d = _delta(a, eng.stats())
    assert d["admitted"] == 20 and d["prefills"] == 20
    parts = sum(d[f"driver_ns_{p}"] for p in PHASES)
    assert abs(parts - d["driver_ns_total"]) <= 0.01 * d["driver_ns_total"]
    for p in ("admit", "prefill", "decode", "deliver"):
        assert d[f"driver_ns_{p}"] > 0, p
    # every prefill was timed where it ran
    assert d["prefill_ns_sum"] == d["driver_ns_prefill"]
    assert d["prefill_tokens_sum"] == sum(3 + i % 5 for i in range(20))
    assert d["admission_wait_ns_sum"] > 0


def test_idle_engine_is_idle(make):
    eng = make("paged")
    time.sleep(0.1)
    a = eng.stats()
    time.sleep(0.6)
    d = _delta(a, eng.stats())
    assert d["driver_ns_total"] > 0.4e9
    assert d["driver_ns_idle"] > 0.9 * d["driver_ns_total"]
    for k in ("admission_wait_ns_sum", "prefill_ns_sum",
              "prefill_tokens_sum", "decode_gap_ns_sum", "compiles",
              "compile_ns", "driver_ns_prefill", "driver_ns_decode"):
        assert d[k] == 0, k


@pytest.mark.parametrize("kind", ["int8", "paged"])
def test_compiles_stand_still_after_first_use(make, nano, kind):
    """After a bucket's first requests, one alone and two that share a
    chunk boundary's launch, nothing compiles, however many requests
    follow; the next bucket's first use builds one program."""
    # a pool shape no other test of this process has compiled
    eng = make(kind, slots=3, max_len=40, auto_start=False)
    lanes = [eng.submit(_prompt(nano, 5, i), 6) for i in range(3)]
    eng.start()                   # a group of two, then one alone
    from ray_tpu.serve.batching import _EngineStream

    for ln in lanes:
        list(_EngineStream(ln))
    a = eng.stats()
    assert a["compiles"] > 0 and a["compile_ns"] > 0
    assert (a["prefills"], a["prefill_launches"]) == (3, 2)
    _run_all(eng, nano, 50, max_new=7)            # prompts of 3-7: bucket 8
    b = eng.stats()
    assert b["compiles"] == a["compiles"]
    assert b["compile_ns"] == a["compile_ns"]
    list(eng.stream(_prompt(nano, 12), 6))        # bucket 16, first use
    assert eng.stats()["compiles"] == b["compiles"] + 1
    list(eng.stream(_prompt(nano, 13), 6))
    assert eng.stats()["compiles"] == b["compiles"] + 1


# ---------------------------------------------------------- decode gap
@pytest.mark.parametrize("kind", ["int8", "paged"])
def test_decode_gap_is_what_a_prefill_costs_running_lanes(make, nano,
                                                          kind):
    eng = make(kind)
    # ---- a lone lane: between its decode steps there is only the
    # driver's own bookkeeping, and its own prefill came before any
    a = eng.stats()
    list(eng.stream(_prompt(nano, 5), 40))
    time.sleep(0.12)
    d = _delta(a, eng.stats())
    assert d["dispatches"] >= 9
    host = sum(d[f"driver_ns_{p}"]
               for p in ("admit", "cover", "deliver", "other"))
    assert d["decode_gap_ns_sum"] <= host
    assert d["decode_gap_ns_sum"] < d["driver_ns_decode"]

    # ---- a request admitted beside a running lane: the lane waits for
    # the whole prefill, which the gap therefore contains
    eng.inject_fault("driver_slow", wedge_s=0.01)   # keep lane A running
    lane = eng.stream(_prompt(nano, 5, 1), 40)
    next(lane)                                      # A is decoding
    a = eng.stats()
    other = threading.Thread(
        target=lambda: list(eng.stream(_prompt(nano, 6, 2), 4)))
    other.start()
    list(lane)
    other.join()
    time.sleep(0.12)
    b = eng.stats()
    d = _delta(a, b)
    assert b["peak_active"] == 2 and d["prefills"] == 1
    assert d["prefill_ns_sum"] > 0
    assert d["decode_gap_ns_sum"] >= d["prefill_ns_sum"]


# --------------------------------------------------------------- spans
@pytest.fixture
def spans_on():
    tracing.drain()
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()
        tracing.drain()


@pytest.mark.parametrize("kind", list(KINDS))
def test_traced_request_and_driver_spans(make, nano, spans_on, kind):
    eng = make(kind)
    time.sleep(0.1)
    a = eng.stats()
    tracing.drain()
    ctx = {"trace_id": "ab" * 16, "span_id": "cd" * 8}
    lanes = [eng.submit(_prompt(nano, 5, i), 9,
                        trace_ctx=ctx if i == 0 else None)
             for i in range(3)]
    from ray_tpu.serve.batching import _EngineStream

    for ln in lanes:
        list(_EngineStream(ln))
    time.sleep(0.12)
    d = _delta(a, eng.stats())
    spans = tracing.local_spans()

    # the traced request: admission -> prefill -> decode chunks, all
    # children of the caller's span, in order on the monotonic clock
    mine = sorted((s for s in spans if s["trace_id"] == ctx["trace_id"]),
                  key=lambda s: s["mono_ns"][0])
    assert all(s["parent_id"] == ctx["span_id"] for s in mine)
    names = [s["name"] for s in mine]
    assert names[:2] == ["engine.admission", "engine.prefill"]
    assert names[2:] and set(names[2:]) == {"decode.chunk"}
    for s, nxt in zip(mine, mine[1:]):
        assert s["mono_ns"][0] <= s["mono_ns"][1] <= nxt["mono_ns"][0]
    for s in mine:      # wall-clock stamps derive from the same pair
        assert abs((s["end"] - s["start"])
                   - (s["mono_ns"][1] - s["mono_ns"][0]) / 1e9) < 1e-6
    pre = mine[1]["attrs"]
    assert pre["bucket"] == 8 and pre["hist_len"] == 0

    # the driver's own trace: one trace id for this driver run, one
    # engine.decode span per dispatch whatever the number of slots
    drv = [s for s in spans if s["kind"] == "driver"]
    assert len({s["trace_id"] for s in drv}) == 1
    assert ctx["trace_id"] not in {s["trace_id"] for s in drv}
    decodes = [s for s in drv if s["name"] == "engine.decode"]
    assert len(decodes) == d["dispatches"] > 0
    assert {s["attrs"]["slots_active"] for s in decodes} <= {1, 2}
    by_id = {s["span_id"]: s for s in drv}
    for s in decodes:   # phases nest under the loop iteration's span
        assert by_id[s["parent_id"]]["name"] == "engine.other"
        assert s["attrs"]["deployment"] == eng.deployment
    # one engine.prefill phase a LAUNCH, which holds one prompt or the
    # two that one chunk boundary admitted
    pre = [s for s in drv if s["name"] == "engine.prefill"]
    assert len(pre) == d["prefill_launches"] <= d["prefills"] == 3
    assert sum(s["attrs"]["group"] for s in pre) == 3
    # an iteration that found nothing to do leaves no spans behind
    tracing.drain()
    time.sleep(0.3)
    assert tracing.local_spans() == []
    assert eng.stats()["driver_ns_idle"] > a["driver_ns_idle"]


def test_tracing_off_records_nothing(make, nano, monkeypatch):
    """With tracing off a phase allocates no span: recording one would
    blow up the driver."""
    def bomb(*a, **kw):
        raise AssertionError("a span was recorded with tracing off")

    assert not tracing.enabled()
    eng = make("paged")
    time.sleep(0.1)         # the driver has made its clock's trace id
    monkeypatch.setattr(tracing, "_record", bomb)
    monkeypatch.setattr(tracing, "_new_id", bomb)
    _run_all(eng, nano, 6)
    st = eng.stats()
    assert st["driver_restarts"] == 0 and st["completed"] == 6
    assert st["driver_ns_decode"] > 0
    assert tracing.local_spans() == []


def test_phase_clock_self_times():
    """The clock alone: nested phases are charged their self time."""
    table = {}
    clock = tracing.PhaseClock("t", table)
    with clock.phase("other"):
        time.sleep(0.01)
        with clock.phase("admit") as ph:
            time.sleep(0.02)
            with clock.phase("prefill", bucket=8):
                time.sleep(0.03)
            with clock.step("register"):
                pass
    assert ph.t1 - ph.t0 >= 0.05e9
    assert table["prefill"] >= 0.03e9 and table["admit"] >= 0.02e9
    assert table["admit"] < 0.03e9 + 0.02e9     # without its child
    assert table["total"] == (table["other"] + table["admit"]
                              + table["prefill"])


# ------------------------------------- a launch accounts for itself (42)
STEPS = ("prefill_key", "prefill_dispatch", "prefill_read",
         "decode_enqueue", "decode_flush", "decode_wait", "decode_read")
DECODE_STEPS = ("enqueue", "flush", "wait", "read")
#: the phases in which the driver holds no dispatch: their CPU time is
#: kept beside their wall time
HOST_PHASES = ("admit", "cover", "deliver", "other")


def test_a_step_is_counted_beside_its_phase():
    """The clock alone: a step adds to ``<phase>.<name>`` and is a PART
    of its phase's self time, never taken from it nor from ``total``."""
    table = {}
    clock = tracing.PhaseClock("t", table)
    with clock.phase("other"):
        with clock.phase("decode") as ph:
            with clock.step("enqueue"):
                time.sleep(0.01)
            with clock.step("wait"):
                time.sleep(0.02)
            with clock.step("wait"):            # a name adds up
                time.sleep(0.01)
    assert set(table) == {"other", "decode", "decode.enqueue",
                          "decode.wait", "total"}
    assert table["decode.enqueue"] >= 0.01e9
    assert table["decode.wait"] >= 0.03e9
    parts = table["decode.enqueue"] + table["decode.wait"]
    assert parts <= table["decode"] == ph.t1 - ph.t0
    assert table["total"] == table["other"] + table["decode"]


@pytest.mark.parametrize("fault", ["phase_in_step", "step_in_step",
                                   "step_outside_a_phase"])
def test_the_clock_refuses_what_would_be_counted_twice(fault):
    clock = tracing.PhaseClock("t", {})
    with pytest.raises(AssertionError, match="step"):
        if fault == "step_outside_a_phase":
            clock.step("lookup")
        with clock.phase("admit"):
            with clock.step("lookup"):
                if fault == "phase_in_step":
                    with clock.phase("prefill"):
                        pass
                else:
                    with clock.step("alloc"):
                        pass
    # the refusal left the clock usable: nothing open, nothing stuck
    with clock.phase("admit"):
        with clock.step("lookup"):
            pass
    assert clock.table["admit.lookup"] >= 0


def _burn(cpu_seconds):
    """Keep this thread ON a processor for ``cpu_seconds`` of its own
    CPU clock, however long the machine takes to grant them."""
    t = time.thread_time() + cpu_seconds
    while time.thread_time() < t:
        pass


@pytest.mark.parametrize("inner", ["sleeps", "burns"])
def test_cpu_self_time_beside_wall_self_time(inner):
    """A phase's CPU time is kept the way its wall time is: its own,
    without the phases opened inside it, where the table is seeded
    with ``cpu.<phase>``. Across a ``time.sleep`` the thread is off the
    processor (near 0); burning it is on, for at most the wall time
    (the two clocks apart by their granularity)."""
    table = {"cpu.other": 0, "cpu.deliver": 0}
    clock = tracing.PhaseClock("t", table)
    with clock.phase("other"):
        _burn(0.03)
        with clock.phase("deliver"):
            time.sleep(0.05) if inner == "sleeps" else _burn(0.05)
        with clock.phase("decode"):     # not seeded: not kept, and its
            _burn(0.01)                 # time is not its parent's
    assert set(table) == {"other", "deliver", "decode", "total",
                          "cpu.other", "cpu.deliver"}
    cpu = {p: table[f"cpu.{p}"] for p in ("other", "deliver")}
    assert table["deliver"] >= 0.049e9 and table["other"] >= 0.029e9
    if inner == "sleeps":
        assert cpu["deliver"] <= 0.01e9 < table["deliver"]
    else:
        assert 0.049e9 <= cpu["deliver"] <= table["deliver"] + 2e6
    # ``other`` is charged its own 30 ms, not the child's 50
    assert 0.029e9 <= cpu["other"] <= table["other"] + 2e6
    assert cpu["other"] < 0.045e9


@pytest.mark.parametrize("kind", list(KINDS))
def test_step_and_cpu_keys_are_there_from_construction(make, kind):
    st = make(kind).stats()
    for step in STEPS:
        assert st[f"driver_ns_{step}"] == 0, step
    assert sorted(k for k in st if k.startswith("driver_cpu_ns_")) == \
        sorted(f"driver_cpu_ns_{p}" for p in HOST_PHASES)
    for p in ("deliver", "cover"):
        assert st[f"driver_cpu_ns_{p}"] == 0, p
    assert st["decode_gap_prefill_ns_sum"] == 0
    assert not [k for k in st if k.startswith("driver_ns_cpu")]


def _run_staggered(eng, nano, n):
    """``_run_all`` with answers of three lengths, so that the lanes do
    not all end in one dispatch (a gap is counted while a lane stays
    occupied)."""
    _run_all(eng, nano, n, max_new=lambda i: 9 + 4 * (i % 3))


#: launches long enough at ``nano`` on the CPU that the stamps between
#: a launch's steps (30-40 us of a cold interpreter after each wait)
#: stay a small part of it: 1-2% where 5% is allowed
LONGER = {"int8": dict(chunk=8), "paged": dict(chunk=8),
          "spec": dict(draft_k=6, slots=4)}


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_decode_launch_is_its_four_steps(make, nano, kind):
    """enqueue + flush + wait + read are the decode phase but for the stamps
    between them: never more, and within 5% of it. What else lands
    between two stamps is the machine's (another worker of the suite
    given the thread's processor) and only ever lowers the sum, so the
    5% is asked of the best of three windows and 20% of each. A
    prefill's three steps lie inside its phase (it hands nothing over:
    a launch's tokens ride the next DECODE enqueue); every host phase's CPU
    time is at most its wall time. (That the seven phases still sum to the
    total with the steps in the same table: ``test_phases_sum_to_
    total``, which sums the phases alone.)"""
    eng = make(kind, **LONGER[kind])
    list(eng.stream(_prompt(nano, 5), 6))       # compiled
    time.sleep(0.12)          # the compiling iteration closed its phase
    ratios = []
    for _ in range(3):
        a = eng.stats()
        _run_staggered(eng, nano, 12)
        time.sleep(0.12)
        d = _delta(a, eng.stats())
        assert d["dispatches"] >= 6
        assert all(d[f"driver_ns_decode_{s}"] > 0 for s in DECODE_STEPS)
        parts = sum(d[f"driver_ns_decode_{s}"] for s in DECODE_STEPS)
        assert parts <= d["driver_ns_decode"]
        ratios.append(parts / d["driver_ns_decode"])
        pre = sum(d[f"driver_ns_prefill_{s}"]
                  for s in ("key", "dispatch", "read"))
        assert 0 < pre <= d["driver_ns_prefill"]
        for p in HOST_PHASES:   # 5 ms: the clocks' granularity, summed
            assert d[f"driver_cpu_ns_{p}"] <= d[f"driver_ns_{p}"] + 5e6, p
    assert max(ratios) >= 0.95 and min(ratios) >= 0.8, ratios


def _shares(d):
    """The three-way split of lane time, by the benchmark's readers for
    the two stalls (``run["stats_delta"]`` is such a difference); the
    device's part is the flush that rides behind the enqueue and the
    wait."""
    run = {"stats_delta": d}
    lane = d["decode_gap_ns_sum"] + d["driver_ns_decode"]
    return (_benchmark_file(
                "layer_metrics/lane_prefill_stall_pct.sat.py").read(run),
            _benchmark_file(
                "layer_metrics/lane_host_stall_pct.sat.py").read(run),
            100.0 * (d["driver_ns_decode_flush"]
                     + d["driver_ns_decode_wait"]) / lane)


@pytest.mark.parametrize("kind", list(KINDS))
def test_lane_time_splits_three_ways(make, nano, kind):
    """Another request's prefill, the host, the wait for the device:
    the three sum to lane time but for the stamps between a launch's
    steps, which are counted here: 0.3% of a lane whose loop takes
    10 ms and more (the throttle: the host's) on a quiet machine, and
    under 5% where the suite's other workers take the thread's
    processor between two stamps."""
    eng = make(kind)
    list(eng.stream(_prompt(nano, 5), 6))
    eng.inject_fault("driver_slow", wedge_s=0.01)
    a = eng.stats()
    _run_staggered(eng, nano, 12)
    time.sleep(0.12)
    d = _delta(a, eng.stats())
    prefill, host, device = _shares(d)
    assert prefill > 0 and host > device > 0
    between = d["driver_ns_decode"] - sum(
        d[f"driver_ns_decode_{s}"] for s in DECODE_STEPS)
    lane = d["decode_gap_ns_sum"] + d["driver_ns_decode"]
    assert prefill + host + device + 100.0 * between / lane == \
        pytest.approx(100, abs=1e-6)
    assert 95 <= prefill + host + device <= 100


@pytest.mark.parametrize("kind", ["int8", "paged"])
def test_the_gap_knows_its_prefills(make, nano, kind):
    """The twin of ``test_decode_gap_is_what_a_prefill_costs_running_
    lanes`` for the part of the gap that was a prefill."""
    eng = make(kind)
    # ---- a lone lane: its own prefill came before any decode
    a = eng.stats()
    list(eng.stream(_prompt(nano, 5), 40))
    time.sleep(0.12)
    d = _delta(a, eng.stats())
    assert d["dispatches"] >= 9 and d["decode_gap_ns_sum"] > 0
    assert d["decode_gap_prefill_ns_sum"] == 0
    assert _shares(d)[0] == 0

    # ---- a request admitted beside a running lane: its whole prefill
    # lies in the lane's gap, and is told from the rest of it
    eng.inject_fault("driver_slow", wedge_s=0.01)   # keep lane A running
    lane = eng.stream(_prompt(nano, 5, 1), 40)
    next(lane)                                      # A is decoding
    a = eng.stats()
    other = threading.Thread(
        target=lambda: list(eng.stream(_prompt(nano, 6, 2), 4)))
    other.start()
    list(lane)
    other.join()
    time.sleep(0.12)
    d = _delta(a, eng.stats())
    assert d["prefills"] == 1 and d["prefill_ns_sum"] > 0
    assert d["decode_gap_prefill_ns_sum"] >= d["prefill_ns_sum"]
    assert d["decode_gap_prefill_ns_sum"] <= d["decode_gap_ns_sum"]
    # the throttle (10 ms a loop) is the host's, not the prefill's
    assert d["decode_gap_ns_sum"] - d["decode_gap_prefill_ns_sum"] \
        >= 0.01e9


# ------------------------- a launch's tokens ride the next enqueue (43)
def _taken(lane, n):
    """``n`` slices of a lane's stream, as a list of tokens."""
    out = []
    for _ in range(n):
        out += [int(t) for t in next(lane)]
    return out


@pytest.mark.parametrize("kind", list(KINDS))
def test_at_full_slots_every_put_rides_behind_a_launch(make, nano, kind):
    """While every slot is taken each launch is followed by another, and
    so whatever its state pass kept is handed over with a program in
    flight: ``deliver_puts_overlapped == deliver_puts`` over such
    launches, and the step ``flush`` is where the time went."""
    eng = make(kind)
    list(eng.stream(_prompt(nano, 5), 6))           # compiled
    eng.inject_fault("driver_slow", wedge_s=0.01)
    lanes = [eng.stream(_prompt(nano, 5, i), 40) for i in (1, 2)]
    for lane in lanes:
        _taken(lane, 2)                             # both are decoding
    a = eng.stats()
    for lane in lanes:
        _taken(lane, 3)
    b = eng.stats()                                 # and still are
    assert b["active_slots"] == 2 == eng.slots
    d = _delta(a, b)
    assert d["dispatches"] >= 2
    assert d["deliver_puts"] == d["deliver_puts_overlapped"] >= 4
    assert d["driver_ns_decode_flush"] > 0
    assert d["completed"] == 0
    for lane in lanes:
        list(lane)


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_driver_death_behind_the_state_pass_loses_no_token(make, nano,
                                                             kind):
    """The driver dies at the loop's top (the throttle holds it there):
    BETWEEN a state pass and the flush of what it kept. The kept slices
    reach the lane before the retryable error, so what the client
    counted is what the engine counted as delivered; the resubmit
    resumes there: no token lost, none twice."""
    from ray_tpu.serve.engine import EngineRestartError

    prompt = _prompt(nano, 5, 7)
    eng = make(kind)
    ref = [int(t) for c in eng.stream(prompt, 30) for t in c]
    eng.inject_fault("driver_slow", wedge_s=0.01)
    a = eng.stats()
    eng.inject_fault("driver_die", at_tokens=a["tokens"] + 9)
    got = []
    with pytest.raises(EngineRestartError):
        for c in eng.stream(prompt, 30):
            got += [int(t) for t in c]
    d = _delta(a, eng.stats())
    assert 9 <= len(got) < 30 and got == ref[:len(got)]
    assert d["tokens"] == len(got)          # counted = handed over
    assert d["deliver_puts"] > d["deliver_puts_overlapped"] > 0
    assert not eng._kept
    deadline = time.monotonic() + 10.0
    while eng.stats()["driver_restarts"] == 0:
        assert eng.supervise()
        assert time.monotonic() < deadline, "supervisor never restarted"
        time.sleep(0.05)
    for c in eng.stream(prompt, 30, resume_from=len(got)):
        got += [int(t) for t in c]
    assert got == ref


# --------------------------------- a launch keeps its own record (57)
def _quiet(eng):
    """The engine at rest (every phase closed, the last record in the
    ring), its counters and the stamp they were taken at."""
    time.sleep(0.12)
    return eng.stats(), time.monotonic_ns()


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_launch_keeps_its_own_record(make, nano, kind):
    """One record a decode or verify launch, every field an int; its
    four steps lie inside its phase and the gap's parts inside the gap;
    over any run the records sum to the table's counters over the same
    launches."""
    from ray_tpu.serve.engine import LAUNCH_FIELDS

    eng = make(kind)
    list(eng.stream(_prompt(nano, 5), 6))       # compiled
    a, since = _quiet(eng)
    _run_staggered(eng, nano, 12)
    b, until = _quiet(eng)
    d = _delta(a, b)
    log = eng.launch_log(since)
    assert len(log) == d["dispatches"] >= 6
    assert len(eng.launch_log()) > len(log)     # the compiling launch
    for r in log:
        assert set(r) == set(LAUNCH_FIELDS) | {"period"}
        assert all(isinstance(r[f], int) for f in LAUNCH_FIELDS
                   if f != "kind")
        assert r["kind"] == ("verify" if kind == "spec" else "chunk")
        assert 0 < r["enqueue"] + r["flush"] + r["wait"] + r["read"] \
            <= r["phase"]
        assert 0 <= r["gap_prefill"] + r["gap_idle"] + r["gap_deliver"] \
            <= r["gap"]
        assert r["period"] == r["gap"] + r["phase"]
        assert 1 <= r["lanes"] <= eng.slots
        assert 0 < r["deliver"] < r["wall"] and 0 < r["cpu_proc"]
        assert r["gc"] >= 0 and r["hold"] >= 0
        if not r["gap"]:        # nobody waited: the launch alone
            assert r["wall"] <= r["phase"] + r["deliver"] + 1_000_000
    assert [r["t0"] for r in log] == sorted(r["t0"] for r in log)
    total = {f: sum(r[f] for r in log) for f in LAUNCH_FIELDS
             if f != "kind"}
    assert total["gap"] == d["decode_gap_ns_sum"] > 0
    assert total["gap_prefill"] == d["decode_gap_prefill_ns_sum"] > 0
    assert total["gap_idle"] == 0       # a lane waited: never idle
    assert total["phase"] == d["driver_ns_decode"]
    for step in DECODE_STEPS:
        assert total[step] == d[f"driver_ns_decode_{step}"], step
    # the state passes, less what was handed over with no launch to
    # ride behind (a deliver phase of its own, inside a gap or not)
    assert 0 < total["deliver"] <= d["driver_ns_deliver"]
    assert total["prompts"] == d["prefills"] == 12
    assert total["prefill_launches"] == d["prefill_launches"]
    assert sum(r["period"] for r in log) == \
        d["decode_gap_ns_sum"] + d["driver_ns_decode"]
    # the walls (what ``gc`` and ``cpu_proc`` cover) hold the launch and
    # its state pass, and no two overlap
    assert all(r["phase"] + r["deliver"] <= r["wall"] for r in log)
    assert sum(r["wall"] for r in log) <= until - since


def test_the_ring_is_bounded_and_the_log_filters(make, nano, monkeypatch):
    from ray_tpu.serve import engine as E

    monkeypatch.setattr(E, "LAUNCH_RING", 8)
    eng = make("paged")
    list(eng.stream(_prompt(nano, 5), 56))      # 14 launches
    time.sleep(0.12)
    assert eng.stats()["dispatches"] >= 14
    log = eng.launch_log()
    assert len(log) == 8
    assert eng.launch_log(log[2]["t0"]) == log[3:]
    assert eng.launch_log(log[-1]["t0"]) == []
    assert "launches" not in eng.stats() and not [
        v for v in eng.stats().values() if isinstance(v, list)]


def test_the_log_is_read_while_the_driver_appends(make, nano, monkeypatch):
    """Readers on other threads (a health pass, a builder's script)
    copy the ring while the driver appends to it: every copy is whole
    records in order, none raises."""
    import sys

    from ray_tpu.serve import engine as E

    monkeypatch.setattr(E, "LAUNCH_RING", 64)   # a ring that turns over
    eng = make("paged")
    list(eng.stream(_prompt(nano, 5), 6))
    stop, seen, errors = threading.Event(), [0], []

    def reader():
        while not stop.is_set():
            try:
                log = eng.launch_log()
                assert len(log) <= 64
                assert [r["t0"] for r in log] == sorted(
                    r["t0"] for r in log)
                assert all(r["period"] == r["gap"] + r["phase"]
                           for r in log)
                seen[0] += 1
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(e)
                return

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=reader) for _ in range(4)]
        for t in readers:
            t.start()
        _run_all(eng, nano, 16, max_new=40)
        stop.set()
        for t in readers:
            t.join(10.0)
    finally:
        sys.setswitchinterval(interval)
        stop.set()
    assert not errors and seen[0] > 0
    assert not [t for t in readers if t.is_alive()]
    st = eng.stats()
    assert st["driver_restarts"] == 0 and st["completed"] == 17
    assert st["dispatches"] > 64 == len(eng.launch_log())


def _long_lane(eng, nano, seed, on_launch):
    """Three lone lanes of 100 tokens, one after the other (75 and more
    launches; a lane's first has no gap); the launch's program wrapped
    to call ``on_launch(n)`` first, ``n`` counted from the wrap.
    Returns the counters' delta and the records."""
    name = "_verify" if eng._drafter is not None else "_step"
    inner, n = getattr(eng, name), [0]

    def program(*args):
        n[0] += 1
        on_launch(n[0])
        return inner(*args)

    a, since = _quiet(eng)
    setattr(eng, name, program)
    try:
        for i in range(3):
            list(eng.stream(_prompt(nano, 5, 3 * seed + i), 100))
    finally:
        setattr(eng, name, inner)
    b, _ = _quiet(eng)
    return _delta(a, b), eng.launch_log(since)


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_slowed_launch_is_one_stall_in_the_part_that_was_slowed(
        make, nano, kind, spans_on, tmp_path):
    """After 59 quiet launches one launch's program sleeps 20 median
    periods inside its enqueue: ONE stall, its excess in ``_enqueue``
    and nowhere else within 10%; the stall's event and span carry the
    record. (Another worker of the suite can stall a quiet launch of
    its own: such a window is run again, twice at most.)"""
    from ray_tpu._private import events as ev
    from ray_tpu.serve.engine import (LAUNCH_FIELDS, LAUNCH_STALL_FACTOR,
                                      _STALL_PARTS)

    ev._reset_for_tests()
    try:
        ev.init(str(tmp_path), proc="stall-test")
        eng = make(kind, max_len=128)
        list(eng.stream(_prompt(nano, 5), 6))   # compiled
        # 10 ms a loop: a steady period, far above the machine's jitter
        eng.inject_fault("driver_slow", wedge_s=0.01)
        slept = []

        def on_launch(n):
            if n == 60:
                periods = sorted(r["period"]
                                 for r in eng.launch_log()[-32:])
                slept.append(20 * periods[16])
                time.sleep(slept[-1] / 1e9)

        for attempt in range(3):
            del slept[:]
            tracing.drain()
            d, log = _long_lane(eng, nano, attempt, on_launch)
            if d["launch_stalls"] == 1:
                break
        assert d["launch_stalls"] == 1 and len(log) >= 75
        excess = d["launch_stall_ns_sum"]
        assert excess == pytest.approx(slept[0], rel=0.1)
        assert d["launch_stall_ns_enqueue"] == pytest.approx(excess,
                                                             rel=0.1)
        for part in _STALL_PARTS:
            if part != "enqueue":
                assert d[f"launch_stall_ns_{part}"] <= 0.1 * excess, part
        slow = max(log, key=lambda r: r["period"])
        assert slow == log[59]
        assert slow["period"] > LAUNCH_STALL_FACTOR * (slept[0] // 20)
        assert d["launch_stall_wall_ns_sum"] == slow["wall"]
        assert d["launch_stall_cpu_ns_sum"] == slow["cpu_proc"]
        assert d["launch_stall_gc_ns_sum"] == slow["gc"]
        # asleep: the replica was off the processor through the stall
        assert slow["cpu_proc"] < 0.5 * slow["wall"]

        # the flight recorder: the launch's event carries its record,
        # and the stall has one of its own
        rec = ev.recorder()
        rec.flush()
        events = ev.read_ring(rec.path)["events"]
        sent = [e["attrs"] for e in events
                if e["kind"] == "engine.dispatch"]
        keys = (set(LAUNCH_FIELDS) - {"kind"}) | {"launch", "period",
                                                  "epoch"}
        assert sent and all(keys <= set(e) for e in sent)
        assert "dispatch_s" not in sent[-1]
        assert sent[-1]["launch"] == slow["kind"]
        stalls = [e["attrs"] for e in events if e["kind"] == "engine.stall"
                  and e["attrs"]["t0"] == slow["t0"]]
        assert len(stalls) == 1 and stalls[0]["excess"] == excess
        assert {k: stalls[0][k] for k in LAUNCH_FIELDS if k != "kind"} \
            == {k: slow[k] for k in LAUNCH_FIELDS if k != "kind"}
        # and the span, on the driver's own trace and clock
        spans = tracing.local_spans()
        mine = [s for s in spans if s["name"] == "engine.stall"
                and s["attrs"]["t0"] == slow["t0"]]
        assert len(mine) == 1 and mine[0]["kind"] == "driver"
        assert mine[0]["mono_ns"] == [slow["t0"] - slow["gap"],
                                      slow["t0"] + slow["phase"]]
        assert mine[0]["trace_id"] in {
            s["trace_id"] for s in spans if s["name"] == "engine.decode"}
    finally:
        ev._reset_for_tests()


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_forced_collection_shows_in_its_launch(make, nano, kind):
    """``gc.collect()`` inside one launch (automatic collection off, so
    that it is the window's only one): that record's ``gc``, one
    generation-2 pause in ``stats()``, and no other record's."""
    import gc

    eng = make(kind, max_len=128)
    list(eng.stream(_prompt(nano, 5), 6))
    gc.collect()
    gc.disable()
    try:
        d, log = _long_lane(eng, nano, 3,
                            lambda n: n == 12 and gc.collect())
    finally:
        gc.enable()
    assert d["gc2_pauses"] == d["gc_pauses"] == 1
    assert log[11]["gc"] == d["gc2_pause_ns_sum"] \
        == d["gc_pause_ns_sum"] > 0
    assert log[11]["gc"] < log[11]["enqueue"]
    assert not [r for i, r in enumerate(log) if i != 11 and r["gc"]]
    assert d["launch_stall_gc_ns_sum"] <= log[11]["gc"]


MS = 1_000_000


def _rec(lanes, prompts=0, each=30, host=4, wait=140, **more):
    """A hand-made record (ms): ``prompts`` prefills of ``each`` and
    ``host`` of the loop's own work in the gap, a state pass of 1, and a
    launch of 2 + 1 + ``wait`` + 1; ``more``: fields given outright."""
    from ray_tpu.serve.engine import LAUNCH_FIELDS

    pre = prompts * each
    r = dict.fromkeys(LAUNCH_FIELDS, 0)
    r.update(gap=pre + host + 1, gap_prefill=pre, gap_deliver=1,
             prompts=prompts, prefill_launches=-(-prompts // 2),
             enqueue=2, flush=1, wait=wait, read=1, phase=wait + 4,
             deliver=1, lanes=lanes)
    r.update(more)
    return tuple(r[f] * (1 if f in ("prompts", "prefill_launches", "lanes",
                                    "kind") else MS)
                 for f in LAUNCH_FIELDS)


#: a full engine's last 32 launches: a prompt or two in every other gap
_FULL = [_rec(120 + i % 8, prompts=i % 4 % 3) for i in range(32)]
_STALL_CASES = {
    # work, and no standstill: None
    "a burst of admissions in one gap":
        (_FULL, _rec(128, prompts=60, host=64), None),
    "the ramp: one lane, then 127 prompts in one gap":
        ([_rec(1, wait=20)] * 32,
         _rec(128, prompts=127, host=130), None),
    "two launches on, a full collection in the gap":
        ([_rec(1, wait=20)] * 30 + [_rec(128, prompts=127), _rec(128)],
         _rec(128, host=190), None),
    "a long prompt where nobody has seen one":
        ([_rec(2, wait=50)] * 32, _rec(2, prompts=1, each=600, wait=50),
         None),
    "a collection and three long prompts":
        (_FULL, _rec(128, prompts=3, each=45, host=180), None),
    # a standstill: the excess, and the part it fell in
    "2 s in a launch's wait":
        (_FULL, _rec(128, prompts=1, wait=2140), (2000, "wait")),
    "1.9 s in a prefill's read":
        (_FULL, _rec(128, prompts=2, gap_prefill=1960, gap=1965),
         (1900, "gap_prefill")),
    "1 s between the phases":
        (_FULL, _rec(128, host=1004), (1000, "gap_host")),
    # half the window is like it, and no more is needed
    "sixteen like it":
        ([_rec(1, wait=20)] * 16 + _FULL[:16], _rec(128, wait=2140),
         (2000, "wait")),
    "fifteen like it":
        ([_rec(1, wait=20)] * 17 + _FULL[:15], _rec(128, wait=2140), None),
}


@pytest.mark.parametrize("case", list(_STALL_CASES))
def test_a_stall_is_a_standstill_and_not_work(case):
    """The rule alone, on hand-made records: a burst of admissions, a
    ramp and a collection are no stall; a standstill is one wherever it
    falls, a prefill's read too, with its excess in that part."""
    from ray_tpu.serve.engine import _STALL_PARTS, _stall

    recent, rec, want = _STALL_CASES[case]
    found = _stall(rec, recent)
    if want is None:
        assert found is None
        return
    excess, usual, parts = found
    by_part = dict(zip(_STALL_PARTS, parts))
    assert usual == 149 * MS        # 4 + 1 of the gap, 144 of the launch
    assert excess == pytest.approx(want[0] * MS, rel=0.02)
    assert by_part.pop(want[1]) == pytest.approx(excess, rel=0.02)
    assert sum(by_part.values()) <= 0.02 * excess


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_burst_of_admissions_counts_no_stall(make, nano, kind):
    """A lone lane for 34 launches, then seven callers at once: their
    prefills (20 ms a prompt here) lie in ONE gap, many periods long,
    and the launches behind it hold eight lanes for the window's one:
    work, and no stall."""
    eng = make(kind, slots=8, chunk=2, max_len=128)
    eng.warm_up()
    eng.inject_fault("driver_slow", wedge_s=0.01)   # a steady period
    inner = eng._prefill

    def prefill(params, cache, tokens, *rest):
        time.sleep(0.02 * (len(tokens) if isinstance(tokens, tuple) else 1))
        return inner(params, cache, tokens, *rest)

    eng._prefill = prefill
    lane = eng.stream(_prompt(nano, 5), 120)
    start = eng.stats()["dispatches"]
    while eng.stats()["dispatches"] < start + 34:
        next(lane)
    a, since = eng.stats(), time.monotonic_ns()
    _run_all(eng, nano, 7, max_new=8)
    list(lane)
    b, _ = _quiet(eng)
    d, log = _delta(a, b), eng.launch_log(since)
    assert len(eng._recent) == 32
    burst = max(log, key=lambda r: r["prompts"])
    assert burst["prompts"] >= 4 and burst["lanes"] >= 5
    usual = sorted(r["period"] for r in eng.launch_log()
                   if r["t0"] < burst["t0"])[-16]
    assert burst["period"] > 4 * usual
    assert d["launch_stalls"] == d["launch_stall_ns_sum"] == 0


def test_gc_counts_registers_one_listener():
    import gc

    table = tracing.gc_counts()
    n = len(gc.callbacks)
    for _ in range(3):
        assert tracing.gc_counts() is table
    assert len(gc.callbacks) == n
    assert set(table) == {"n", "ns", "n2", "ns2", "open_ns"}
    before = dict(table)
    gc.collect()
    assert table["n2"] == before["n2"] + 1 and table["n"] > before["n"]
    assert table["ns2"] > before["ns2"] and table["open_ns"] == 0
    assert table["ns"] - before["ns"] >= table["ns2"] - before["ns2"]


@pytest.mark.parametrize("kind", list(KINDS))
def test_hold_is_what_lay_between_a_read_and_the_next_flush(make, nano,
                                                            kind):
    """A lone lane's first launch hands nothing over (``hold`` 0) and
    every later one what the launch before it kept, held for the gap
    and the enqueue; a prefill that lay between two launches is inside
    the hold. Twelve quiet launches classify nothing (a driver run's
    first 16 never do, whatever the machine did to them): no stall."""
    eng = make(kind)
    list(eng.stream(_prompt(nano, 5), 6))
    a, since = _quiet(eng)
    eng._recent.clear()         # at rest: as a driver run begins
    # a verify launch commits one to three tokens
    list(eng.stream(_prompt(nano, 5, 1), 20 if kind == "spec" else 48))
    b, _ = _quiet(eng)
    d, log = _delta(a, b), eng.launch_log(since)
    assert 9 <= len(log) == len(eng._recent) <= 16
    assert d["launch_stalls"] == d["launch_stall_ns_sum"] == 0
    assert log[0]["hold"] == 0 and log[0]["gap"] == 0
    for r in log[1:]:
        assert r["hold"] >= r["gap"] + r["enqueue"] > 0
    # one message a launch rode behind the next; the last launch's
    # (slice and end) went with no launch to ride behind
    assert d["deliver_puts_overlapped"] == len(log) - 1
    assert d["deliver_hold_ns_sum"] > sum(r["hold"] for r in log)

    eng.inject_fault("driver_slow", wedge_s=0.01)   # keep lane A running
    lane = eng.stream(_prompt(nano, 5, 2), 40)
    next(lane)
    a, since = eng.stats(), time.monotonic_ns()
    other = threading.Thread(
        target=lambda: list(eng.stream(_prompt(nano, 6, 3), 4)))
    other.start()
    list(lane)
    other.join()
    time.sleep(0.12)
    after = [r for r in eng.launch_log(since) if r["prompts"]]
    assert len(after) == 1 and after[0]["prefill_launches"] == 1
    assert after[0]["hold"] >= after[0]["gap"] \
        > after[0]["gap_prefill"] > 0

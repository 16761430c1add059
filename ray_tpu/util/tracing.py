"""Distributed tracing: spans around task submit/execute with context
propagation across process boundaries.

Capability parity with the reference's tracing helper (reference:
``python/ray/util/tracing/tracing_helper.py`` — ``_inject_tracing_into_function``
serializes the caller's span context into a hidden ``_ray_trace_ctx`` kwarg
and the worker reopens a child span around user code) and with C++ profile
events (reference: ``src/ray/core_worker/profile_event.h``). Re-designed for
this runtime: the context rides the task wire meta (``trace_ctx`` key on the
spec), spans buffer per process and flush to the head alongside task events,
and the head folds them into the chrome-trace timeline and a ``get_spans``
RPC — no OpenTelemetry dependency (zero-egress image), but the span model
(trace_id / span_id / parent_id / attributes) matches, so an exporter is a
drain loop away.

Usage::

    import ray_tpu
    from ray_tpu.util import tracing

    ray_tpu.init()
    tracing.enable()
    with tracing.span("my-request", user="alice"):
        ref = my_task.remote()          # submit span, child of my-request
        ray_tpu.get(ref)                # worker executes under same trace
    spans = tracing.get_spans()          # cluster-wide, from the head
"""
from __future__ import annotations

import contextlib
import contextvars
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple, Union

# (trace_id, span_id) of the active span in this thread/coroutine.
_current: contextvars.ContextVar = contextvars.ContextVar(
    "rt_trace_ctx", default=None)

_enabled = os.environ.get("RT_TRACING_ENABLED", "").lower() in (
    "1", "true", "yes", "on")
# Finished spans waiting for a flush to the head.
_buffer: deque = deque(maxlen=100_000)
# Spans evicted at capacity (the deque drops silently; a trace missing
# its middle is worse than an honest drop count). Guarded by _drop_lock;
# reported to the head with every span flush and surfaced through
# ``get_spans(with_meta=True)`` and the
# ``tracing_spans_dropped_total`` counter.
_dropped = 0
_drop_lock = threading.Lock()


def enable() -> None:
    """Turn on span recording in THIS process. Remote workers switch on
    lazily: any task submitted while tracing is enabled carries a
    ``trace_ctx``, and executing a traced task records spans regardless
    of the worker-local flag (the decision belongs to the submitter,
    like the reference's driver-side ``_tracing_startup_hook``).

    Serve proxies mirror the driver's flag on the next
    ``serve.start()``/``serve.run()`` call (or set ``RT_TRACING_ENABLED=1``
    cluster-wide to trace every process from boot)."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def current_context() -> Optional[Dict[str, str]]:
    cur = _current.get()
    if cur is None:
        return None
    return {"trace_id": cur[0], "span_id": cur[1]}


def clock_pair() -> Dict[str, float]:
    """This process's wall clock and monotonic clock read together.
    Every span flush carries a fresh pair, so the head can place spans
    that have ``mono_ns`` stamps on one axis however the wall clock
    stepped since they were taken (one machine's processes share
    ``CLOCK_MONOTONIC``; waits that cross nodes keep the wall clock)."""
    return {"wall": time.time(), "mono_ns": time.monotonic_ns()}


# Maps a ``time.monotonic_ns()`` stamp to wall-clock seconds for the
# ``start``/``end`` every span carries.
_anchor = clock_pair()


def wall_of(mono_ns: int) -> float:
    return _anchor["wall"] + (mono_ns - _anchor["mono_ns"]) / 1e9


def _record(name: str, kind: str, trace_id: str, span_id: str,
            parent_id: Optional[str], start: float, end: float,
            attrs: Optional[Dict[str, Any]], status: str = "ok",
            mono_ns: Optional[Tuple[int, int]] = None) -> dict:
    span = {
        "name": name, "kind": kind,
        "trace_id": trace_id, "span_id": span_id, "parent_id": parent_id,
        "start": start, "end": end, "status": status,
    }
    if mono_ns is not None:
        # the same interval on time.monotonic_ns(): the clock of the
        # engine's counters, the benchmark's stamps and (through one
        # marker event) a jax.profiler trace
        span["mono_ns"] = [int(mono_ns[0]), int(mono_ns[1])]
    if attrs:
        span["attrs"] = attrs
    if len(_buffer) >= (_buffer.maxlen or 0) > 0:
        _note_dropped(1)  # append below evicts the oldest span silently
    _buffer.append(span)
    return span


_drop_counter = None


def _note_dropped(n: int) -> None:
    global _dropped, _drop_counter
    if n <= 0:
        return
    with _drop_lock:
        _dropped += n
        # Lazy init under the same lock: a racing double-create would
        # register two instruments and lose one side's increments.
        if _drop_counter is None:
            try:
                from ray_tpu._private.metrics import Counter

                _drop_counter = Counter(
                    "tracing_spans_dropped_total",
                    "Finished spans evicted from the per-process buffer "
                    "at capacity before a flush")
            except Exception:  # noqa: BLE001 - never break tracing
                return
    try:
        _drop_counter.inc(n)
    except Exception:  # noqa: BLE001 - accounting must never break tracing
        pass


def take_dropped() -> int:
    """Drop count since the last take (shipped with each span flush)."""
    global _dropped
    with _drop_lock:
        n, _dropped = _dropped, 0
        return n


def add_dropped(n: int) -> None:
    """Return an unshipped drop count after a failed flush (the head
    never saw it, so it must ride the next report)."""
    global _dropped
    if n > 0:
        with _drop_lock:
            _dropped += n


def dropped_total() -> int:
    """Drops counted in this process and not yet reported to the head."""
    with _drop_lock:
        return _dropped


@contextlib.contextmanager
def span(name: str, kind: str = "internal", **attrs):
    """Record a span; nested ``span()``/task submissions become children.

    No-op (yields None) when tracing is disabled, so library code may
    instrument unconditionally. Inside a traced task the propagated
    context is active even though the worker never called ``enable()``
    — user spans there must record, so the context check comes first.
    """
    parent = _current.get()
    if parent is None and not _enabled:
        yield None
        return
    trace_id = parent[0] if parent else _new_id(16)
    span_id = _new_id(8)
    token = _current.set((trace_id, span_id))
    start = time.time()
    status = "ok"
    try:
        yield {"trace_id": trace_id, "span_id": span_id}
    except BaseException:
        status = "error"
        raise
    finally:
        _current.reset(token)
        _record(name, kind, trace_id, span_id,
                parent[1] if parent else None, start, time.time(),
                attrs or None, status)


class ManualSpan:
    """Span whose lifetime crosses threads (streaming responses: opened
    where the stream is submitted, finished wherever it ends). The
    contextvar window for parenting child submissions is explicit
    (:meth:`activate`), so no token is ever reset on a foreign thread.
    """

    def __init__(self, name: str, kind: str, parent, attrs):
        self.name = name
        self.kind = kind
        self.trace_id = parent[0] if parent else _new_id(16)
        self.span_id = _new_id(8)
        self._parent_id = parent[1] if parent else None
        self._attrs = attrs or None
        self._start = time.time()
        self._done = False

    @contextlib.contextmanager
    def activate(self):
        token = _current.set((self.trace_id, self.span_id))
        try:
            yield self
        finally:
            _current.reset(token)

    def finish(self, status: str = "ok") -> None:
        if self._done:
            return
        self._done = True
        _record(self.name, self.kind, self.trace_id, self.span_id,
                self._parent_id, self._start, time.time(), self._attrs,
                status)


def manual_span(name: str, kind: str = "internal",
                **attrs) -> Optional[ManualSpan]:
    """Open a :class:`ManualSpan`, or None when tracing is off (callers
    guard their ``activate``/``finish`` with that)."""
    parent = _current.get()
    if parent is None and not _enabled:
        return None
    return ManualSpan(name, kind, parent, attrs)


def record_span(name: str, start: Optional[float] = None,
                end: Optional[float] = None, kind: str = "stage",
                parent_ctx: Optional[Dict[str, str]] = None,
                status: str = "ok",
                mono_ns: Optional[Tuple[int, int]] = None,
                **attrs) -> Optional[dict]:
    """Record an already-measured span (start/end are wall-clock
    ``time.time()`` stamps) without touching the active context. A span
    measured on the monotonic clock passes ``mono_ns=(start, end)``
    (``time.monotonic_ns()``) instead; its wall-clock stamps derive
    from those and the span keeps both.

    The serve data plane uses this for stage timings whose lifetime does
    not match any ``with`` block: queue waits measured across a process
    hop (``replica.queue_wait`` starts at the router's submission stamp),
    batcher flush waits recorded on the flusher thread, and per-chunk
    decode dispatches. Parents under ``parent_ctx`` (a wire context dict)
    when given, else the caller's active span; no-op when neither exists
    and tracing is off."""
    parent = None
    if parent_ctx is not None:
        parent = (parent_ctx["trace_id"], parent_ctx["span_id"])
    else:
        parent = _current.get()
    if parent is None and not _enabled:
        return None
    if mono_ns is not None:
        start, end = wall_of(mono_ns[0]), wall_of(mono_ns[1])
    trace_id = parent[0] if parent else _new_id(16)
    return _record(name, kind, trace_id, _new_id(8),
                   parent[1] if parent else None, start,
                   time.time() if end is None else end,
                   attrs or None, status, mono_ns)


_compiles: Optional[Dict[str, int]] = None
_compiles_lock = threading.Lock()


def compile_counts() -> Dict[str, int]:
    """``{"n", "ns"}``: programs XLA built in this process and the time
    that took, counted by one ``jax.monitoring`` listener (registered on
    the first call; jax reports a build that the persistent cache served
    too). The table is live: after warm-up both must stand still."""
    global _compiles
    with _compiles_lock:
        if _compiles is None:
            import jax.monitoring

            table = {"n": 0, "ns": 0}

            def on_duration(event: str, seconds: float, **_kw):
                if event == "/jax/core/compile/backend_compile_duration":
                    table["n"] += 1
                    table["ns"] += int(seconds * 1e9)

            jax.monitoring.register_event_duration_secs_listener(
                on_duration)
            _compiles = table
        return _compiles


_gc: Optional[Dict[str, int]] = None
_gc_lock = threading.Lock()


def gc_counts() -> Dict[str, int]:
    """``{"n", "ns", "n2", "ns2", "open_ns"}``: garbage collections of
    this process and the time they took (every generation; generation 2
    alone), and the ``time.monotonic_ns()`` stamp at which the one in
    flight started (0: none is), counted by one ``gc.callbacks``
    listener (registered on the first call). A collection holds the
    interpreter lock whichever thread it runs on, so its time is a
    pause of every thread that needs the lock. The table is live. The
    listener runs on every generation-0 collection of every thread:
    two stamps and two additions, nothing else."""
    global _gc
    with _gc_lock:
        if _gc is None:
            import gc

            table = {"n": 0, "ns": 0, "n2": 0, "ns2": 0, "open_ns": 0}

            def on_gc(phase: str, info: dict):
                if phase == "start":
                    table["open_ns"] = time.monotonic_ns()
                elif table["open_ns"]:
                    ns = time.monotonic_ns() - table["open_ns"]
                    table["open_ns"] = 0
                    table["n"] += 1
                    table["ns"] += ns
                    if info["generation"] == 2:
                        table["n2"] += 1
                        table["ns2"] += ns

            gc.callbacks.append(on_gc)
            _gc = table
        return _gc


class PhaseClock:
    """What one driver thread (the serving engine's) is doing, phase by
    phase. The thread is at every moment in exactly one phase; phases
    nest, and a phase is charged its SELF time (its span minus what the
    phases opened inside it cover), so the phases of ``table`` sum to
    ``table["total"]`` by construction. Entering and leaving a phase
    takes one ``time.monotonic_ns()`` and one ``time.thread_time_ns()``
    stamp each and does four things with the pairs:

    - adds the self time to ``table[name]``: plain ints owned by the
      driver thread; other threads read them racily (a phase in flight
      is not counted yet);
    - adds the self CPU time (this thread on a processor, kept the way
      the wall time is) to ``table["cpu.<name>"]`` where the table was
      seeded with that key: wall minus cpu of a phase is the thread OFF
      the processor in it: blocked on the device, waiting for the
      interpreter lock, descheduled;
    - holds a ``jax.profiler.TraceAnnotation("<prefix>.<name>", ...)``
      open over the same interval, so that in any ``jax.profiler`` trace
      the phases lie in the host plane on the trace's own clock, beside
      the device operations (an atomic load when no trace is running);
    - when :func:`enabled`, records one span (``kind="driver"``, one
      trace id per clock, i.e. per driver run) with ``mono_ns``. Spans
      are held until the outermost phase closes, and dropped if that
      one was ``muted``: a loop that wakes twenty times a second to find
      nothing to do must not fill the span buffers with it.

    A :meth:`step` is a named PART of the phase that is open: counted
    beside the phase (``table["<phase>.<name>"]``), never taken from it.

    One clock per driver run: the table outlives it (the engine's), the
    stack of open phases does not.

    ONE time base. Every stamp is ``time.monotonic_ns()``:
    CLOCK_MONOTONIC, one for all processes of a machine. The spans'
    ``mono_ns``, the engine's launch records
    (``DecodeEngine.launch_log()``: ``t0``, and ``t0 - gap`` .. ``t0 +
    phase``), the flight recorder's ``mono`` (the same clock in
    seconds) and the stamp a tracing process takes inside its sync
    annotation (``benchmarks/perf``: ``Tracer.handoff()``'s
    ``sync_host_ns``) are on it. A ``jax.profiler`` trace has a clock
    of its own; ``offset = <the sync annotation's start in the trace> -
    sync_host_ns`` maps one onto the other, so a record's launch lies at
    ``t0 + offset`` in the trace, beside this clock's
    ``<prefix>.<phase>.<step>`` annotations in the host plane and the
    device's operations: a reducer joins launches to device gaps by
    interval and needs no sampler.
    """

    def __init__(self, prefix: str, table: Dict[str, int], **attrs):
        # jax only where a driver thread exists: this module is imported
        # by processes that must never load it
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.prefix = prefix
        self.table = table
        self.attrs = attrs              # on every span of this clock
        self.trace_id = _new_id(16)
        self._open: List["_Phase"] = []
        self._step: Optional["_Step"] = None
        self._spans: List[tuple] = []   # of the outermost open phase

    def phase(self, name: str, **args) -> "_Phase":
        """``with clock.phase("decode", slots_active=n) as ph:``; after
        the block ``ph.t0``/``ph.t1`` are its monotonic stamps. ``args``
        go to the annotation and the span."""
        return _Phase(self, name, args)

    def step(self, name: str, **args) -> "_Step":
        """A named part of the phase that is open: an annotation in the
        profiler's trace (``<prefix>.<phase>.<name>``) and, from one
        ``time.monotonic_ns()`` stamp at each end, its duration added to
        ``table["<phase>.<name>"]``. Steps do not nest, and no phase
        opens inside one."""
        return _Step(self, name, args)

    def span(self, name: str, t0: int, t1: int, args: dict) -> None:
        """One span of this clock's trace that is no phase (a stall the
        driver found in its own launches), from two monotonic stamps it
        already holds; nothing when not :func:`enabled`."""
        if _enabled:
            _record(f"{self.prefix}.{name}", "driver", self.trace_id,
                    _new_id(8), None, wall_of(t0), wall_of(t1),
                    {**self.attrs, **args}, mono_ns=(t0, t1))


class _Step:
    __slots__ = ("clock", "key", "t0", "_ann")

    def __init__(self, clock: PhaseClock, name: str, args: dict):
        assert clock._open, f"step {name!r} outside any phase"
        self.clock = clock
        self.key = f"{clock._open[-1].name}.{name}"
        self._ann = clock._annotation(f"{clock.prefix}.{self.key}", **args)

    def __enter__(self):
        c = self.clock
        assert c._step is None, f"step {self.key!r} inside {c._step.key!r}"
        self._ann.__enter__()
        c._step = self
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, et, ev, tb):
        t1 = time.monotonic_ns()
        c = self.clock
        c.table[self.key] = c.table.get(self.key, 0) + t1 - self.t0
        c._step = None
        self._ann.__exit__(et, ev, tb)
        return False


class _Phase:
    __slots__ = ("clock", "name", "args", "t0", "t1", "span_id",
                 "muted", "_inner", "_cpu0", "_cpu_inner", "_ann")

    def __init__(self, clock: PhaseClock, name: str, args: dict):
        self.clock, self.name, self.args = clock, name, args
        self.t0 = self.t1 = self._inner = self._cpu_inner = 0
        self.span_id = _new_id(8) if _enabled else None
        self.muted = False      # set on an outermost phase: no spans

    def __enter__(self):
        c = self.clock
        assert c._step is None, \
            f"phase {self.name!r} inside step {c._step.key!r}"
        self._ann = c._annotation(f"{c.prefix}.{self.name}", **self.args)
        self._ann.__enter__()
        c._open.append(self)
        self._cpu0 = time.thread_time_ns()
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, et, ev, tb):
        self.t1 = time.monotonic_ns()
        cpu = time.thread_time_ns() - self._cpu0
        c = self.clock
        dur = self.t1 - self.t0
        stack = c._open
        stack.pop()             # ``with`` blocks of one thread: LIFO
        c.table[self.name] = c.table.get(self.name, 0) + dur - self._inner
        if "cpu." + self.name in c.table:
            c.table["cpu." + self.name] += cpu - self._cpu_inner
        if stack:
            stack[-1]._inner += dur
            stack[-1]._cpu_inner += cpu
        else:
            c.table["total"] = c.table.get("total", 0) + dur
        self._ann.__exit__(et, ev, tb)
        if _enabled:
            c._spans.append((
                f"{c.prefix}.{self.name}", "driver", c.trace_id,
                self.span_id or _new_id(8),
                stack[-1].span_id if stack else None,
                wall_of(self.t0), wall_of(self.t1),
                {**c.attrs, **self.args} or None,
                "ok" if et is None else "error", (self.t0, self.t1)))
        if not stack and c._spans:
            spans, c._spans = c._spans, []
            if not self.muted:
                for span in spans:
                    _record(*span)
        return False


@contextlib.contextmanager
def activate_context(ctx: Optional[Dict[str, str]]):
    """Make a wire context (``{"trace_id", "span_id"}``) the active span
    on this thread for the duration of the block, so spans recorded and
    tasks submitted inside parent under it. Used where a request crosses
    an untraced thread hop — e.g. the batcher invoking the user handler
    on its flusher thread. No-op for ``ctx=None``."""
    if ctx is None:
        yield None
        return
    token = _current.set((ctx["trace_id"], ctx["span_id"]))
    try:
        yield ctx
    finally:
        _current.reset(token)


def on_submit(name: str) -> Optional[Dict[str, str]]:
    """Called by the core worker at task/actor-call submission. Records a
    point-in-time submit span (child of the caller's active span) and
    returns the wire context the execute side parents under, or None when
    tracing is off (the common case — one branch on the hot path).

    A worker submitting from inside a traced task has an active context
    (execute_span set it) even though its local flag is off — the chain
    must continue across hops, so the context check comes first."""
    parent = _current.get()
    if parent is None and not _enabled:
        return None
    trace_id = parent[0] if parent else _new_id(16)
    span_id = _new_id(8)
    now = time.time()
    _record(f"submit {name}", "submit", trace_id, span_id,
            parent[1] if parent else None, now, now, None)
    return {"trace_id": trace_id, "span_id": span_id}


@contextlib.contextmanager
def execute_span(meta: dict, name: str):
    """Worker-side child span around user-code execution of a traced task.

    Pulls the propagated context from the task wire meta; a task with no
    ``trace_ctx`` (tracing off at the submitter) costs one dict lookup.
    """
    ctx = meta.get("trace_ctx")
    if ctx is None:
        yield None
        return
    trace_id = ctx["trace_id"]
    span_id = _new_id(8)
    token = _current.set((trace_id, span_id))
    start = time.time()
    status = "ok"
    try:
        yield {"trace_id": trace_id, "span_id": span_id}
    except BaseException:
        status = "error"
        raise
    finally:
        _current.reset(token)
        _record(f"execute {name}", "execute", trace_id, span_id,
                ctx.get("span_id"), start, time.time(), None, status)


def drain() -> List[dict]:
    """Hand off buffered finished spans (called by the flush loop).
    Pops item-wise: a span appended concurrently by an executor thread
    either makes this drain or stays for the next one — a snapshot +
    clear() would silently drop it."""
    out: List[dict] = []
    while True:
        try:
            out.append(_buffer.popleft())
        except IndexError:
            return out


def requeue(spans: List[dict]) -> None:
    """Return drained spans to the buffer after a failed flush (oldest
    first, so a healthy next flush preserves order; the deque bound
    drops the oldest if the head stays unreachable)."""
    if _buffer.maxlen:
        # extendleft on a bounded deque evicts from the RIGHT silently;
        # count what cannot fit so the loss is visible.
        _note_dropped(len(spans) + len(_buffer) - _buffer.maxlen)
    _buffer.extendleft(reversed(spans))


def local_spans() -> List[dict]:
    """Finished spans still buffered in this process (testing hook)."""
    return list(_buffer)


def get_spans(limit: int = 1000,
              with_meta: bool = False) -> Union[List[dict], Dict[str, Any]]:
    """Cluster-wide finished spans, from the head (flushes local first).

    ``with_meta=True`` returns ``{"spans": [...], "dropped_total": N}``
    where ``dropped_total`` counts spans evicted from process buffers at
    capacity cluster-wide — a non-zero value means traces may be missing
    their middles."""
    from ray_tpu.core.worker import CoreWorker

    core = CoreWorker.current()
    core.flush_task_events()
    out = core.head_call("get_spans",
                         {"limit": limit, "with_meta": with_meta})
    return out

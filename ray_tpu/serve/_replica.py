"""Replica actor: hosts one copy of a deployment's user callable.

Capability parity with the reference's replica
(reference: ``python/ray/serve/_private/replica.py:231`` — user callable
wrapper, ongoing-request accounting, health checks, reconfigure), rebuilt
for this runtime's threaded actors: requests execute on the actor's
``max_concurrency`` thread pool, ongoing counts are plain
lock-protected integers, and metrics are pulled by the controller.
"""
from __future__ import annotations

import asyncio
import inspect
import threading
import time
from typing import Any, Dict, Optional

import cloudpickle

from .._private import events as _events
from ..util import tracing
from .request import (HANDOFF_KEY, REQUEST_ID_KEY, RESUME_FROM_KEY,
                      SUBMITTED_AT_KEY, TRACE_CTX_KEY,
                      ReplicaDrainingError, ReplicaOverloadedError,
                      RequestDeadlineExceeded, _request_deadline,
                      _request_deployment, _request_handoff, _request_id,
                      _request_resume_from, deadline_expired)

#: Bound on the fault-injection invocation log (test hook, see below).
_INVOCATION_LOG_CAP = 10_000


class Replica:
    """Created by the controller with
    ``max_concurrency = max_ongoing_requests + headroom`` so that metrics and
    health probes still run while requests saturate the pool.

    Request lifecycle (server half; ``handle.py`` is the client half):
    every request is admitted under the lock BEFORE user code runs —
    a replica at ``max_ongoing_requests`` pushes back with the typed
    ``ReplicaOverloadedError`` (the router re-picks, it does not mark
    the replica dead), and a request whose absolute deadline already
    passed is dropped with ``RequestDeadlineExceeded`` so TPU cycles are
    never spent computing answers nobody is waiting for. The deadline is
    exposed to user code (and the batcher) via a contextvar."""

    def __init__(self, app_name: str, deployment_name: str, replica_id: str,
                 payload: bytes, user_config: Any = None,
                 max_ongoing_requests: int = 0,
                 engine_config: Optional[dict] = None):
        self.app_name = app_name
        self.deployment_name = deployment_name
        self.replica_id = replica_id
        callable_def, init_args, init_kwargs = cloudpickle.loads(payload)
        init_args = _resolve_handles(app_name, init_args)
        init_kwargs = _resolve_handles(app_name, init_kwargs)
        if inspect.isclass(callable_def):
            self._user = callable_def(*init_args, **init_kwargs)
        else:
            self._user = callable_def  # plain function deployment
        if engine_config:
            self._apply_engine_config(engine_config)
        # Compile every engine's whole program set BEFORE this
        # constructor returns — that return is what reports the replica
        # ready, and health probes (so the driver's wedge timer) only
        # start after it. A compiler error raises here and reaches the
        # caller of serve.run() as the replica's start error.
        for eng in self._engines():
            eng.warm_up()
        self._lock = threading.Lock()
        # Signalled when the last in-flight request finishes, so drain()
        # wakes immediately instead of polling (rtlint RT104 audit: the
        # old 10 ms sleep loop burned a controller RPC thread and added
        # up to 10 ms to every graceful teardown). Shares _lock, so
        # _ongoing stays single-lock state.
        self._idle_cond = threading.Condition(self._lock)
        self._ongoing = 0
        self._total = 0
        # Server-side admission bound; 0 = unlimited (the controller
        # passes the deployment's max_ongoing_requests).
        self._max_ongoing = int(max_ongoing_requests or 0)
        self._expired = 0
        self._overloaded = 0
        # Graceful-drain state: once draining, admissions push back with
        # the retryable ReplicaDrainingError (router re-picks) while
        # running work finishes.
        self._draining = False
        self._drains = 0
        self._start_time = time.time()
        # Fault-injection hook (armed via set_fault_injection; testing
        # only): optional per-request latency/error plus an invocation
        # log recording (method, start, deadline) for every admitted
        # request — overload and deadline tests assert on it instead of
        # relying on real slowness.
        self._fault: Dict[str, Any] = {}
        self._invocations: list = []
        if user_config is not None:
            self.reconfigure(user_config)

    # ------------------------------------------------------------ data plane
    def _admit(self, method_name: str, ctx: Optional[dict]
               ) -> Optional[float]:
        """Admission gate run before any user code; returns the request
        deadline. Raises the typed pushback/expiry errors."""
        deadline = (ctx or {}).get("deadline_s")
        with self._lock:
            if self._draining:
                # Routing signal, not a failure: the router re-picks
                # another replica; this one is being torn down.
                raise ReplicaDrainingError(
                    f"{self.replica_id} is draining for shutdown")
            if deadline_expired(deadline):
                self._expired += 1
                self._count_lifecycle("requests_expired", "replica")
                raise RequestDeadlineExceeded(
                    f"request deadline passed before {self.replica_id} "
                    f"started {method_name}")
            if self._max_ongoing and self._ongoing >= self._max_ongoing:
                self._overloaded += 1
                raise ReplicaOverloadedError(
                    f"{self.replica_id} at max_ongoing_requests="
                    f"{self._max_ongoing}")
            self._ongoing += 1
            self._total += 1
            ongoing = self._ongoing
        _events.emit("replica.admit",
                     request=(ctx or {}).get(REQUEST_ID_KEY, ""),
                     replica=self.replica_id,
                     deployment=self.deployment_name,
                     method=method_name, ongoing=ongoing)
        self._observe_queue_wait(ctx)
        return deadline

    def _observe_queue_wait(self, ctx: Optional[dict]):
        """``replica.queue_wait`` stage: submission stamp (router side)
        to admission here — transit plus any actor-mailbox queueing.
        Wall-clock across processes, like the deadline it rides with."""
        submitted_at = (ctx or {}).get(SUBMITTED_AT_KEY)
        if submitted_at is None:
            return
        now = time.time()
        # Cross-machine wall clocks: clamp so skew never yields a
        # negative wait (histogram) or an end-before-start span.
        start = min(submitted_at, now)
        from .._private.metrics import serve_metrics

        serve_metrics()["queue_wait"].observe(
            now - start,
            labels={"deployment": self.deployment_name,
                    "where": "replica"})
        tctx = (ctx or {}).get(TRACE_CTX_KEY)
        if tctx is not None:
            tracing.record_span("replica.queue_wait", start, now,
                                parent_ctx=tctx,
                                deployment=self.deployment_name,
                                replica=self.replica_id)

    def _count_lifecycle(self, name: str, where: str):
        from .._private.metrics import serve_metrics

        serve_metrics()[name].inc(
            labels={"deployment": self.deployment_name, "where": where})

    def _pre_invoke(self, method_name: str, deadline: Optional[float]):
        """Fault-injection hook: log the invocation, then apply the
        configured latency/error. A no-op unless armed."""
        fi = self._fault
        if not fi:
            return
        with self._lock:
            self._invocations.append(
                {"method": method_name, "start": time.time(),
                 "deadline": deadline})
            if len(self._invocations) > _INVOCATION_LOG_CAP:
                del self._invocations[:-_INVOCATION_LOG_CAP]
        if fi.get("latency_s"):
            time.sleep(fi["latency_s"])
        rate = fi.get("error_rate", 0.0)
        if rate:
            import random

            if random.random() < rate:
                raise RuntimeError(
                    f"injected fault on {self.replica_id}.{method_name}")

    def handle_request(self, method_name: str, args: tuple, kwargs: dict,
                       ctx: dict = None):
        deadline = self._admit(method_name, ctx)
        token = None
        if ctx and ctx.get("multiplexed_model_id"):
            from .multiplex import _request_model_id

            token = _request_model_id.set(ctx["multiplexed_model_id"])
        dl_token = _request_deadline.set(deadline)
        dep_token = _request_deployment.set(self.deployment_name)
        rid_token = _request_id.set(
            (ctx or {}).get(REQUEST_ID_KEY) or None)
        # Prefill hop of a disaggregated dispatch (ISSUE 14): the
        # continuous-batching wrapper answers with a leased handoff
        # descriptor instead of a stream.
        ho_token = _request_handoff.set((ctx or {}).get(HANDOFF_KEY))
        try:
            self._pre_invoke(method_name, deadline)
            if inspect.isfunction(self._user) or inspect.isbuiltin(self._user):
                method = self._user
            else:
                method = getattr(self._user, method_name)
            # user_code stage span: the slice of the request actually
            # spent in the deployment's handler (queue waits and
            # transport excluded). Nested spans/handle calls/batch
            # submissions inside the handler parent under it.
            with tracing.span("user_code", kind="stage",
                              deployment=self.deployment_name,
                              method=method_name):
                out = method(*args, **kwargs)
                if inspect.iscoroutine(out):
                    # Per-call loop: our replicas are thread-concurrent,
                    # not loop-concurrent; shared batching state lives
                    # in serve.batching's thread queues instead.
                    out = asyncio.run(out)
            return out
        finally:
            _request_handoff.reset(ho_token)
            _request_id.reset(rid_token)
            _request_deployment.reset(dep_token)
            _request_deadline.reset(dl_token)
            if token is not None:
                from .multiplex import _request_model_id

                _request_model_id.reset(token)
            with self._lock:
                self._ongoing -= 1
                if self._ongoing == 0:
                    self._idle_cond.notify_all()

    def handle_request_streaming(self, method_name: str, args: tuple,
                                 kwargs: dict, ctx: dict = None):
        """Generator twin of ``handle_request`` (reference:
        ``serve/_private/replica.py:391-543`` handle_request_streaming):
        items from the user generator stream back to the caller one at a
        time over the core streaming-generator transport instead of
        buffering the whole response.

        Chunked-decode mode: handlers on the fused decode path yield
        per-chunk token SLICES (one list per device dispatch) rather
        than per-token items. Those stream through unchanged — one
        stream item per chunk — unless the caller sets
        ``ctx["flatten_chunks"]``, which re-yields each list/tuple item
        element-wise so per-token consumers keep token granularity
        without a second code path on the replica.

        Mid-stream failover (``ctx["resume_from"] = n``): the caller
        already holds the first ``n`` tokens of this deterministic
        stream, delivered by a replica that has since died. Engine-fed
        streams suppress the replayed prefix INSIDE the engine (the
        continuous-batching wrapper forwards the count into
        ``engine.submit``); any other handler gets the generic fallback
        — the replica drops the first ``n`` tokens of the replayed
        stream before they reach the wire."""
        deadline = self._admit(method_name, ctx)
        token = None
        if ctx and ctx.get("multiplexed_model_id"):
            from .multiplex import _request_model_id

            token = _request_model_id.set(ctx["multiplexed_model_id"])
        resume_from = int((ctx or {}).get(RESUME_FROM_KEY, 0) or 0)
        dl_token = _request_deadline.set(deadline)
        dep_token = _request_deployment.set(self.deployment_name)
        rid_token = _request_id.set(
            (ctx or {}).get(REQUEST_ID_KEY) or None)
        rf_token = _request_resume_from.set(resume_from)
        # Decode hop of a disaggregated dispatch (ISSUE 14): the
        # continuous-batching wrapper imports the shipped KV instead of
        # prefilling locally (or falls back to a local prefill when the
        # payload is gone/corrupt — token-identical by determinism).
        ho_token = _request_handoff.set((ctx or {}).get(HANDOFF_KEY))
        try:
            self._pre_invoke(method_name, deadline)
            # user_code stage span covers the ITERATION of the handler
            # (the whole stream), mirroring _traced_gen's contract for
            # generator tasks; per-dispatch chunk spans nest inside it.
            with tracing.span("user_code", kind="stage",
                              deployment=self.deployment_name,
                              method=method_name):
                out = self._invoke_user(method_name, args, kwargs)
                # Continuous-engine streams (@serve.batch(continuous=
                # True)) carry their own per-dispatch decode.chunk spans
                # with real device timing — recording pull-wait spans
                # here too would double-count the stage.
                engine_fed = bool(getattr(out, "__rt_engine_stream__",
                                          False))
                items = self._traced_items(self._normalize_stream(out),
                                           engine_fed=engine_fed)
                if resume_from and not engine_fed:
                    items = self._suppress_prefix(items, resume_from)
                if ctx and ctx.get("flatten_chunks"):
                    for item in items:
                        if isinstance(item, (list, tuple)):
                            yield from item
                        elif getattr(item, "ndim", 0):
                            # ndarray chunk slice (e.g. generate_chunked's
                            # [B, j]): row-major flatten to scalars — for
                            # the B == 1 serving case that is exactly
                            # per-token order.
                            yield from item.ravel().tolist()
                        else:
                            yield item
                else:
                    yield from items
        finally:
            _request_handoff.reset(ho_token)
            _request_resume_from.reset(rf_token)
            _request_id.reset(rid_token)
            _request_deployment.reset(dep_token)
            _request_deadline.reset(dl_token)
            if token is not None:
                from .multiplex import _request_model_id

                _request_model_id.reset(token)
            with self._lock:
                self._ongoing -= 1
                if self._ongoing == 0:
                    self._idle_cond.notify_all()

    @staticmethod
    def _suppress_prefix(items, n: int):
        """Replay-token suppression for non-engine streams: drop the
        first ``n`` TOKENS — counted by the same
        :func:`~.request.stream_item_width` contract the caller-side
        generator records deliveries with — from a deterministically
        replayed stream, so a resumed caller never sees a duplicate.
        The chunk containing the boundary is trimmed, not dropped."""
        from .request import stream_item_width

        for item in items:
            if n <= 0:
                yield item
                continue
            w = stream_item_width(item)
            if w <= n:
                n -= w
                continue
            if isinstance(item, (list, tuple)):
                yield list(item[n:])
            else:
                yield item.reshape(-1)[n:]
            n = 0

    @staticmethod
    def _traced_items(items, engine_fed: bool = False):
        """Pass-through iterator that records one stage span per stream
        item when the request is traced: ``decode.chunk`` for chunk
        slices (list/tuple/array — one fused device dispatch each),
        ``stream.item`` for scalar items. The span covers the time this
        replica spent PRODUCING the item (the pull from the user
        generator), which for chunked decode is exactly one dispatch.
        ``engine_fed`` streams skip span recording entirely: the decode
        engine records one authoritative ``decode.chunk`` span per fused
        dispatch on its driver thread."""
        from ..util.tracing import current_context, record_span

        if engine_fed or current_context() is None:
            yield from items  # untraced / engine-traced: no overhead
            return
        idx = 0
        while True:
            t0 = time.time()
            try:
                item = next(items)
            except StopIteration:
                return
            chunk = isinstance(item, (list, tuple)) or \
                bool(getattr(item, "ndim", 0))
            if isinstance(item, (list, tuple)):
                width = len(item)
            elif getattr(item, "ndim", 0):
                # ndarray chunk slice [B, j]: every element is a token
                # (len() would report B, undercounting by the chunk
                # factor the span exists to record).
                width = int(getattr(item, "size", 1))
            else:
                width = 1
            record_span("decode.chunk" if chunk else "stream.item",
                        t0, index=idx, tokens=width)
            idx += 1
            yield item

    def _invoke_user(self, method_name: str, args: tuple, kwargs: dict):
        """Call the user callable and return its RAW result (generator,
        coroutine, engine stream, plain value) without starting any
        iteration — the caller inspects it before normalization."""
        if inspect.isfunction(self._user) or inspect.isbuiltin(self._user):
            method = self._user
        else:
            method = getattr(self._user, method_name)
        return method(*args, **kwargs)

    def _normalize_stream(self, out):
        """Normalize one raw handler result into a sync iterator."""
        if inspect.isasyncgen(out):
            # Drain the async generator on a private loop; the
            # replica's concurrency model is threads, not one loop.
            loop = asyncio.new_event_loop()
            try:
                while True:
                    try:
                        yield loop.run_until_complete(out.__anext__())
                    except StopAsyncIteration:
                        break
            finally:
                # Abandoned stream: run the handler's cleanup
                # (try/finally, context managers) before the loop
                # goes away — GC would otherwise try to aclose on a
                # closed loop.
                try:
                    loop.run_until_complete(out.aclose())
                except Exception:  # noqa: BLE001 - cleanup best-effort
                    pass
                loop.close()
        elif inspect.isgenerator(out) or hasattr(out, "__next__"):
            yield from out
        else:
            if inspect.iscoroutine(out):
                out = asyncio.run(out)
            # Non-generator handler called in streaming mode: a
            # single-item stream keeps the caller's contract.
            yield out

    # ---------------------------------------------------------- control plane
    def _engines(self) -> list:
        """Every DecodeEngine the user callable constructed (the units
        the supervisor, drain, and chaos fault points operate on)."""
        from .engine import DecodeEngine

        if not hasattr(self._user, "__dict__"):
            return []
        return [v for v in vars(self._user).values()
                if isinstance(v, DecodeEngine)]

    def _apply_engine_config(self, engine_config: dict):
        """Push the deployment schema's ``engine:`` block (paged-KV +
        speculative-decoding knobs) into every DecodeEngine the user
        callable constructed — applied right after ``__init__``, before
        any traffic, which is the only window an engine may be repaged
        or given a drafter in."""
        for eng in self._engines():
            eng.apply_config(**engine_config)

    def get_metrics(self) -> Dict[str, Any]:
        with self._lock:
            out = {"replica_id": self.replica_id, "ongoing": self._ongoing,
                   "total": self._total,
                   "expired": self._expired,
                   "overloaded": self._overloaded,
                   "draining": self._draining,
                   "drains": self._drains,
                   "uptime": time.time() - self._start_time}
        try:
            engines = self._engines()
            if engines:
                out["engines"] = [e.stats() for e in engines]
        except Exception:  # noqa: BLE001 - metrics stay useful without it
            pass
        return out

    def claim_handoff(self, lease_id: str, epoch: int) -> bool:
        """Release one handoff lease on this (prefill) replica's
        engines — the decode side imported the shipped KV, so the pin
        on the shipped object may drop before the lease expires. Fired
        by the router after the decode hop's first item; an unknown or
        already-swept lease returns False, which is fine (the importer
        holds its bytes)."""
        return any(eng.claim_handoff(lease_id, epoch)
                   for eng in self._engines())

    def inject_engine_fault(self, kind: str = "driver_die",
                            at_tokens: int = 0,
                            wedge_s: float = 0.0) -> int:
        """Arm one chaos fault (driver death / wedge / process kill at
        token N) on every DecodeEngine of this replica — the fault
        points behind ``tests/test_serve_chaos.py`` and
        ``benchmarks/serve_gpt.py --chaos``. Returns how many engines
        were armed. Testing only."""
        engines = self._engines()
        for eng in engines:
            eng.inject_fault(kind, at_tokens=at_tokens, wedge_s=wedge_s)
        return len(engines)

    def set_fault_injection(self, latency_s: float = 0.0,
                            error_rate: float = 0.0) -> bool:
        """Arm the per-request fault-injection hook (testing only): every
        admitted request is logged, then delayed ``latency_s`` and failed
        with probability ``error_rate`` before user code runs."""
        with self._lock:
            self._fault = {"latency_s": float(latency_s),
                           "error_rate": float(error_rate)}
            self._invocations = []
        return True

    def clear_fault_injection(self) -> bool:
        with self._lock:
            self._fault = {}
        return True

    def get_invocation_log(self) -> list:
        """Invocation records ({method, start, deadline}) captured while
        fault injection is armed — the overload tests assert that no
        invocation STARTED after its request deadline."""
        with self._lock:
            return list(self._invocations)

    def get_node_id(self):
        """The node hosting this replica (locality routing hint)."""
        from ..core.worker import CoreWorker

        core = CoreWorker._current
        return getattr(core, "node_id", None) if core is not None else None

    def check_health(self) -> bool:
        # Engine driver supervision first (ISSUE 7): a dead or wedged
        # driver thread is restarted ONCE — its lanes fail with the
        # retryable EngineRestartError, so clients resume on another
        # replica — and the replica stays healthy. Only a REPEAT failure
        # reports unhealthy, escalating to controller-driven replica
        # replacement.
        for eng in self._engines():
            if not eng.supervise():
                return False
        fn = getattr(self._user, "check_health", None)
        if fn is not None:
            out = fn()
            if inspect.iscoroutine(out):
                out = asyncio.run(out)
            return bool(out) if out is not None else True
        return True

    def reconfigure(self, user_config: Any):
        fn = getattr(self._user, "reconfigure", None)
        if fn is not None:
            out = fn(user_config)
            if inspect.iscoroutine(out):
                asyncio.run(out)
        return True

    def drain(self, timeout_s: float = 5.0) -> bool:
        """Graceful shutdown (controller teardown / scale-down / health
        replacement path): stop admissions — new requests push back with
        the retryable :class:`ReplicaDrainingError` so routers re-pick —
        drain every DecodeEngine (queued requests fail retryably at
        once, running lanes finish, stragglers fail retryably at the
        deadline so clients resume elsewhere), then wait for the
        remaining in-flight requests. Returns True when everything
        finished inside the budget; False means stragglers were failed
        retryably. Idempotent. The drain counter/duration metrics are
        observed by the CONTROLLER around this RPC — a replica about to
        be killed may never ship its final metrics snapshot."""
        t0 = time.time()
        deadline = t0 + max(float(timeout_s), 0.0)
        with self._lock:
            self._draining = True
            self._drains += 1
            ongoing = self._ongoing
        _events.emit("replica.drain", replica=self.replica_id,
                     deployment=self.deployment_name, phase="begin",
                     ongoing=ongoing, timeout_s=float(timeout_s))
        for eng in self._engines():
            eng.drain(max(deadline - time.time(), 0.0))
        _events.emit("replica.drain", replica=self.replica_id,
                     deployment=self.deployment_name,
                     phase="engines_drained")
        # Condition wait, not a poll: the last finishing request
        # notifies, so an idle replica returns immediately and a busy
        # one wakes the moment its in-flight count hits zero.
        # rtsan RS104 audit (ISSUE 13): the wait is deadline-bounded
        # AND re-checks the predicate (_ongoing) each wake — a lost
        # notify degrades to the drain budget, never a hang; the only
        # lock held is the condition's own (_idle_cond shares _lock).
        with self._idle_cond:
            while self._ongoing and time.time() < deadline:
                self._idle_cond.wait(
                    timeout=max(deadline - time.time(), 0.0))
            clean = self._ongoing == 0
            stragglers = self._ongoing
        _events.emit("replica.drain", replica=self.replica_id,
                     deployment=self.deployment_name, phase="end",
                     clean=clean, stragglers=stragglers,
                     elapsed_s=round(time.time() - t0, 4))
        return clean


def _resolve_handles(app_name: str, obj):
    """Replace bound-deployment markers with live handles at init time
    (reference analogue: init-arg DAG resolution in
    ``serve/_private/deployment_graph_build.py``)."""
    from .handle import DeploymentHandle, _HandleMarker

    if isinstance(obj, _HandleMarker):
        return DeploymentHandle(app_name, obj.deployment_name)
    if isinstance(obj, tuple):
        return tuple(_resolve_handles(app_name, x) for x in obj)
    if isinstance(obj, list):
        return [_resolve_handles(app_name, x) for x in obj]
    if isinstance(obj, dict):
        return {k: _resolve_handles(app_name, v) for k, v in obj.items()}
    return obj

"""The deployment class the serving cells run: an ordinary
``@serve.deployment`` around a ``DecodeEngine`` behind
``@serve.batch(continuous=True)``, as a user of the system writes one.

It is the benchmark's, so that the one process which holds the chip can
do for the benchmark what nothing else can: make the weights from the
seed on the device, start and stop ``jax.profiler`` around a slice
counted in the engine's chunk launches, sample what its engine's driver
thread is doing, compare the served arithmetic with the plain
reference, and hand out the engine's counters. The trace's file is
written here and read elsewhere (``trace_reduce.reduce_in_child``).
Everything it reads from the program is public (``engine.stats()``)
except where a comment says otherwise.
"""
from __future__ import annotations

import os
import sys
import threading
import time


def seeded_params(arch, cfg, seed: int, init: dict):
    """Weights from the seed under ONE jit, on the device, in the type
    the program holds them in. The tree (names, shapes, types) and each
    leaf's standard deviation are the architecture's
    (``arch.param_shapes``, ``arch.leaf_std`` from the configuration
    file's ``init``); the values are drawn with the chip's hardware
    generator (the ``rbg`` key type), which makes 1.3 G values in a
    fraction of the time of the program's threefry-seeded init."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        arch.param_shapes(cfg))

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            std = arch.leaf_std(cfg, init, jax.tree_util.keystr(path),
                                leaf.shape)
            if std is None:
                out.append(jnp.ones(leaf.shape, leaf.dtype))
                continue
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                        * std).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    return jax.block_until_ready(jax.jit(make)(key))


def device_peak_bytes(stats: dict) -> int:
    """Peak memory of one chip as the runtime reports it: the
    allocator's ``peak_bytes_in_use`` (arrays: weights, cache, state)
    plus, where the backend keeps one, the pool it reserves for compiled
    programs' temporaries (``peak_bytes_reserved``), which the first
    figure does not include."""
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


class HostSampler:
    """What a thread is doing, every ``period_s``: the innermost frame
    inside ``anchor`` (a file name), as ``file.py:function``. Stamps are
    ``time.monotonic_ns()``."""

    def __init__(self, anchor: str, entry: str, period_s: float = 0.002):
        self.anchor, self.entry, self.period_s = anchor, entry, period_s
        self.samples = []
        self._stop = threading.Event()
        self._thread = None

    def _label(self, frame):
        inner, seen_entry = None, False
        while frame is not None:
            code = frame.f_code
            if code.co_filename.endswith(self.anchor):
                if inner is None:
                    inner = code.co_name
                if code.co_name == self.entry:
                    seen_entry = True
            frame = frame.f_back
        if not seen_entry:
            return None
        name = inner.replace("<", "_").replace(">", "_")
        return f"{os.path.basename(self.anchor)}:{name}"

    def _run(self):
        tid = None
        while not self._stop.is_set():
            frames = sys._current_frames()
            now = time.monotonic_ns()
            if tid is None or tid not in frames:
                tid = next((t for t, f in frames.items()
                            if t != threading.get_ident()
                            and self._label(f)), None)
            if tid is not None:
                lab = self._label(frames[tid])
                if lab:
                    self.samples.append((now, lab))
            del frames
            time.sleep(self.period_s)

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(2.0)
        return self.samples


def wait_slice(advanced, launches: int, until: float, period_s: float,
               clock=time.monotonic, sleep=time.sleep) -> str:
    """Wait for the end of a slice counted in launches: until
    ``advanced()``, the counter's advance since the slice began, has
    reached ``launches`` (returns ``"launches"``), or the clock has
    reached ``until`` (``"seconds"``), whichever comes first. The
    counter is read every ``period_s`` and no more often, so the slice
    ends within one reading of either."""
    while True:
        sleep(period_s)
        if advanced() >= launches:
            return "launches"
        if clock() >= until:
            return "seconds"


class Tracer:
    """``jax.profiler`` around a slice, in the process that holds the
    chip, with the host sampler beside it and one marker event that
    ties the host's clock to the trace's. The file is written here and
    not read: :meth:`handoff` is what only this process knows, and
    ``trace_reduce.reduce_handoff`` makes the numbers from it and the
    file, in this process once its window is over (training,
    :meth:`result`) or in a child of the driver (serving, whose
    replica then holds the interpreter for nothing after
    ``stop_trace()``).

    A slice is as long as its caller makes it (``start()`` ...
    ``stop()``: training's three steps, a mix without
    ``trace_launches``) or is counted in launches: given ``counter``
    (the engine's chunk dispatches so far), ``start(launches,
    limit_s)`` sets a watcher beside the trace that stops it when the
    counter has advanced by ``launches`` or after ``limit_s`` seconds,
    whichever comes first; ``stop()`` then waits for the watcher. What
    cost a traced slice follows the device EVENTS in it, which follow
    the launches and not the seconds: counted so, a program whose step
    is n times shorter is traced for a slice n times shorter that
    holds the same events. What tracing cost is part of the result
    (``run["trace"]["cost"]``): the seconds ``stop_trace()`` took (a
    serving cell calls it inside the window), the slice as it was
    (``slice_s``, ``launches``, ``ended_by``), the file's size, the
    device events loaded and the seconds of loading and reducing."""

    #: how often the watcher reads the counter
    WATCH_PERIOD_S = 0.1

    def __init__(self, log_dir: str, anchor: str, entry: str,
                 counter=None):
        self.log_dir, self.anchor, self.entry = log_dir, anchor, entry
        self.counter = counter
        self.sampler = self.samples = None
        self.sync_host_ns = None
        self.t_start = self.t_stop = self.stop_s = None
        self.launches = self.ended_by = None
        self._n0 = self._watcher = self._watch_error = None

    def start(self, launches: int = None, limit_s: float = None):
        import jax

        # the profiler's defaults, Python tracer included: without it
        # a trace is a quarter to a half smaller and device_idle_pct
        # reads 0.6-1.5 points lower (PERF.md section 6, PR 29), so
        # turning it off is a step in every traced metric's history
        jax.profiler.start_trace(self.log_dir)
        self.t_start = time.monotonic_ns()
        with jax.profiler.TraceAnnotation("perfbench_sync"):
            self.sync_host_ns = time.monotonic_ns()
        if self.counter is not None:
            self._n0 = self.counter()
        self.sampler = HostSampler(self.anchor, self.entry)
        self.sampler.start()
        if launches:
            self._watcher = threading.Thread(
                target=self._watch, daemon=True,
                args=(int(launches), self.t_start / 1e9 + limit_s))
            self._watcher.start()

    def _watch(self, launches: int, until: float):
        try:
            self._stop(wait_slice(lambda: self.counter() - self._n0,
                                  launches, until, self.WATCH_PERIOD_S))
        except Exception as e:  # noqa: BLE001 - raised by stop()
            self._watch_error = e

    def stop(self) -> dict:
        """Stop tracing and sampling, or wait for the watcher of a
        slice counted in launches to have done so; the file is written,
        not read. Returns the slice as it was."""
        if self._watcher is None:
            self._stop("seconds" if self.counter is not None else None)
        else:
            self._watcher.join()
            if self._watch_error is not None:
                raise self._watch_error
        return self.slice()

    def _stop(self, ended_by):
        import jax

        self.samples = self.sampler.stop()
        self.t_stop = time.monotonic_ns()
        if self.counter is not None:
            self.launches = self.counter() - self._n0
        self.ended_by = ended_by
        jax.profiler.stop_trace()
        self.stop_s = (time.monotonic_ns() - self.t_stop) / 1e9

    def slice(self) -> dict:
        """The slice as it was, on the host's monotonic clock in
        seconds (one clock for every process of a machine)."""
        return {"t_start_s": self.t_start / 1e9,
                "t_stop_s": self.t_stop / 1e9,
                "mid_s": (self.t_start + self.t_stop) / 2e9,
                "slice_s": (self.t_stop - self.t_start) / 1e9,
                "launches": self.launches, "ended_by": self.ended_by,
                "trace_stop_s": self.stop_s}

    def handoff(self) -> dict:
        """What only the tracing process knows, for
        ``trace_reduce.reduce_handoff``: the slice, and what ties the
        host's clock to the trace's. Reads no file."""
        return dict(self.slice(), log_dir=self.log_dir,
                    samples=self.samples, t_start=self.t_start,
                    t_stop=self.t_stop, sync_host_ns=self.sync_host_ns)

    def result(self, describe: bool = False) -> dict:
        """Reduce the trace here (seconds of Python: only once the
        measured window is over, and never inside a replica)."""
        import trace_reduce

        return trace_reduce.reduce_handoff(
            dict(self.handoff(), describe=describe))


def make_deployment(conf: dict, seed: int, require_tpu: bool,
                    trace_dir: str):
    """The deployment for one serving configuration file. Deployment
    settings an operator sets come from ``conf["deployment"]``."""
    from ray_tpu import serve

    dep = conf["deployment"]

    @serve.deployment(
        num_replicas=1,
        max_ongoing_requests=dep["max_ongoing_requests"],
        max_queued_requests=dep["max_queued_requests"],
        health_check_period_s=dep["health_check_period_s"],
        ray_actor_options={"num_tpus": 1 if require_tpu else 0})
    class PerfGPT:
        def __init__(self):
            t0 = time.monotonic()
            import perf_harness
            from ray_tpu._private import chip

            self.device = chip.require_tpu() if require_tpu \
                else chip.device_summary()
            print(f"[pid {os.getpid()}] platform="
                  f"{self.device['platform']} device_kind="
                  f"{self.device['kind']!r} count={self.device['count']}",
                  flush=True)
            self.arch = perf_harness.load_architecture(conf)
            self.cfg = self.arch.model_cfg(conf)
            t1 = time.monotonic()
            params = seeded_params(self.arch, self.cfg, seed,
                                   conf["init"])
            t2 = time.monotonic()
            self.engine = self.arch.make_engine(params, self.cfg, conf)
            self.timing = {"import_s": t1 - t0, "weights_s": t2 - t1,
                           "engine_s": time.monotonic() - t2}
            self.arrivals = {}
            self.tracer = None

        @serve.batch(continuous=True)
        def decode(self, request):
            return self.engine, {"prompt": request["prompt"],
                                 "max_new": request["max_new"]}

        def __call__(self, request):
            rec = [time.monotonic(), None]
            self.arrivals[request["rid"]] = rec
            return _Stamped(self.decode(request), rec)

        def report(self) -> dict:
            import jax

            mem = [d.memory_stats() or {} for d in jax.devices()]
            return {"device": self.device, "pid": os.getpid(),
                    "timing": self.timing,
                    "stats": self.engine.stats(),
                    "t": time.monotonic(),
                    "memory_peak_bytes": max(device_peak_bytes(m)
                                             for m in mem),
                    "memory_stats": {k: int(v) for k, v in mem[0].items()
                                     if isinstance(v, (int, float))}}

        def arrivals_log(self) -> dict:
            """rid -> [arrival at the replica, first slice out of the
            engine], monotonic seconds."""
            return dict(self.arrivals)

        def reference_check(self, n_prompt: int, n_steps: int,
                            served=None) -> dict:
            import perf_reference_check

            return perf_reference_check.serve_check(
                self.arch, self.engine, self.cfg, conf, seed, n_prompt,
                n_steps, served)

        def trace_start(self, launches: int = None,
                        limit_s: float = None) -> bool:
            """Begin the traced slice. With ``launches`` it ends by
            itself (``Tracer``), watched through the engine's public
            counter of chunk dispatches alone."""
            import program_names

            self.tracer = Tracer(
                trace_dir, program_names.ENGINE_FILE,
                program_names.DRIVER_ENTRY,
                counter=lambda: self.engine.stats()["dispatches"])
            self.tracer.start(launches, limit_s)
            return True

        def trace_stop(self) -> dict:
            """End the slice, or wait for its end where it is counted
            in launches: the slice as it was."""
            return self.tracer.stop()

        def trace_result(self) -> dict:
            """What only the replica knows of the slice. No file is
            read here: the driver has the trace reduced in a child of
            its own once the window has closed."""
            tracer, self.tracer = self.tracer, None
            return tracer.handoff()

    return PerfGPT


class _Stamped:
    """The engine's stream, with the time its first slice left the
    engine noted. Keeps the marker the replica reads off engine
    streams."""

    __rt_engine_stream__ = True

    def __init__(self, inner, rec):
        self._inner, self._rec = inner, rec

    def __iter__(self):
        return self

    def __next__(self):
        item = next(self._inner)
        if self._rec[1] is None:
            self._rec[1] = time.monotonic()
        return item

    def close(self):
        self._inner.close()

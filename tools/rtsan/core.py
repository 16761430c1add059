"""rtsan core: the runtime sanitizer.

One :class:`Sanitizer` per process. :func:`enable` monkeypatches the
``threading.Lock`` / ``RLock`` / ``Condition`` factories (repo-created
locks become :class:`SanLock` wrappers; stdlib-internal locks — Events,
queues, futures — are left raw, decided by the factory caller's file),
patches ``time.sleep`` and ``threading.Thread.start``, wraps the
``jit_*`` program factories in ``ray_tpu.models.gpt_decode``, and
instruments every method carrying an rtlint ``owner=`` / ``holds=`` /
``entry=`` annotation (read through THE same loader rtlint uses,
:mod:`tools.rtlint.annotations`). ``enabled`` is the patch state;
``active`` gates all recording and enforcement, so a dormant sanitizer
costs one flag check per operation and :func:`disable` restores every
identity (pinned by the no-op test).

Checks:

=======  ===========================================================
RS101    lock-order cycle: the global acquisition-order graph gained
         an edge closing a cycle — a potential ABBA deadlock,
         reported with both acquisition stacks even if the deadlock
         never fires in this run
RS102    a ``holds=<lock>`` method entered without ``self.<lock>``
         held (raises), or naming an attribute that does not exist
         (hard error — the contract is unverifiable)
RS103    an ``owner=driver`` method called from a thread that is not
         the registered driver (raises); ``entry=driver`` methods
         (re)register their caller, and a dead owner is rebound
RS104    blocking while holding a repo lock: ``time.sleep`` under a
         lock, ``Condition.wait`` with no timeout (or while holding
         OTHER locks — only the condition's own lock is released),
         and device dispatch (a ``jit_*`` program invocation) under a
         lock; per-site hold times are histogrammed either way
RS105    a thread started inside a :func:`Sanitizer.thread_watch`
         window (engine/drafter/pipeline start sites) still alive at
         its end — a leaked driver
=======  ===========================================================

Findings ride rtlint's machinery: the same :class:`Finding` model and
line-number-free baseline keys (``tools/rtsan/baseline.json``, shipped
EMPTY), with inline suppressions spelled ``# rtsan: disable=RSxxx
<why>`` at the reported line (or the line above / the enclosing def),
resolved through :class:`tools.rtlint.core.Module` with
``tag="rtsan"``. RS102/RS103 raise :class:`RTSanViolation` at the
violation site (a broken contract is a bug NOW); RS101/RS104/RS105 are
recorded and fail the suite at the conftest gate.
"""
from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
import traceback
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from ..rtlint.annotations import load_annotations, parse_directives
from ..rtlint.core import Finding, Module, load_baseline

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json")

#: Modules whose annotated methods are instrumented by default — the
#: engine/controller/pipeline surfaces whose contracts rtlint checks
#: statically. Import failures are gated (a stripped environment just
#: sanitizes less).
DEFAULT_MODULES = (
    "ray_tpu.serve.engine",
    "ray_tpu.serve.draft",
    "ray_tpu.serve.handoff",
    "ray_tpu.serve.autoscaler",
    "ray_tpu.serve._replica",
    "ray_tpu.serve._controller",
    "ray_tpu.data.llm",
    "ray_tpu.data.executor",
    "ray_tpu._private.object_store",
)

#: Thread start-sites the leak watch flags by default: the driver
#: threads of the sanitized subsystems. Infra threads (head, core
#: worker, reaper) are long-lived by design and out of scope.
DEFAULT_THREAD_TARGETS = (
    "ray_tpu/serve/engine.py",
    "ray_tpu/serve/draft.py",
    "ray_tpu/data/llm.py",
)

# Originals captured at import time, BEFORE any patching.
_ORIG_LOCK = threading.Lock
_ORIG_RLOCK = threading.RLock
_ORIG_CONDITION = threading.Condition
_ORIG_SLEEP = time.sleep
_ORIG_THREAD_START = threading.Thread.start

_THIS_FILE = os.path.abspath(__file__)
_STDLIB_SUFFIXES = (os.sep + "threading.py", os.sep + "queue.py")


def annotation_coverage(modules=DEFAULT_MODULES) -> Dict[str, dict]:
    """Static annotation-coverage summary (ISSUE 15 satellite): how
    much of the sanitized driver surface actually carries the
    ``owner=`` / ``holds=`` / ``entry=`` contracts that rtlint
    (RT101/RT102/RT108/RT110) checks statically and this sanitizer
    enforces at runtime. An unannotated driver method or an unnamed
    lock is a gap BOTH tools are blind to, so the fraction is the
    visible size of the shared contract set.

    Per module: ``methods`` / ``annotated`` count the methods of
    driver-owned classes (>= 1 ``owner=``/``entry=`` method) and how
    many of them carry any contract; ``locks`` / ``locks_with_holds``
    count the lock-ish ``self.<attr>`` assignments (``lock|cond|
    mutex``) and how many are named by at least one ``holds=``.
    ``totals`` aggregates with the two fractions. Purely source-based
    (``find_spec``, no import), so it works without :func:`enable`."""
    import ast as _ast
    import importlib.util

    from ..rtlint.annotations import LOCKISH_RE as lockish
    out: Dict[str, dict] = {"modules": {}, "totals": {}}
    for modname in modules:
        try:
            spec = importlib.util.find_spec(modname)
            path = getattr(spec, "origin", None)
            if not path or not os.path.exists(path):
                continue
            with open(path, encoding="utf-8") as f:
                src = f.read()
            anns = load_annotations(src)
            tree = _ast.parse(src)
        except Exception:  # noqa: BLE001 - coverage is best-effort
            continue
        contracts = {(a.cls, a.name) for a in anns}
        driver_classes = {a.cls for a in anns
                          if a.owner or a.entry}
        holds_named = {h for a in anns for h in a.holds}
        methods = annotated = 0
        locks = set()

        def classes(node, prefix=""):
            for child in _ast.iter_child_nodes(node):
                if isinstance(child, _ast.ClassDef):
                    yield f"{prefix}{child.name}", child
                    yield from classes(child,
                                       f"{prefix}{child.name}.")
                elif isinstance(child, (_ast.FunctionDef,
                                        _ast.AsyncFunctionDef)):
                    yield from classes(child, prefix)

        for qual, cls in classes(tree):
            names = [n.name for n in cls.body
                     if isinstance(n, (_ast.FunctionDef,
                                       _ast.AsyncFunctionDef))]
            if qual in driver_classes:
                methods += len(names)
                annotated += sum((qual, n) in contracts for n in names)
        for node in _ast.walk(tree):
            targets = []
            if isinstance(node, _ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, _ast.AnnAssign):
                targets = [node.target]
            for t in targets:
                while isinstance(t, (_ast.Tuple, _ast.List)) and t.elts:
                    t = t.elts[0]
                if isinstance(t, _ast.Attribute) and \
                        isinstance(t.value, _ast.Name) and \
                        t.value.id == "self" and lockish.search(t.attr):
                    locks.add(t.attr)
        covered = len(locks & holds_named)
        out["modules"][modname] = {
            "methods": methods, "annotated": annotated,
            "locks": len(locks), "locks_with_holds": covered,
        }
    out["totals"] = coverage_totals(out["modules"].values())
    return out


def coverage_totals(rows) -> dict:
    """Aggregate per-module coverage rows into the ``totals`` block —
    THE one implementation, shared by single-process snapshots and the
    CLI's multi-artifact merge so they can never disagree."""
    rows = list(rows)
    methods = sum(r["methods"] for r in rows)
    annotated = sum(r["annotated"] for r in rows)
    locks = sum(r["locks"] for r in rows)
    covered = sum(r["locks_with_holds"] for r in rows)
    return {
        "methods": methods, "annotated": annotated,
        "locks": locks, "locks_with_holds": covered,
        "method_fraction": round(annotated / methods, 3)
        if methods else 1.0,
        "lock_fraction": round(covered / locks, 3) if locks else 1.0,
    }


class RTSanViolation(RuntimeError):
    """A broken owner=/holds= contract, raised at the violation site."""


_MISSING = object()


def _caller_site() -> Optional[Tuple[str, int]]:
    """(abspath, lineno) of the nearest frame outside rtsan itself and
    the threading machinery."""
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        if fn != _THIS_FILE and not fn.endswith(_STDLIB_SUFFIXES):
            return os.path.abspath(fn), f.f_lineno
        f = f.f_back
    return None


class _TLS(threading.local):
    def __init__(self):
        self.held: list = []     # [_Held] in acquisition order


class _Held:
    __slots__ = ("lock", "t0", "site")

    def __init__(self, lock, t0, site):
        self.lock = lock
        self.t0 = t0
        self.site = site         # "path:line" of the acquire call


#: Hold-time histogram bucket upper bounds (seconds); the last bucket
#: is unbounded.
HOLD_BUCKETS = (0.001, 0.01, 0.1, 1.0)


class SanLock:
    """Instrumented lock: forwards to a real ``threading.Lock`` /
    ``RLock`` while feeding the sanitizer's acquisition-order graph,
    per-thread held stack, and hold-time histogram. Implements the
    ``_is_owned`` / ``_release_save`` / ``_acquire_restore`` protocol
    so ``threading.Condition`` composes (and tracking follows the wait
    through the release/reacquire)."""

    def __init__(self, inner, site: str, san: "Sanitizer",
                 reentrant: bool):
        self._inner = inner
        self._reentrant = reentrant
        self._san = san
        self._owner: Optional[int] = None   # thread ident
        self._count = 0
        self.site = site       # creation site "relpath:line"
        self.name: Optional[str] = None     # set by holds= resolution

    # ------------------------------------------------------------- protocol
    def acquire(self, blocking: bool = True, timeout: float = -1):
        me = threading.get_ident()
        if self._reentrant and self._owner == me:
            got = self._inner.acquire(blocking, timeout)
            if got:
                self._count += 1
            return got
        if blocking:
            # Only BLOCKING acquires feed the lock-order graph: a
            # trylock-and-bail (blocking=False) cannot participate in a
            # deadlock by construction, and recording it would turn the
            # repo's drain patterns into false RS101 cycles.
            self._san.note_acquire(self)
        got = self._inner.acquire(blocking, timeout)
        if got:
            self._owner = me
            self._count = 1
            self._san.note_acquired(self)
        return got

    def release(self):
        if self._reentrant and self._count > 1:
            self._count -= 1
            self._inner.release()
            return
        self._owner = None
        self._count = 0
        self._san.note_released(self)
        self._inner.release()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()

    def locked(self) -> bool:
        try:
            return self._inner.locked()
        except AttributeError:   # RLock has no locked() on this python
            return self._owner is not None

    def held_by_current(self) -> bool:
        return self._owner == threading.get_ident()

    # Condition integration: release/reacquire fully (RLock recursion
    # included) while keeping the sanitizer's held stack truthful.
    def _is_owned(self) -> bool:
        return self.held_by_current()

    def _release_save(self):
        state = (self._count, self._owner)
        self._owner = None
        self._count = 0
        self._san.note_released(self)
        if self._reentrant:
            inner_state = self._inner._release_save()
        else:
            self._inner.release()
            inner_state = None
        return state + (inner_state,)

    def _acquire_restore(self, saved):
        count, owner, inner_state = saved
        if self._reentrant:
            self._inner._acquire_restore(inner_state)
        else:
            self._inner.acquire()
        self._count = count
        self._owner = owner
        self._san.note_acquired(self)

    def __repr__(self):
        return (f"<SanLock {'R' if self._reentrant else ''}"
                f"{self.name or self.site} inner={self._inner!r}>")


class SanCondition(_ORIG_CONDITION):
    """Instrumented condition: its lock is (or wraps into) a SanLock,
    so acquisition tracking rides the normal lock protocol; ``wait``
    additionally flags timeout-less waits and waits that still hold
    OTHER locks (RS104) — only the condition's own lock is released
    while parked."""

    def __init__(self, lock, site: str, san: "Sanitizer"):
        super().__init__(lock)
        self._san_site = site
        self._san = san

    def wait(self, timeout=None):
        san = self._san
        if san.active:
            site = _caller_site()
            if timeout is None:
                san.record(
                    "RS104", site,
                    f"timeout-less Condition.wait on the condition "
                    f"created at {self._san_site} — an un-notified (or "
                    f"lost-wakeup) wait parks this thread forever; "
                    f"bound it with a timeout and re-check the "
                    f"predicate in a loop",
                    symbol=f"cond_wait_timeoutless.{self._san_site}")
            others = [h for h in san.tls.held if h.lock is not self._lock]
            if others:
                held = ", ".join(h.lock.name or h.lock.site
                                 for h in others)
                san.record(
                    "RS104", site,
                    f"Condition.wait while still holding [{held}] — "
                    f"wait releases ONLY the condition's own lock "
                    f"({self._san_site}); everything else stays held "
                    f"for the full wait",
                    symbol=f"cond_wait_holding.{self._san_site}")
        return super().wait(timeout)


class _DispatchFn:
    """Wrapper for one compiled jit program: flags invocation while a
    repo lock is held (RS104 — device dispatch under an engine or
    controller lock serializes everyone behind a device-speed wait).
    Attribute access (``_cache_size`` etc.) delegates to the program."""

    def __init__(self, fn, factory_name: str, san: "Sanitizer"):
        self._fn = fn
        self._factory_name = factory_name
        self._san = san

    def __call__(self, *args, **kwargs):
        san = self._san
        if san.active and san.tls.held:
            held = ", ".join(h.lock.name or h.lock.site
                             for h in san.tls.held)
            site = _caller_site()
            san.record(
                "RS104", site,
                f"device dispatch ({self._factory_name} program) while "
                f"holding [{held}] — a dispatch can block for a full "
                f"device step (or a first-call compile); never hold an "
                f"engine/controller lock across it",
                symbol=f"dispatch_under_lock.{self._factory_name}")
        return self._fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)


class _DispatchFactory:
    """Wrapper for an ``lru_cache``'d ``jit_*`` factory: returns the
    SAME :class:`_DispatchFn` per underlying program, so identity-based
    program counting (``factory(...).cache_info()``,
    ``fn._cache_size()``) keeps working."""

    __rtsan__ = True

    def __init__(self, orig, name: str, san: "Sanitizer"):
        self._orig = orig
        self._name = name
        self._san = san
        # id(fn) -> (fn, wrapper); holding fn keeps the id stable.
        self._wrappers: Dict[int, tuple] = {}

    def __call__(self, *args, **kwargs):
        fn = self._orig(*args, **kwargs)
        ent = self._wrappers.get(id(fn))
        if ent is None or ent[0] is not fn:
            if len(self._wrappers) >= 256:
                # The strong refs here would otherwise pin every
                # lru-evicted program alive forever; identity only
                # matters between consecutive factory calls, so a rare
                # wholesale reset is safe (wrappers rebuild on demand
                # and delegate to the same underlying programs).
                self._wrappers.clear()
            ent = (fn, _DispatchFn(fn, self._name, self._san))
            self._wrappers[id(fn)] = ent
        return ent[1]

    def __getattr__(self, item):
        return getattr(self._orig, item)


class Sanitizer:
    """Per-process sanitizer state. Use the module-level singleton via
    :func:`tools.rtsan.enable`."""

    def __init__(self):
        self.enabled = False
        self.active = False
        self.tls = _TLS()
        self._mu = _ORIG_RLOCK()          # raw: never self-instrumented
        self.roots = [REPO_ROOT] + [
            r for r in os.environ.get("RT_SAN_ROOTS", "").split(":") if r]
        self.findings: List[Finding] = []
        self._finding_keys: set = set()
        self.suppressed: List[dict] = []
        # (site_a, site_b) -> {count, acquire_stack, acquire_site}
        self.edges: Dict[Tuple[str, str], dict] = {}
        self._succ: Dict[str, set] = {}
        self._cycles_seen: set = set()
        # lock site -> {name, count, total_s, max_s, buckets[...]}
        self.holds: Dict[str, dict] = {}
        self.thread_targets = tuple(DEFAULT_THREAD_TARGETS)
        self.thread_allow: list = []
        self._modules_cache: Dict[str, Optional[Module]] = {}
        self._seen_modules: set = set()
        self._instrumented: list = []     # (cls, attr, orig_fn)
        self._factory_patches: list = []  # (module, attr, orig)
        self._atexit_armed = False

    # -------------------------------------------------------------- plumbing
    def _in_roots(self, path: str) -> bool:
        return any(path.startswith(r + os.sep) or path == r
                   for r in self.roots)

    def _rel(self, path: str) -> str:
        for r in self.roots:
            if path.startswith(r + os.sep):
                return os.path.relpath(path, r).replace(os.sep, "/")
        return path.replace(os.sep, "/")

    def _suppressed_at(self, abspath: str, line: int, rule: str) -> bool:
        mod = self._modules_cache.get(abspath, False)
        if mod is False:
            mod = None
            try:
                with open(abspath, encoding="utf-8") as f:
                    src = f.read()
                mod = Module(abspath, self._rel(abspath), src,
                             tag="rtsan")
            except (OSError, SyntaxError, UnicodeDecodeError):
                pass
            self._modules_cache[abspath] = mod
        return mod is not None and mod.suppresses(line, rule)

    def record(self, rule: str, site: Optional[Tuple[str, int]],
               message: str, symbol: str,
               raise_violation: bool = False) -> Optional[Finding]:
        """Register one finding (suppression- and dedup-checked); with
        ``raise_violation`` also raises :class:`RTSanViolation` —
        contract breaks (RS102/RS103) are bugs at the call site, not
        just report lines."""
        path, line = site if site else ("<unknown>", 0)
        if path != "<unknown>" and self._suppressed_at(path, line, rule):
            with self._mu:
                self.suppressed.append({
                    "rule": rule, "path": self._rel(path), "line": line,
                    "symbol": symbol})
            return None
        f = Finding(self._rel(path), line, rule, message, symbol)
        fresh = False
        with self._mu:
            if f.key not in self._finding_keys:
                self._finding_keys.add(f.key)
                self.findings.append(f)
                fresh = True
        if fresh and os.environ.get("RT_SAN_VERBOSE"):
            print(f"rtsan: {f.render()}", file=sys.stderr)
        if raise_violation:
            raise RTSanViolation(f.render())
        return f if fresh else None

    # ------------------------------------------------------------- lock hooks
    def note_acquire(self, lock: SanLock):
        """Pre-acquire: record acquisition-order edges from every held
        lock to this one; a NEW edge gets a stack and a cycle check."""
        if not self.active:
            return
        held = self.tls.held
        if not held:
            return
        b = lock.site
        cycle_msgs = []
        with self._mu:
            for h in held:
                a = h.lock.site
                if a == b or h.lock is lock:
                    continue
                e = self.edges.get((a, b))
                if e is not None:
                    e["count"] += 1
                    continue
                site = _caller_site()
                self.edges[(a, b)] = {
                    "count": 1,
                    "acquire_site": f"{self._rel(site[0])}:{site[1]}"
                    if site else "<unknown>",
                    "acquire_stack": "".join(
                        traceback.format_stack(sys._getframe(2),
                                               limit=16)),
                }
                self._succ.setdefault(a, set()).add(b)
                path = self._find_path(b, a)
                if path is not None:
                    cyc = tuple(sorted(set(path + [b])))
                    if cyc not in self._cycles_seen:
                        self._cycles_seen.add(cyc)
                        cycle_msgs.append((a, b, path, site))
        for a, b, path, site in cycle_msgs:
            chain = " -> ".join(path + [b])
            back_edge = self.edges.get((path[0], path[1])) if \
                len(path) > 1 else self.edges.get((b, a))
            back_stack = (back_edge or {}).get("acquire_stack", "")
            this_stack = self.edges[(a, b)]["acquire_stack"]
            self.record(
                "RS101", site,
                f"lock-order cycle: acquiring {b} while holding {a} "
                f"closes the cycle [{chain}] — two threads taking "
                f"these locks in opposite orders can deadlock even if "
                f"this run never does. Acquiring stack:\n{this_stack}"
                f"Opposite-order stack (first seen):\n{back_stack}",
                symbol=f"cycle.{'->'.join(sorted(set(path + [b])))}")

    def _find_path(self, src: str, dst: str) -> Optional[List[str]]:
        """BFS over the order graph; returns the site path src..dst."""
        prev = {src: None}
        frontier = [src]
        while frontier:
            nxt = []
            for n in frontier:
                for m in self._succ.get(n, ()):
                    if m in prev:
                        continue
                    prev[m] = n
                    if m == dst:
                        out = [m]
                        while prev[out[-1]] is not None:
                            out.append(prev[out[-1]])
                        return out[::-1]
                    nxt.append(m)
            frontier = nxt
        return None

    def note_acquired(self, lock: SanLock):
        if not self.active:
            return
        site = _caller_site()
        self.tls.held.append(_Held(
            lock, time.perf_counter(),
            f"{self._rel(site[0])}:{site[1]}" if site else "<unknown>"))

    def note_released(self, lock: SanLock):
        held = self.tls.held
        for i in range(len(held) - 1, -1, -1):
            if held[i].lock is lock:
                h = held.pop(i)
                if not self.active:
                    return
                dt = time.perf_counter() - h.t0
                with self._mu:
                    st = self.holds.get(lock.site)
                    if st is None:
                        st = self.holds[lock.site] = {
                            "name": lock.name, "count": 0,
                            "total_s": 0.0, "max_s": 0.0,
                            "buckets": [0] * (len(HOLD_BUCKETS) + 1)}
                    if lock.name and not st["name"]:
                        st["name"] = lock.name
                    st["count"] += 1
                    st["total_s"] += dt
                    st["max_s"] = max(st["max_s"], dt)
                    for j, ub in enumerate(HOLD_BUCKETS):
                        if dt < ub:
                            st["buckets"][j] += 1
                            break
                    else:
                        st["buckets"][-1] += 1
                return

    # ------------------------------------------------------------- factories
    def _lock_factory(self, orig, reentrant: bool):
        san = self

        def factory():
            inner = orig()
            f = sys._getframe(1)
            path = f.f_code.co_filename
            if not san._in_roots(os.path.abspath(path)):
                return inner
            site = f"{san._rel(os.path.abspath(path))}:{f.f_lineno}"
            return SanLock(inner, site, san, reentrant)

        factory.__rtsan__ = True
        factory.__orig__ = orig
        return factory

    def _condition_factory(self, orig_cond):
        san = self

        def factory(lock=None):
            f = sys._getframe(1)
            path = os.path.abspath(f.f_code.co_filename)
            if not san._in_roots(path):
                return orig_cond(lock)
            site = f"{san._rel(path)}:{f.f_lineno}"
            if lock is None:
                lock = SanLock(_ORIG_RLOCK(), site, san, True)
            return SanCondition(lock, site, san)

        factory.__rtsan__ = True
        factory.__orig__ = orig_cond
        return factory

    def _san_sleep(self, secs):
        if self.active and self.tls.held:
            held = ", ".join(h.lock.name or h.lock.site
                             for h in self.tls.held)
            self.record(
                "RS104", _caller_site(),
                f"time.sleep({secs!r}) while holding [{held}] — every "
                f"thread queued on those locks stalls for the whole "
                f"sleep; release first, or wait on a condition",
                symbol="sleep_under_lock")
        return _ORIG_SLEEP(secs)

    def _san_thread_start(self):
        san = self

        def start(t):
            if san.enabled:
                site = _caller_site()
                if site is not None:
                    try:
                        t._rtsan_start_site = \
                            f"{san._rel(site[0])}:{site[1]}"
                        t._rtsan_start_abs = site
                    except Exception:  # noqa: BLE001 - slots-only Thread
                        pass
            return _ORIG_THREAD_START(t)

        start.__rtsan__ = True
        return start

    # -------------------------------------------------------- instrumentation
    def _instrument_module(self, modname: str):
        """Wrap every annotated method of ``modname`` with the
        owner/holds contract check. Import failures are gated — an
        environment missing the module just sanitizes less."""
        import importlib

        try:
            mod = importlib.import_module(modname)
            path = getattr(mod, "__file__", None)
            if not path:
                return
            with open(path, encoding="utf-8") as f:
                src = f.read()
            anns = load_annotations(src)
        except Exception:  # noqa: BLE001 - gated: sanitize what imports
            return
        abspath = os.path.abspath(path)
        for ann in anns:
            if ann.cls is None:
                continue
            obj = mod
            for part in ann.cls.split("."):
                obj = getattr(obj, part, None)
                if obj is None:
                    break
            if not isinstance(obj, type):
                continue
            fn = obj.__dict__.get(ann.name)
            if not callable(fn) or getattr(fn, "__rtsan_contract__", None):
                continue
            setattr(obj, ann.name,
                    self._wrap_contract(fn, ann, abspath, obj.__name__))
            self._instrumented.append((obj, ann.name, fn))

    def _wrap_contract(self, fn, ann, abspath: str, clsname: str):
        import functools

        san = self
        holds = ann.holds
        is_owner = ann.owner == "driver"
        is_entry = ann.entry == "driver"
        site = (abspath, ann.lineno)

        @functools.wraps(fn)
        def wrapper(self_obj, *args, **kwargs):
            if san.active:
                san.check_contract(self_obj, holds, is_owner, is_entry,
                                   site, clsname, ann.name)
            return fn(self_obj, *args, **kwargs)

        wrapper.__rtsan_contract__ = ann
        return wrapper

    def check_contract(self, obj, holds, is_owner: bool, is_entry: bool,
                       site, clsname: str, method: str):
        for name in holds:
            lk = getattr(obj, name, _MISSING)
            if lk is _MISSING:
                self.record(
                    "RS102", site,
                    f"{clsname}.{method} is annotated 'holds={name}' "
                    f"but self.{name} does not exist on this instance "
                    f"— the contract is unverifiable (hard error; fix "
                    f"the annotation or the attribute)",
                    symbol=f"{clsname}.{method}.holds_missing.{name}",
                    raise_violation=True)
                continue
            if isinstance(lk, SanLock):
                if lk.name is None:
                    lk.name = f"{clsname}.{name}"
                held = lk.held_by_current()
            elif hasattr(lk, "_is_owned"):     # raw RLock / Condition
                held = lk._is_owned()
            elif hasattr(lk, "locked"):        # raw Lock: best-effort
                held = lk.locked()
            else:
                held = False
            if not held:
                self.record(
                    "RS102", site,
                    f"{clsname}.{method} entered without self.{name} "
                    f"held — the 'holds={name}' contract promises "
                    f"every caller locks first",
                    symbol=f"{clsname}.{method}.holds.{name}",
                    raise_violation=True)
        if is_owner or is_entry:
            cur = threading.current_thread()
            prev = getattr(obj, "_rtsan_owner", None)
            if is_entry or prev is None or not prev.is_alive():
                # entry=driver (re)binds: the caller IS the driver by
                # definition (engine restart, pipeline reuse); a dead
                # owner also rebinds (ownership transfers to the
                # failing thread once the driver is confirmed dead).
                try:
                    obj._rtsan_owner = cur
                except Exception:  # noqa: BLE001 - slots-only instance
                    pass
            elif prev is not cur:
                self.record(
                    "RS103", site,
                    f"{clsname}.{method} (owner=driver) called from "
                    f"thread {cur.name!r} but the registered driver is "
                    f"{prev.name!r} (alive) — only the driver thread "
                    f"may run this",
                    symbol=f"{clsname}.{method}.owner",
                    raise_violation=True)

    def _wrap_jit_factories(self):
        # every model description the engine may be handed, found by
        # rtlint's rule (a module of ray_tpu/models that defines
        # ``cache_spec``), never listed: each binds the frame's
        # factories under its own ``jit_*`` names
        try:
            import importlib

            from ..rtlint.callgraph import description_names
            modules = [
                importlib.import_module(f"ray_tpu.models.{name}")
                for name in description_names(
                    os.path.join(REPO_ROOT, "ray_tpu", "models"))]
        except Exception:  # noqa: BLE001 - gated: no device surface here
            return
        for module in modules:
            for name in dir(module):
                if not name.startswith("jit_"):
                    continue
                orig = getattr(module, name)
                if not callable(orig) or getattr(orig, "__rtsan__", False):
                    continue
                setattr(module, name, _DispatchFactory(orig, name, self))
                self._factory_patches.append((module, name, orig))

    # -------------------------------------------------------------- lifecycle
    def enable(self, modules=DEFAULT_MODULES, active: bool = True,
               wrap_dispatch: bool = True) -> "Sanitizer":
        """Patch everything. Idempotent; repeat calls can only widen
        ``active`` and instrument not-yet-seen modules."""
        fresh = not self.enabled
        self.enabled = True
        self.active = self.active or active
        if fresh:
            threading.Lock = self._lock_factory(_ORIG_LOCK, False)
            threading.RLock = self._lock_factory(_ORIG_RLOCK, True)
            threading.Condition = self._condition_factory(_ORIG_CONDITION)
            time.sleep = self._san_sleep
            threading.Thread.start = self._san_thread_start()
        for m in modules:
            if m not in self._seen_modules:
                self._seen_modules.add(m)
                self._instrument_module(m)
        if wrap_dispatch and modules:
            self._wrap_jit_factories()
        out_dir = os.environ.get("RT_SAN_DIR")
        if out_dir and not self._atexit_armed:
            self._atexit_armed = True
            atexit.register(self._atexit_dump, out_dir)
        return self

    def disable(self) -> "Sanitizer":
        """Restore every patched identity (the zero-overhead path)."""
        if not self.enabled:
            return self
        self.active = False
        self.enabled = False
        threading.Lock = _ORIG_LOCK
        threading.RLock = _ORIG_RLOCK
        threading.Condition = _ORIG_CONDITION
        time.sleep = _ORIG_SLEEP
        threading.Thread.start = _ORIG_THREAD_START
        for cls, attr, orig in reversed(self._instrumented):
            setattr(cls, attr, orig)
        self._instrumented.clear()
        self._seen_modules.clear()
        for mod, attr, orig in reversed(self._factory_patches):
            setattr(mod, attr, orig)
        self._factory_patches.clear()
        return self

    @contextmanager
    def activated(self):
        """Temporarily turn recording/enforcement on (the per-test
        opt-in window used by conftest)."""
        prev = self.active
        self.active = True
        try:
            yield self
        finally:
            self.active = prev

    # ------------------------------------------------------------- thread watch
    @contextmanager
    def thread_watch(self, targets=None, allow=(), grace_s: float = 0.2):
        """Leak detector: threads STARTED inside this window (from a
        target start-site) still alive at its end are RS105 findings.
        ``targets`` filters by start-site suffix (default: the
        engine/drafter/pipeline files); ``allow`` adds name substrings
        to ignore on top of :attr:`thread_allow`."""
        targets = tuple(targets) if targets is not None \
            else self.thread_targets
        before = set(threading.enumerate())
        try:
            yield
        finally:
            if self.active:
                leaked = []
                for t in threading.enumerate():
                    if t in before or not t.is_alive():
                        continue
                    site = getattr(t, "_rtsan_start_site", None)
                    abs_site = getattr(t, "_rtsan_start_abs", None)
                    if site is None or abs_site is None:
                        continue
                    path = site.rsplit(":", 1)[0]
                    if targets and not any(
                            path.endswith(x) or abs_site[0].endswith(x)
                            for x in targets):
                        continue
                    if any(p in t.name
                           for p in list(allow) + self.thread_allow):
                        continue
                    leaked.append((t, site, abs_site))
                for t, site, abs_site in leaked:
                    t.join(grace_s)   # a thread mid-exit is not a leak
                    if not t.is_alive():
                        continue
                    path = site.rsplit(":", 1)[0]
                    self.record(
                        "RS105", abs_site,
                        f"thread {t.name!r} started at {site} is still "
                        f"alive at watch teardown — a leaked driver "
                        f"keeps its pool (and a device queue slot) "
                        f"pinned forever; shut the owner down",
                        symbol=f"leaked_thread.{path}")

    # --------------------------------------------------------------- reports
    def snapshot(self) -> dict:
        """JSON-ready state: the run artifact ``python -m tools.rtsan
        --report`` renders."""
        coverage = annotation_coverage(
            tuple(sorted(self._seen_modules)) or DEFAULT_MODULES)
        with self._mu:
            return {
                "version": 1,
                "pid": os.getpid(),
                "coverage": coverage,
                "findings": [f.to_dict() for f in self.findings],
                "suppressed": list(self.suppressed),
                "edges": [
                    {"from": a, "to": b,
                     "count": e["count"],
                     "acquire_site": e.get("acquire_site", ""),
                     "acquire_stack": e.get("acquire_stack", "")}
                    for (a, b), e in sorted(self.edges.items())],
                "holds": [
                    {"site": s, **st}
                    for s, st in sorted(self.holds.items())],
            }

    def dump(self, path: str) -> str:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.snapshot(), f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path

    def _atexit_dump(self, out_dir: str):
        try:
            os.makedirs(out_dir, exist_ok=True)
            self.dump(os.path.join(out_dir, f"rtsan-{os.getpid()}.json"))
        except Exception:  # noqa: BLE001 - best-effort on teardown
            pass

    def gate(self, extra: Optional[List[dict]] = None,
             baseline_path: str = DEFAULT_BASELINE) -> dict:
        """The --check-style verdict: findings (plus ``extra`` finding
        dicts merged from worker artifacts) not in the baseline are
        NEW and must fail the suite."""
        baseline = load_baseline(baseline_path)
        merged: Dict[str, Finding] = {}
        with self._mu:
            for f in self.findings:
                merged[f.key] = f
        for d in extra or ():
            f = Finding(d["path"], d["line"], d["rule"], d["message"],
                        d["symbol"])
            merged.setdefault(f.key, f)
        new = sorted(f for f in merged.values() if f.key not in baseline)
        old = sorted(f for f in merged.values() if f.key in baseline)
        return {"new": new, "baselined": old,
                "suppressed": len(self.suppressed)}

    def stats_block(self, path_filter: str = "serve/") -> dict:
        """The ``engine.stats()`` sanitizer block: process findings
        count plus max hold time per named lock whose site matches
        ``path_filter`` (chaos benchmarks assert zero findings)."""
        with self._mu:
            return {
                "findings": len(self.findings),
                "max_hold_s": {
                    (st["name"] or s): round(st["max_s"], 6)
                    for s, st in sorted(self.holds.items())
                    if path_filter in s},
            }


#: THE per-process sanitizer.
SANITIZER = Sanitizer()

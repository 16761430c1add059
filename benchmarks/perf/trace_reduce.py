"""From the profiler's trace to numbers: device busy time, the device
operations that took most time, device time by the name scope the
program wrote, each compiled program's time, and the idle gaps with
what the host was doing in them.

The reduction works on a neutral form, so that it can be checked on a
small recorded trace (``recorded/``) without a chip::

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, dur_ns], ...]}]}],
     "paths": [str, ...]}

An event of a device's ``XLA Ops`` line may carry a fourth element, an
index into ``paths``: the name-scope path the compiler recorded for
the operation (``jit(decode_chunk_slots_paged)/while/body/closed_call/
pallas_call:``; what ``jax.named_scope`` and the primitives' names
make). Without it, or without ``paths`` (the recordings of PRs 23 and
24), the operation is unscoped.

:func:`load_xplane` makes that form from the ``.xplane.pb`` the JAX
profiler writes (needs jax for ``jax.profiler.ProfileData``, and no
device). Everything else is plain Python.

Who reduces. The process that traced hands out what only it knows
(``perf_deployment.Tracer.handoff``: the trace's directory, the host
samples, the slice's ends and the marker's time on the host's clock)
and :func:`reduce_handoff` makes the numbers from that and the file. A
training child calls it itself once its window is over. A serving
replica never does: its driver, which imports no jax, calls
:func:`reduce_in_child` once the window has closed, and this file then
runs as a child process on the CPU (``python trace_reduce.py
<handoff.json> <result.json>``, ``JAX_PLATFORMS=cpu``), so that
nothing holds the replica's interpreter after ``stop_trace()``.

A TPU device is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event per executed HLO operation (nested for control flow: a
``while`` spans its body's operations) and ``XLA Modules`` one event
per launch of a compiled program. Where the path is kept, read off a
v5e's trace (jax 0.9.0, builder's chip run, PR 29): not on the event,
whose stats are ``device_offset_ps``, ``device_duration_ps`` and a time
scale, but on the operation's ``XEventMetadata`` in the device plane,
as the stat ``tf_op``, beside ``program_id``, ``hlo_category``,
``flops``, ``bytes_accessed`` and ``source``. ``jax.profiler.
ProfileData`` does not hand out an event's metadata, so
:func:`op_paths` reads the planes' metadata tables from the file's
bytes itself (a few hundred operations, whatever the number of events)
and :func:`load_xplane` looks a path up once per operation. About half
of the operations carry one (fusions the compiler made of several
source lines, copies and parameter converts do not). The compiler's
metadata is not part of the compilation cache's key: a program found
in the cache brings the paths it was first compiled with.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_EVENT = "perfbench_sync"
PATH_STAT = "tf_op"
PROGRAM_STAT = "program_id"
UNSCOPED = "(unscoped)"
OTHER = "other"
TOP_SCOPES = 40


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _varint(buf, i: int):
    r = shift = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << shift
        if b < 0x80:
            return r, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, bytes of 8 or 4 for fixed ones, a memoryview (no copy) for
    a length-delimited value."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            val = buf[i:i + n]
            i += n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            val = bytes(buf[i:i + n])
            i += n
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, val


def _map_entries(plane, field: int):
    """(key, value) of a ``map<int64, message>`` field of an XPlane."""
    for num, entry in _fields(plane):
        if num == field:
            kv = dict(_fields(entry))
            yield kv.get(1), kv.get(2, b"")


def op_paths(path: str) -> Dict[str, Dict[str, Dict[int, str]]]:
    """plane name -> operation's event name -> program id -> name-scope
    path, for the device planes of an ``.xplane.pb``. Reads the file as
    ``tsl/profiler/protobuf/xplane.proto`` lays it out (XSpace.planes
    1; XPlane.name 2, .event_metadata 4, .stat_metadata 5;
    XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .uint64 3,
    .int64 4, .str 5, .ref 7; XStatMetadata.name 2) and skips the
    lines, where the events are, by their length."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name = next((bytes(v).decode() for n, v in _fields(plane)
                     if n == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                      for k, v in _map_entries(plane, 5)}
        ops = out.setdefault(name, {})
        for _key, meta in _map_entries(plane, 4):
            op_name, scope, program = None, None, 0
            for n, v in _fields(meta):
                if n == 2:
                    op_name = bytes(v).decode(errors="replace")
                elif n == 5:
                    stat = dict(_fields(v))
                    which = stat_names.get(stat.get(1))
                    if which == PATH_STAT:
                        scope = bytes(stat[5]).decode() if 5 in stat \
                            else stat_names.get(stat.get(7))
                    elif which == PROGRAM_STAT:
                        program = stat.get(3, stat.get(4, 0))
            if op_name and scope:
                ops.setdefault(op_name, {})[program] = scope
    return out


def _program_id(module_event_name: str) -> int:
    """``jit_step(123)`` -> 123: the program a launch belongs to."""
    m = re.search(r"\((\d+)\)$", module_event_name)
    return int(m.group(1)) if m else 0


def load_xplane(path: str, keep_host: Tuple[str, ...] = (SYNC_EVENT,)
                ) -> dict:
    """The neutral form of an ``.xplane.pb``: every event of the device
    planes, and of the host planes only those named in ``keep_host``.
    An ``XLA Ops`` event gets its operation's name-scope path, looked
    up once per operation; where programs share an operation's name and
    differ in its path, the launch that encloses the event decides."""
    from jax.profiler import ProfileData

    scopes = op_paths(path)
    data = ProfileData.from_file(path)
    planes, paths, index = [], [], {}

    def idx(scope: str) -> int:
        if scope not in index:
            index[scope] = len(paths)
            paths.append(scope)
        return index[scope]

    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name in keep_host]
            if events:
                lines[line.name] = events
        ops = scopes.get(plane.name)
        if ops and OPS_LINE in lines:
            launches = sorted((s, _program_id(n)) for n, s, _d in
                              lines.get(MODULES_LINE, []))
            starts = [s for s, _p in launches]
            # one path for the name in every program: an index; else
            # a table by program id
            known = {n: idx(next(iter(by.values())))
                     if len(set(by.values())) == 1
                     else {p: idx(sc) for p, sc in by.items()}
                     for n, by in ops.items()}
            for ev in lines[OPS_LINE]:
                k = known.get(ev[0])
                if isinstance(k, dict):
                    j = bisect.bisect_right(starts, ev[1]) - 1
                    k = k.get(launches[j][1]) if j >= 0 else None
                if k is not None:
                    ev.append(k)
        if lines:
            planes.append({"name": plane.name, "lines": [
                {"name": n, "events": e} for n, e in lines.items()]})
    return {"planes": planes, "paths": paths}


def describe(path: str, per_line: int = 12) -> dict:
    """What a trace holds, for a reader who has not seen one: planes,
    lines, counts and the first events with their stats."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            head = []
            for ev in evs[:per_line]:
                stats = {}
                try:
                    stats = {k: str(v)[:120] for k, v in ev.stats}
                except Exception:  # noqa: BLE001 - description only
                    pass
                head.append({"name": ev.name[:160],
                             "start_ns": int(ev.start_ns),
                             "dur_ns": int(ev.duration_ns),
                             "stats": stats})
            lines.append({"name": line.name, "n": len(evs), "head": head})
        out.append({"name": plane.name, "lines": lines})
    return {"planes": out}


def cut(trace: dict, window: Tuple[int, int], samples, offset_ns,
        name_chars: int = 96) -> dict:
    """A small piece of a trace in the neutral form, for ``recorded/``:
    the device events that begin inside ``window``, names shortened,
    times moved to start at 0, with the host samples of that span."""
    a, b = window
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            evs = [[ev[0][:name_chars], ev[1] - a, *ev[2:]]
                   for ev in line["events"] if a <= ev[1] < b]
            if evs:
                lines.append({"name": line["name"], "events": evs})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes, "paths": trace.get("paths", []),
            "window": [0, b - a],
            "samples": [[t + offset_ns - a, lab] for t, lab in samples
                        if a <= t + offset_ns < b],
            "host_offset_ns": 0}


def sync_event_ns(trace: dict) -> Optional[int]:
    """When, on the trace's clock, the host marker event began."""
    return next((ev[1] for p in trace["planes"] for ln in p["lines"]
                 for ev in ln["events"] if ev[0] == SYNC_EVENT), None)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _self_times(events: List[list]) -> Dict[tuple, int]:
    """Per (name, path index or None), duration minus what nested
    events cover (a ``while`` is charged only what its body does not
    account for)."""
    out: Dict[tuple, int] = {}
    stack: List[list] = []       # [key, end, self_ns]
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        start, dur = ev[1], ev[2]
        end = start + dur
        while stack and stack[-1][1] <= start:
            n, _e, s = stack.pop()
            out[n] = out.get(n, 0) + max(s, 0)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([(ev[0], ev[3] if len(ev) > 3 else None), end, dur])
    while stack:
        n, _e, s = stack.pop()
        out[n] = out.get(n, 0) + max(s, 0)
    return out


def _clip(events: List[list], window: Optional[Tuple[int, int]]):
    if window is None:
        return events
    a, b = window
    out = []
    for ev in events:
        s, e = max(ev[1], a), min(ev[1] + ev[2], b)
        if e > s:
            out.append([ev[0], s, e - s, *ev[3:]])
    return out


def short_name(name: str) -> str:
    """An operation's name as the breakdown prints it: the HLO name and
    its result shape, without the operands."""
    m = re.match(r"^%?([\w.\-]+) = (\(?[\w]+\[[\d,]*\])", name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return name[:96]


def scope_key(path: Optional[str]) -> str:
    """A recorded path as the table keys it: the ``jit(...)`` wrappers
    at its head and the colon at its end cut off, so that what is left
    begins with what the program wrote (``while/body/closed_call/
    pallas_call``, ``moe.experts/dot_general``)."""
    if not path:
        return UNSCOPED
    parts = path.rstrip(":").split("/")
    while parts and re.match(r"^p?jit\(.*\)$", parts[0]):
        parts.pop(0)
    return "/".join(parts) or UNSCOPED


def scope_seconds(run: dict, scope: str) -> Optional[float]:
    """Device self time, in seconds, of every operation under a scope:
    the rows of ``run["trace"]["scopes"]`` whose path holds ``scope``'s
    components next to one another at any depth (``"moe.experts"``
    finds ``while/body/moe.experts/dot_general``; ``"pallas_call"``
    every Pallas kernel). None where the run has no table or the table
    names no such scope; what fell under ``other`` is not searched."""
    table = ((run.get("trace") or {}).get("scopes"))
    if not table:
        return None
    want = scope.strip("/").split("/")
    total, found = 0.0, False
    for path, seconds in table.items():
        parts = path.split("/")
        if path != OTHER and any(
                parts[i:i + len(want)] == want
                for i in range(len(parts) - len(want) + 1)):
            total, found = total + seconds, True
    return total if found else None


def reduce(trace: dict, window: Optional[Tuple[int, int]] = None,
           samples: Optional[List[Tuple[int, str]]] = None,
           host_offset_ns: Optional[int] = None, top: int = 10) -> dict:
    """``window`` is in the trace's own nanoseconds (default: from the
    first to the last device event). ``samples`` are ``(t_ns, label)``
    of what the host was doing, on a host clock that
    ``host_offset_ns`` (trace ns minus host ns, from
    :func:`sync_event_ns`) maps onto the trace's.

    ``"scopes"``: the operations' self time in seconds (mean over the
    devices, the traced slice) by :func:`scope_key` of their path: the
    ``TOP_SCOPES`` largest and the rest as ``"other"``, so that the
    table sums to ``"ops_self_s"``, the self time of all operations.
    :func:`scope_seconds` sums a scope for a reader."""
    devices = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            if m and line["name"] in (OPS_LINE, MODULES_LINE):
                devices.setdefault(int(m.group(1)), {})[line["name"]] = \
                    line["events"]
    if not devices:
        return {"devices": 0}
    if window is None:
        firsts = [min(e[1] for e in d.get(OPS_LINE) or d[MODULES_LINE])
                  for d in devices.values()]
        lasts = [max(e[1] + e[2] for e in d.get(OPS_LINE)
                     or d[MODULES_LINE]) for d in devices.values()]
        window = (min(firsts), max(lasts))
    win_ns = window[1] - window[0]
    busy, ops, programs, scopes = [], {}, {}, {}
    paths = trace.get("paths") or []
    gaps0: List[Tuple[int, int]] = []
    for i, dev in sorted(devices.items()):
        evs = _clip(dev.get(OPS_LINE) or dev.get(MODULES_LINE) or [],
                    window)
        merged = _union([(ev[1], ev[1] + ev[2]) for ev in evs])
        busy.append(sum(b - a for a, b in merged))
        for (name, k), ns in _self_times(evs).items():
            key = short_name(name)
            ops[key] = ops.get(key, 0) + ns
            key = scope_key(paths[k] if k is not None else None)
            scopes[key] = scopes.get(key, 0) + ns
        for name, _s, d in _clip(dev.get(MODULES_LINE) or [], window):
            p = programs.setdefault(name, {"launches": 0, "ns": 0})
            p["launches"] += 1
            p["ns"] += d
        if i == min(devices):
            edge = window[0]
            for a, b in merged:
                if a > edge:
                    gaps0.append((edge, a))
                edge = max(edge, b)
            if window[1] > edge:
                gaps0.append((edge, window[1]))
    n = len(devices)
    by_size = sorted(scopes.items(), key=lambda kv: -kv[1])
    table = {k: v / n / 1e9 for k, v in by_size[:TOP_SCOPES]}
    if by_size[TOP_SCOPES:]:
        table[OTHER] = sum(v for _k, v in by_size[TOP_SCOPES:]) / n / 1e9
    out = {
        "devices": n,
        "window_s": win_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_by_device": [b / 1e9 for b in busy],
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "ops_self_s": sum(ops.values()) / n / 1e9,
        "scopes": table,
        "programs": {k: {"launches": v["launches"] / n,
                         "seconds": v["ns"] / n / 1e9}
                     for k, v in programs.items()},
        "idle_s": sum(b - a for a, b in gaps0) / 1e9,
    }
    out["idle_gaps"] = _attribute(gaps0, samples or [], host_offset_ns,
                                  top)
    out["launches_by_host"] = _launches_by_host(
        devices[min(devices)].get(MODULES_LINE) or [], window,
        samples or [], host_offset_ns)
    return out


def _launches_by_host(modules, window, samples, offset_ns) -> dict:
    """Launches of compiled programs that lie WHOLLY inside the window,
    grouped by what the host was doing at the launch's middle. The
    program's own names say nothing today (``jit__unknown``), but the
    engine's driver blocks on every dispatch, so the function it waits
    in tells a decode chunk from a prefill. Under each host label the
    launches are also kept by program, so that a reader can take the
    one program that does the work and leave out the tiny ones (a key
    made, a scalar converted) that fall under the same label."""
    if not samples or offset_ns is None:
        return {}
    import bisect

    ts = sorted((t + offset_ns, lab) for t, lab in samples)
    keys = [t for t, _l in ts]
    out: Dict[str, dict] = {}
    for _name, start, dur in modules:
        if start < window[0] or start + dur > window[1]:
            continue
        j = bisect.bisect_right(keys, start + dur // 2) - 1
        lab = ts[j][1] if j >= 0 else "unattributed"
        g = out.setdefault(lab, {"launches": 0, "seconds": 0.0,
                                 "programs": {}})
        g["launches"] += 1
        g["seconds"] += dur / 1e9
        p = g["programs"].setdefault(_name, {"launches": 0,
                                             "seconds": 0.0})
        p["launches"] += 1
        p["seconds"] += dur / 1e9
    return out


def _attribute(gaps, samples, offset_ns, top: int) -> List[list]:
    """Idle seconds by what the host was doing: every sample inside a
    gap stands for the time to the next sample (or the gap's end)."""
    if not gaps:
        return []
    by: Dict[str, int] = {}
    if not samples or offset_ns is None:
        by["unattributed"] = sum(b - a for a, b in gaps)
    else:
        ts = sorted((t + offset_ns, lab) for t, lab in samples)
        j = 0
        for a, b in gaps:
            while j < len(ts) and ts[j][0] < a:
                j += 1
            # what was running when the gap opened: the sample before
            cur = ts[j - 1][1] if j > 0 else "unattributed"
            edge = a
            k = j
            while k < len(ts) and ts[k][0] < b:
                by[cur] = by.get(cur, 0) + ts[k][0] - edge
                edge, cur = ts[k][0], ts[k][1]
                k += 1
            by[cur] = by.get(cur, 0) + b - edge
    return [[k, v / 1e9] for k, v in sorted(
        by.items(), key=lambda kv: -kv[1])[:top]]


def load_trace(path: str) -> dict:
    """The neutral form of a trace file: of an ``.xplane.pb``, or of a
    ``.json`` that holds the form already (``recorded/``)."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    return load_xplane(path)


def reduce_handoff(h: dict) -> dict:
    """``run["trace"]``, from what the tracing process handed out
    (``perf_deployment.Tracer.handoff``, with ``"describe"``) and the
    file it wrote: :func:`reduce` over the slice, with what tracing
    cost. ``h["path"]`` names the file where it is not the newest
    under ``h["log_dir"]``."""
    t0 = time.monotonic()
    path = h.get("path") or find_xplane(h["log_dir"])
    trace = load_trace(path)
    samples = h["samples"]
    sync = sync_event_ns(trace)
    offset = None if sync is None else sync - h["sync_host_ns"]
    window = None
    if offset is not None:
        window = (h["t_start"] + offset, h["t_stop"] + offset)
    red = reduce(trace, window=window, samples=samples,
                 host_offset_ns=offset)
    red["host_window_s"] = (h["t_stop"] - h["t_start"]) / 1e9
    red["samples"] = len(samples)
    red["cost"] = {
        "trace_stop_s": h["trace_stop_s"], "slice_s": h["slice_s"],
        "launches": h["launches"], "ended_by": h["ended_by"],
        "xplane_bytes": os.path.getsize(path),
        "device_events": sum(
            len(ln["events"]) for p in trace["planes"]
            if DEVICE_PLANE.match(p["name"]) for ln in p["lines"])}
    if h.get("describe"):
        red["describe"] = describe(path)
        if window is not None:
            red["cut"] = cut(trace, (window[0] + 10 ** 9,
                                     window[0] + 10 ** 9 + 7 * 10 ** 8),
                             samples, offset)
    red["cost"]["reduce_s"] = time.monotonic() - t0
    return red


def reduce_in_child(handoff: dict, workdir: str) -> dict:
    """:func:`reduce_handoff` in a child process on the CPU: for a
    caller that may not import jax (a serving cell's driver) and whose
    replica is not to read a file. The same function on the same file
    with the same arguments gives the same dictionary, as JSON carries
    it; ``reduce_s`` is the child's. Raises RuntimeError with the end
    of the child's standard error where it fails."""
    src, dst = (os.path.join(workdir, n) for n in
                ("trace_handoff.json", "trace_reduced.json"))
    with open(src, "w") as f:
        json.dump(handoff, f)
    if os.path.exists(dst):
        os.remove(dst)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), src, dst],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True)
    if proc.returncode != 0 or not os.path.exists(dst):
        raise RuntimeError(
            f"the reduction's child exited {proc.returncode}: "
            f"{proc.stderr.strip()[-600:]}")
    with open(dst) as f:
        return json.load(f)


if __name__ == "__main__":
    with open(sys.argv[1]) as _f:
        _red = reduce_handoff(json.load(_f))
    with open(sys.argv[2], "w") as _f:
        json.dump(_red, _f)

"""From the profiler's trace to numbers: device busy time, the device
operations that took most time, each compiled program's time, and the
idle gaps with what the host was doing in them.

The reduction works on a neutral form, so that it can be checked on a
small recorded trace (``recorded/``) without a chip::

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, dur_ns], ...]}]}]}

:func:`load_xplane` makes that form from the ``.xplane.pb`` the JAX
profiler writes (needs jax; only the process that holds the chip calls
it). Everything else is plain Python.

A TPU device is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event per executed HLO operation (nested for control flow: a
``while`` spans its body's operations) and ``XLA Modules`` one event
per launch of a compiled program.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_EVENT = "perfbench_sync"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str, keep_host: Tuple[str, ...] = (SYNC_EVENT,)
                ) -> dict:
    """The neutral form of an ``.xplane.pb``: every event of the device
    planes, and of the host planes only those named in ``keep_host``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name in keep_host]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


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
            evs = [[n[:name_chars], s - a, d] for n, s, d in
                   line["events"] if a <= s < b]
            if evs:
                lines.append({"name": line["name"], "events": evs})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes, "window": [0, b - a],
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


def _self_times(events: List[list]) -> Dict[str, int]:
    """Per name, duration minus what nested events cover (a ``while``
    is charged only what its body does not account for)."""
    out: Dict[str, int] = {}
    stack: List[list] = []       # [name, end, self_ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            n, _e, s = stack.pop()
            out[n] = out.get(n, 0) + max(s, 0)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, dur])
    while stack:
        n, _e, s = stack.pop()
        out[n] = out.get(n, 0) + max(s, 0)
    return out


def _clip(events: List[list], window: Optional[Tuple[int, int]]):
    if window is None:
        return events
    a, b = window
    out = []
    for name, start, dur in events:
        s, e = max(start, a), min(start + dur, b)
        if e > s:
            out.append([name, s, e - s])
    return out


def short_name(name: str) -> str:
    """An operation's name as the breakdown prints it: the HLO name and
    its result shape, without the operands."""
    m = re.match(r"^%?([\w.\-]+) = (\(?[\w]+\[[\d,]*\])", name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return name[:96]


def reduce(trace: dict, window: Optional[Tuple[int, int]] = None,
           samples: Optional[List[Tuple[int, str]]] = None,
           host_offset_ns: Optional[int] = None, top: int = 10) -> dict:
    """``window`` is in the trace's own nanoseconds (default: from the
    first to the last device event). ``samples`` are ``(t_ns, label)``
    of what the host was doing, on a host clock that
    ``host_offset_ns`` (trace ns minus host ns, from
    :func:`sync_event_ns`) maps onto the trace's."""
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
    busy, ops, programs = [], {}, {}
    gaps0: List[Tuple[int, int]] = []
    for i, dev in sorted(devices.items()):
        evs = _clip(dev.get(OPS_LINE) or dev.get(MODULES_LINE) or [],
                    window)
        merged = _union([(s, s + d) for _n, s, d in evs])
        busy.append(sum(b - a for a, b in merged))
        for name, ns in _self_times(evs).items():
            key = short_name(name)
            ops[key] = ops.get(key, 0) + ns
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
    out = {
        "devices": n,
        "window_s": win_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_by_device": [b / 1e9 for b in busy],
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
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

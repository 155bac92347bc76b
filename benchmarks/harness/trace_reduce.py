"""From a jax.profiler trace (.xplane.pb) to device busy and idle time,
device time by XLA module, and the breakdown the ledger keeps.

Two steps, so that the arithmetic is checked on a small recorded trace:
`load` reads the planes into plain tuples; `reduce` works on those.

The device's planes are named /device:TPU:<n>; on each, the line
"XLA Ops" holds one event per executed operation and "XLA Modules" one
per executed program (jit_<function>(<fingerprint>)). The benchmark's
own host marks are TraceAnnotations named bench:<statement>#<seq>.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench:"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> dict:
    """{"ops": {device: [(start_ns, end_ns, name)]}, "modules": {...},
    "marks": [(start_ns, end_ns, name)], "lines": {plane: [line names]}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"ops": {}, "modules": {}, "marks": [], "lines": {}}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        names = []
        for line in plane.lines:
            names.append(line.name)
            if m and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                out[key].setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
            elif not m:
                out["marks"].extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith(MARK))
        out["lines"][plane.name] = names
    for per_device in (out["ops"], out["modules"]):
        for events in per_device.values():
            events.sort()
    out["marks"].sort()
    return out


def _union(intervals):
    """Merged, sorted (start, end) list of possibly overlapping ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        elif e > s:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def window_of(events: dict):
    """The traced window: from the first benchmark mark's start to the
    last one's end; without marks, the span of the device's operations."""
    if events["marks"]:
        return (min(s for s, _, _ in events["marks"]),
                max(e for _, e, _ in events["marks"]))
    spans = [(ev[0][0], max(e for _, e, _ in ev))
             for ev in events["ops"].values() if ev]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(events: dict, top: int = 10):
    """None when no device operation was traced. Else: busy_s (union of
    the device-operation intervals inside the window, averaged over the
    devices that ran any), window_s, by_module [[name, seconds]] (most
    first), idle_gaps [[label, seconds]] (most first; a gap is labelled
    with the statement in flight on the host at its middle and how far
    through that statement it fell), and marks as (start_s, end_s, name)
    relative to the window's start."""
    win = window_of(events)
    if win is None or not any(events["ops"].values()):
        return None
    lo, hi = win
    busy = []
    by_module: dict = {}
    gaps: dict = {}
    marks = events["marks"]
    mark_starts = [s for s, _, _ in marks]
    for dev, ops in sorted(events["ops"].items()):
        if not ops:
            continue
        merged = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        mods = events["modules"].get(dev, [])
        mod_starts = [s for s, _, _ in mods]
        per_mod: dict = {}
        for s, e, _ in ops:
            i = bisect.bisect_right(mod_starts, s) - 1
            name = _module_name(mods[i][2]) \
                if i >= 0 and s < mods[i][1] else "(no module)"
            per_mod.setdefault(name, []).append((s, e))
        for name, iv in per_mod.items():
            t = sum(e - s for s, e in _union(_clip(iv, lo, hi)))
            if t:
                by_module[name] = by_module.get(name, 0) + t
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                mid = (edge + s) / 2
                label = "no_statement_in_flight"
                i = bisect.bisect_right(mark_starts, mid) - 1
                # marks start in order; one that covers `mid` lies a
                # few clients back at most
                stop = max(i - 64, -1)
                while i > stop:
                    ms, me, mn = marks[i]
                    if me > mid:
                        tenth = int(10 * (mid - ms) / max(me - ms, 1))
                        label = f"{mn[len(MARK):].split('#')[0]}" \
                                f"@{tenth * 10}%"
                        break
                    i -= 1
                gaps[label] = gaps.get(label, 0) + (s - edge)
            edge = max(edge, e)
    n = len(busy)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "by_module": ranked(by_module),
        "idle_gaps": ranked(gaps),
        "marks": [((s - lo) / 1e9, (e - lo) / 1e9, name[len(MARK):])
                  for s, e, name in marks],
    }

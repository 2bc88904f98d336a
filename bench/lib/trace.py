"""Reduce a ``jax.profiler`` trace to the numbers the metrics read.

``load`` turns the ``.xplane.pb`` the profiler wrote into plain data:
planes of lines of ``(name, start_ns, duration_ns)`` events, so that a
recorded or a synthetic trace goes through the same ``reduce``.

The measured window is the span of the harness's own ``bench.window``
annotation. On each device plane, busy time is the union of the
intervals in which an XLA op ran inside that window; the gaps of that
union are attributed to what the host was doing at their midpoint (the
innermost host event that covers it).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)
Planes = Dict[str, Dict[str, List[Event]]]


def load(trace_dir: str) -> Planes:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data."""
    from jax._src.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes: Planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend((e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events)
    return planes


def _clip(events, lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> List[Tuple[int, int]]:
    """Merged, sorted intervals."""
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclasses.dataclass
class DeviceSummary:
    busy_ns: int
    module_ns: Dict[str, int]      # module name (id suffix dropped) -> ns
    op_ns: Dict[str, int]          # op name -> ns
    gaps: List[Tuple[int, int]]    # idle intervals inside the window


@dataclasses.dataclass
class TraceSummary:
    window: Tuple[int, int]
    devices: Dict[int, DeviceSummary]
    gap_names: List[Tuple[str, float]]   # longest gaps of the first device

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices in the trace."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices.values()) \
            / len(self.devices) / 1e9

    def module_s(self, prefix: str) -> float:
        """Device seconds of modules whose name starts with ``prefix``,
        summed over devices."""
        return sum(ns for d in self.devices.values()
                   for name, ns in d.module_ns.items()
                   if name.startswith(prefix)) / 1e9

    def top_ops(self, n: int = TOP) -> List[Tuple[str, float]]:
        total: collections.Counter = collections.Counter()
        for d in self.devices.values():
            total.update(d.op_ns)
        return [(name, ns / 1e9) for name, ns in total.most_common(n)]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """An HLO op's name and result shape, without its layout and
    operands: ``%fusion.7 = f32[42720,128]``."""
    return name.split("{", 1)[0].strip()


def _window(planes: Planes) -> Tuple[int, int]:
    for lines in planes.values():
        for evs in lines.values():
            for name, s, d in evs:
                if name == WINDOW:
                    return s, s + d
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def _host_events(planes: Planes) -> List[Event]:
    out = []
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            continue
        for evs in lines.values():
            out.extend(e for e in evs if e[2] > 0 and e[0] != WINDOW)
    return out


def _attribute(gap: Tuple[int, int], host: List[Event]) -> str:
    mid = (gap[0] + gap[1]) // 2
    best = None
    for name, s, d in host:
        if s <= mid <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best is not None else "host.none"


def reduce(planes: Planes) -> TraceSummary:
    lo, hi = _window(planes)
    devices: Dict[int, DeviceSummary] = {}
    for pname, lines in planes.items():
        m = DEVICE_PLANE.match(pname)
        if m is None:
            continue
        ops = lines.get(OPS_LINE, [])
        busy = union(_clip(ops, lo, hi))
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = b
        if t < hi:
            gaps.append((t, hi))
        modules: collections.Counter = collections.Counter()
        for name, s, d in lines.get(MODULES_LINE, []):
            clipped = _clip([(name, s, d)], lo, hi)
            if clipped:
                a, b = clipped[0]
                modules[_module_name(name)] += b - a
        op_ns: collections.Counter = collections.Counter()
        for name, s, d in ops:
            clipped = _clip([(name, s, d)], lo, hi)
            if clipped:
                a, b = clipped[0]
                op_ns[_op_name(name)] += b - a
        devices[int(m.group(1))] = DeviceSummary(
            busy_ns=sum(b - a for a, b in busy), module_ns=dict(modules),
            op_ns=dict(op_ns), gaps=gaps)
    gap_names: List[Tuple[str, float]] = []
    if devices:
        first = devices[min(devices)]
        host = _host_events(planes)
        longest = sorted(first.gaps, key=lambda g: g[0] - g[1])[:TOP]
        gap_names = [(_attribute(g, host), (g[1] - g[0]) / 1e9)
                     for g in longest]
    return TraceSummary(window=(lo, hi), devices=devices,
                        gap_names=gap_names)


def breakdown(summary: Optional[TraceSummary]) -> Optional[dict]:
    """The result line's ``breakdown``: the device ops that took most
    time and the longest idle gaps, by what the host was doing."""
    if summary is None or not summary.devices:
        return None
    return {"device_ops": [[n, s] for n, s in summary.top_ops()],
            "idle_gaps": [[n, s] for n, s in summary.gap_names]}

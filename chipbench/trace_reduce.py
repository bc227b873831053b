"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` file the profiler writes, read with
``jax.profiler.ProfileData``.  Device planes are named
``/device:TPU:<i>``; on each, the ``XLA Ops`` line holds one event per
executed operation, named by its HLO text, and the ``XLA Modules`` line
one per executed program (``Async XLA Ops``, copies in flight beside
the compute, is not counted as busy).  The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``chipbench.<what>``) sit on
host planes, on the same clock to about a millisecond (the device's
timestamps ran 1.2 ms early in a trace recorded on a v5e).

Everything is clipped to the span ``chipbench.window``: busy time is the
union of operation intervals there, averaged over the devices that ran
anything; an idle gap is a stretch of the window in which no operation
ran, cut at span edges, each piece labelled by the innermost benchmark
span around its middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """'decode_attention_paged.6' from the HLO text a TPU trace names an
    operation by ('%decode_attention_paged.6 = bf16[...] custom-call(...)')."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over devices
    ops: Dict[str, float]               # op name -> device seconds
    modules: Dict[str, List[float]]     # program name -> each run's seconds
    idle: Dict[str, float]              # host span -> idle device seconds
    devices: int

    def module_seconds(self, fragment: str) -> List[float]:
        """Runs of every program whose name holds ``fragment``."""
        return [d for name, ds in self.modules.items() if fragment in name
                for d in ds]

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.ops.items() if rx.search(name))


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w0: int, w1: int) -> Optional[Tuple[int, int]]:
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def reduce_planes(planes) -> Reduced:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``) —
    the shape of ``jax.profiler.ProfileData.planes``."""
    spans: List[Tuple[int, int, str]] = []
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = int(ev.start_ns)
                    spans.append((s, s + int(ev.duration_ns), ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = windows[0]
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW]
    ops: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    busy_per_device = []
    busy_union_all: List[Tuple[int, int]] = []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                c = _clip(s, s + int(ev.duration_ns), w0, w1)
                if c is None:
                    continue
                secs = (c[1] - c[0]) * 1e-9
                if line.name == OPS_LINE:
                    intervals.append(c)
                    name = op_name(ev.name)
                    ops[name] = ops.get(name, 0.0) + secs
                else:
                    modules.setdefault(ev.name, []).append(secs)
        if not intervals:
            continue
        u = _union(intervals)
        busy_per_device.append(sum(e - s for s, e in u) * 1e-9)
        busy_union_all.extend(u)
    if not busy_per_device:
        raise ValueError("no device operation ran inside the window")
    busy_all = _union(busy_union_all)
    idle: Dict[str, float] = {}
    cuts = sorted({t for s, e, _ in inner for t in (s, e)})
    cursor = w0
    for s, e in busy_all + [(w1, w1)]:
        if s > cursor:
            # split the gap at span edges; label each piece by the
            # innermost span around its middle
            edges = [cursor] + [t for t in cuts if cursor < t < s] + [s]
            for a, b in zip(edges, edges[1:]):
                mid = (a + b) // 2
                around = [x for x in inner if x[0] <= mid < x[1]]
                label = (min(around, key=lambda x: x[1] - x[0])[2]
                         if around else "(no span)")
                idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
        cursor = max(cursor, e)
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy_per_device) / len(busy_per_device),
                   ops=ops, modules=modules, idle=idle,
                   devices=len(busy_per_device))


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def top_ops(d: Dict[str, float], n: int = 10) -> List[List]:
    """The operations that took most device time, leaving out loops and
    calls, whose time is their body's."""
    return top({k: v for k, v in d.items()
                if not k.split(".")[0] in CONTAINERS}, n)

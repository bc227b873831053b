#!/usr/bin/env python3
"""Read the program's own instrumentation out of a ``jax.profiler`` trace.

    python3 chipbench/scopes.py <trace.xplane.pb>

The serving program marks its device work with ``jax.named_scope``
(``layers``, ``attention``, ``ffn``: path components of each operation's
``tf_op``) and its host phases with ``jax.profiler.TraceAnnotation``
spans named ``repro.<layer>.<what>``, some with arguments
(``repro.engine.prefill`` carries ``computed_tokens``, ``padded_tokens``
and ``bucket``).  ``jax.profiler.ProfileData`` gives events but not the
event metadata that holds ``tf_op``, so this module decodes the XSpace
protobuf wire format itself:

    XSpace.planes -> XPlane {name, lines, event_metadata, stat_metadata}
    XLine {name, timestamp_ns, events}; XEvent {metadata_id, offset_ps,
    duration_ps, stats}; XEventMetadata {name, stats}; XStat {metadata_id,
    a value, or ref_value naming a stat_metadata entry whose name is the
    string}

What it returns, inside the benchmark's span ``chipbench.window``:

- device seconds per (program, scope label): each ``XLA Ops`` event's
  self time (its duration less the events nested in it, so a ``while``
  counts only its own overhead), labelled by the program's named scopes
  on its ``tf_op`` path (``layers/attention``, ``layers``, ``""`` for
  none), and the runs of each program;
- idle device time labelled by the host spans around it: the innermost
  ``chipbench.*`` span and the ``repro.*`` spans, outer to inner;
- every ``repro.*`` span with its arguments;
- the device-to-host clock offset.  The trace pairs each program run on
  the device (``XLA Modules``, stat ``run_id``) with the host runtime's
  events of the same ``run_id``: the run starts after the first of them
  (the enqueue) and ends before the last (the completion callback).
  Over all runs that bounds the offset; its midpoint is applied to
  every device timestamp before anything is clipped or labelled.
"""
from __future__ import annotations

import re
import struct
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

WINDOW = "chipbench.window"
BENCH_PREFIX = "chipbench."
PROGRAM_PREFIX = "repro."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the program's named scopes, outer to inner
SCOPES = ("layers", "attention", "ffn")


# ---------------------------------------------------------------------------
# the wire format
# ---------------------------------------------------------------------------
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def fields(buf: bytes, i: int = 0, end: Optional[int] = None
           ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varints, a
    (start, end) slice for length-delimited fields, raw bytes for the
    fixed-width ones."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


@dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: Dict[str, object]
    meta: Dict[str, object]            # the event metadata's stats


@dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


def _stats(buf: bytes, spans, names: Dict[int, str]) -> Dict[str, object]:
    out: Dict[str, object] = {}
    for s in spans:
        key, val = None, None
        for f, v in fields(buf, *s):
            if f == 1:
                key = names.get(v, str(v))
            elif f == 2:
                val = struct.unpack("<d", v)[0]
            elif f == 3:
                val = v
            elif f == 4:
                val = v - (1 << 64) if v >= 1 << 63 else v
            elif f in (5, 6):
                val = _text(buf, v)
            elif f == 7:
                val = names.get(v, "")
        out[key] = val
    return out


def _plane(buf: bytes, span) -> Plane:
    name, lines, emeta, smeta = "", [], [], {}
    for f, v in fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta.append(v)
        elif f == 5:
            for mf, mv in fields(buf, *v):
                if mf == 2:
                    m = dict(fields(buf, *mv))
                    smeta[m.get(1, 0)] = _text(buf, m.get(2, (0, 0)))
    metas: Dict[int, Tuple[str, Dict[str, object]]] = {}
    for e in emeta:
        for mf, mv in fields(buf, *e):
            if mf != 2:
                continue
            mid, mname, mstats = 0, "", []
            for f, v in fields(buf, *mv):
                if f == 1:
                    mid = v
                elif f == 2:
                    mname = _text(buf, v)
                elif f == 5:
                    mstats.append(v)
            metas[mid] = (mname, _stats(buf, mstats, smeta))
    out: Dict[str, List[Event]] = {}
    for ls in lines:
        lname, t0_ns, evs = "", 0, []
        for f, v in fields(buf, *ls):
            if f == 2:
                lname = _text(buf, v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                evs.append(v)
        events = out.setdefault(lname, [])
        for es in evs:
            mid = off = dur = 0
            st = []
            for f, v in fields(buf, *es):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
                elif f == 4:
                    st.append(v)
            mname, mstats = metas.get(mid, ("", {}))
            start = t0_ns + off * 1e-3
            events.append(Event(mname, start, start + dur * 1e-3,
                                _stats(buf, st, smeta), mstats))
    return Plane(name, out)


def read_planes(path: str) -> List[Plane]:
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(buf, v) for f, v in fields(buf) if f == 1]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
@dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    args: Dict[str, object]


@dataclass
class Offset:
    """Host time = device time + ``ns``, within [``lo_ns``, ``hi_ns``],
    from ``pairs`` program runs; ``ns`` is 0 where no run was paired."""
    ns: float
    lo_ns: float
    hi_ns: float
    pairs: int


@dataclass
class Scoped:
    window_s: float
    offset: Offset
    devices: int
    # (program, scope label) -> device seconds of the ops' self time
    device_s: Dict[Tuple[str, str], float] = field(default_factory=dict)
    runs: Dict[str, int] = field(default_factory=dict)
    # (program, scope label, op name) -> device seconds
    ops: Dict[Tuple[str, str, str], float] = field(default_factory=dict)
    # (innermost chipbench span, repro spans outer/inner) -> idle seconds
    idle: Dict[Tuple[str, str], float] = field(default_factory=dict)
    spans: List[Span] = field(default_factory=list)

    def seconds(self, program: str, under: str,
                not_under: Tuple[str, ...] = ()) -> float:
        """Device seconds, in programs whose name holds ``program``, of
        ops under scope ``under`` and none of ``not_under``."""
        return sum(s for (p, label), s in self.device_s.items()
                   if program in p and under in label.split("/")
                   and not set(not_under) & set(label.split("/")))

    def runs_of(self, program: str) -> int:
        return sum(n for p, n in self.runs.items() if program in p)

    def idle_under(self, span: str) -> float:
        return sum(s for (_, path), s in self.idle.items()
                   if span in path.split("/"))

    def args(self, name: str) -> List[Dict[str, object]]:
        return [s.args for s in self.spans if s.name == name]


def scope_label(tf_op: str) -> str:
    """'layers/attention' from 'jit(f)/layers/while/body/attention/dot:'."""
    path = tf_op.rsplit(":", 1)[0].split("/")[1:-1]
    return "/".join(c for c in path if c in SCOPES)


def program_name(module: str) -> str:
    """'jit__decode_paged_fn' from 'jit__decode_paged_fn(1363...)'."""
    return re.sub(r"\(\d+\)$", "", module)


def clock_offset(runs: Dict[int, Tuple[float, float]],
                 host: Dict[int, List[float]]) -> Offset:
    """``runs``: run_id -> device (start, end); ``host``: run_id -> the
    start times of the host's events for that run."""
    lo, hi, pairs = -float("inf"), float("inf"), 0
    for rid, (s, e) in runs.items():
        ts = host.get(rid)
        if not ts or len(ts) < 2:
            continue
        lo = max(lo, min(ts) - s)
        hi = min(hi, max(ts) - e)
        pairs += 1
    if not pairs:
        return Offset(0.0, 0.0, 0.0, 0)
    return Offset((lo + hi) / 2, lo, hi, pairs)


def _self_times(evs: List[Tuple[float, float, int]]) -> Dict[int, float]:
    """Index -> duration less the events nested directly inside it, for
    (start, end, index) intervals that nest or do not meet."""
    out: Dict[int, float] = {}
    stack: List[Tuple[float, float, int]] = []
    for s, e, k in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out[k] = e - s
        if stack:
            out[stack[-1][2]] -= min(e, stack[-1][1]) - s
        stack.append((s, e, k))
    return out


def _idle(busy: List[Tuple[float, float]], w0: float, w1: float,
          spans: List[Span]) -> Dict[Tuple[str, str], float]:
    """Gaps of ``busy`` (sorted, disjoint) in [w0, w1), cut at span
    edges, each piece labelled by the spans open over it."""
    edges = sorted([(s.start_ns, 1, k) for k, s in enumerate(spans)]
                   + [(s.end_ns, 0, k) for k, s in enumerate(spans)])
    open_: set = set()
    j = 0
    out: Dict[Tuple[str, str], float] = {}

    def label() -> Tuple[str, str]:
        on = sorted((spans[k] for k in open_),
                    key=lambda s: (s.start_ns, -s.end_ns))
        bench = [s.name for s in on if s.name.startswith(BENCH_PREFIX)
                 and s.name != WINDOW]
        prog = [s.name for s in on if s.name.startswith(PROGRAM_PREFIX)]
        return (bench[-1] if bench else "", "/".join(prog))

    def advance(t: float) -> None:
        nonlocal j
        while j < len(edges) and edges[j][0] <= t:
            _, opening, k = edges[j]
            (open_.add if opening else open_.discard)(k)
            j += 1

    cursor = w0
    for s, e in busy + [(w1, w1)]:
        if s > cursor:
            advance(cursor)
            a = cursor
            while j < len(edges) and edges[j][0] < s:
                t = edges[j][0]
                if t > a:
                    key = label()
                    out[key] = out.get(key, 0.0) + (t - a) * 1e-9
                    a = t
                advance(t)
            if s > a:
                key = label()
                out[key] = out.get(key, 0.0) + (s - a) * 1e-9
        cursor = max(cursor, e)
    return out


def reduce_planes(planes: List[Plane]) -> Scoped:
    spans: List[Span] = []
    host_runs: Dict[int, List[float]] = {}
    devices = []
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            devices.append(p)
            continue
        for evs in p.lines.values():
            for ev in evs:
                if ev.name.startswith((BENCH_PREFIX, PROGRAM_PREFIX)):
                    spans.append(Span(ev.name, ev.start_ns, ev.end_ns,
                                      dict(ev.stats)))
                rid = ev.stats.get("run_id")
                if rid is not None:
                    host_runs.setdefault(int(rid), []).append(ev.start_ns)
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    dev_runs = {int(ev.stats["run_id"]): (ev.start_ns, ev.end_ns)
                for p in devices for ev in p.lines.get(MODULES_LINE, [])
                if "run_id" in ev.stats}
    off = clock_offset(dev_runs, host_runs)
    out = Scoped(window_s=(w1 - w0) * 1e-9, offset=off, devices=0,
                 spans=[s for s in spans if s.name.startswith(PROGRAM_PREFIX)
                        and w0 <= s.start_ns < w1])
    busy: List[Tuple[float, float]] = []
    for p in devices:
        ran = False
        programs: Dict[int, str] = {}
        for ev in p.lines.get(MODULES_LINE, []):
            m = re.search(r"\((\d+)\)$", ev.name)
            name = program_name(ev.name)
            if m:
                programs[int(m.group(1))] = name
            if min(ev.end_ns + off.ns, w1) > max(ev.start_ns + off.ns, w0):
                out.runs[name] = out.runs.get(name, 0) + 1
        ops = []
        for k, ev in enumerate(p.lines.get(OPS_LINE, [])):
            s = max(ev.start_ns + off.ns, w0)
            e = min(ev.end_ns + off.ns, w1)
            if e > s:
                ops.append((s, e, k))
        evs = p.lines.get(OPS_LINE, [])
        for k, secs in _self_times(ops).items():
            ev = evs[k]
            prog = programs.get(ev.meta.get("program_id"), "")
            tf_op = str(ev.meta.get("tf_op") or "")
            key = (prog, scope_label(tf_op) if tf_op else "")
            out.device_s[key] = out.device_s.get(key, 0.0) + secs * 1e-9
            ok = key + (ev.name.split(" = ", 1)[0].lstrip("%"),)
            out.ops[ok] = out.ops.get(ok, 0.0) + secs * 1e-9
            ran = True
        busy.extend((s, e) for s, e, _ in ops)
        out.devices += ran
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(busy):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    inner = [s for s in spans if s.name != WINDOW]
    out.idle = _idle(merged, w0, w1, inner)
    return out


def read_file(path: str) -> Scoped:
    return reduce_planes(read_planes(path))


def summary(sc: Scoped, program: str = "_decode_paged_fn") -> str:
    """One line: the clock offset, the idle split by program span, the
    idle share under no program span, and ``program``'s scoped time per
    run."""
    idle = sum(sc.idle.values())
    by_span: Dict[str, float] = {}
    for (_, path), s in sc.idle.items():
        inner = path.rsplit("/", 1)[-1] or "(none)"
        by_span[inner] = by_span.get(inner, 0.0) + s
    runs = sc.runs_of(program)
    per_run = {label or "(none)": round(1e3 * s / runs, 3)
               for (p, label), s in sorted(sc.device_s.items())
               if program in p and runs}
    parts = [
        f"clock_offset_ms={sc.offset.ns * 1e-6:.4f}",
        f"offset_bounds_ms=[{sc.offset.lo_ns * 1e-6:.4f},"
        f"{sc.offset.hi_ns * 1e-6:.4f}]",
        f"offset_pairs={sc.offset.pairs}",
        f"idle_s={idle:.4f}",
        "idle_by_span=" + ",".join(
            f"{k}:{v:.4f}" for k, v in sorted(by_span.items(),
                                              key=lambda kv: -kv[1])),
        "unscoped_idle_share=%.4f" % (by_span.get("(none)", 0.0) / idle
                                      if idle else 0.0),
        f"{program}_runs={runs}",
        f"{program}_ms_per_run=" + ",".join(
            f"{k}:{v}" for k, v in per_run.items()),
    ]
    return "trace: " + " ".join(parts)


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    sc = read_file(argv[0])
    print(summary(sc))
    for (prog, label, name), s in sorted(sc.ops.items(),
                                         key=lambda kv: -kv[1])[:20]:
        print(f"op: {prog} {label or '(none)'} {name} {s:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

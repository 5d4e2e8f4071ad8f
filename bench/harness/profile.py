"""From a profiler trace to device numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain event lists: the device's ops (the "XLA Ops" line, each
tagged with the program it ran in), its programs ("XLA Modules") and
the host thread that carries the benchmark's own spans (``bench.*``).
``reduce`` turns those lists, clipped to the traced window, into the
numbers the per-layer readers and the ``breakdown`` use:

- busy: the union of the intervals in which an op ran; idle is the rest
  of the window;
- each op's self time (its duration less that of ops nested in it, so a
  ``while`` is not counted twice with its body), keyed ``program/op``;
- each program's run count and time;
- each idle gap, charged to what the host was doing at its middle: the
  innermost ``bench.*`` span and, inside it, the innermost host event.

Times are in seconds.  The device and host lines of one trace share a
clock (nanoseconds from the trace's start).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start_s, end_s)

SPAN_PREFIX = "bench."
WINDOW_SPANS = ("bench.traced", "bench.window")


@dataclasses.dataclass
class TraceEvents:
    ops: List[Tuple[str, str, float, float]]    # (program, op, start, end)
    programs: List[Event]
    host: List[Event]                           # the bench's thread
    n_devices: int


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def _program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _tag_programs(ops: List[Event], programs: List[Event]):
    """Each op with the program whose run contains its start."""
    progs = sorted(programs, key=lambda e: e[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda x: x[1]):
        while j + 1 < len(progs) and progs[j + 1][1] <= s:
            j += 1
        prog = progs[j][0] if progs and progs[j][1] <= s <= progs[j][2] \
            else "?"
        out.append((prog, name, s, e))
    return out


def read_xplane(path: str) -> TraceEvents:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: List[Tuple[str, str, float, float]] = []
    programs: List[Event] = []
    n_devices = 0
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "CUSTOM" not in plane.name:
            n_devices += 1
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" not in lines or "XLA Ops" not in lines:
                continue
            progs = [(_program_name(e.name), e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                     for e in lines["XLA Modules"].events]
            dev_ops = [(_op_name(e.name), e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in lines["XLA Ops"].events]
            programs += progs
            ops += _tag_programs(dev_ops, progs)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                evs = [(e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in ln.events]
                if any(n.startswith(SPAN_PREFIX) for n, _, _ in evs):
                    host += evs
    return TraceEvents(ops, programs, host, max(n_devices, 1))


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def window_of(ev: TraceEvents) -> Tuple[float, float]:
    """The traced window: the outermost ``bench.traced`` (else
    ``bench.window``) span, else the extent of the device's ops."""
    for name in WINDOW_SPANS:
        spans = [(s, e) for n, s, e in ev.host if n == name]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
    return min(o[2] for o in ev.ops), max(o[3] for o in ev.ops)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(ops) -> Dict[str, float]:
    """Self seconds per ``program/op``: nested ops are subtracted from
    the op that contains them."""
    acc: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []           # [key, end, child_time, dur]

    def close(item):
        acc[item[0]] += item[3] - item[2]

    for prog, name, s, e in sorted(ops, key=lambda o: (o[2], -o[3])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack and e <= stack[-1][1]:
            stack[-1][2] += e - s
        elif stack:                   # overlaps without nesting
            close(stack.pop())
        stack.append([f"{prog}/{name}", e, 0.0, e - s])
    while stack:
        close(stack.pop())
    return dict(acc)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    op_self_s: Dict[str, float]
    op_total_s: Dict[str, float]
    program_runs: Dict[str, int]
    program_s: Dict[str, float]
    idle_by_host: Dict[str, float]

    def device_ops(self, n: int = 10) -> List[list]:
        top = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        top = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def kernel_s(self, substring: str) -> float:
        return sum(v for k, v in self.op_total_s.items()
                   if substring in k.split("/", 1)[1])


def _host_at(host: List[Event], t: float) -> str:
    """The innermost bench span covering ``t`` and, inside it, the
    innermost other host event."""
    cover = [(e - s, n) for n, s, e in host if s <= t < e]
    spans = sorted(c for c in cover if c[1].startswith(SPAN_PREFIX))
    others = sorted(c for c in cover if not c[1].startswith(SPAN_PREFIX))
    span = spans[0][1] if spans else "outside bench spans"
    if others and (not spans or others[0][0] < spans[0][0]):
        return f"{span}/{others[0][1]}"
    return span


def reduce(ev: TraceEvents, window: Optional[Tuple[float, float]] = None
           ) -> Reduction:
    w0, w1 = window or window_of(ev)
    ops = [(p, n, max(s, w0), min(e, w1)) for p, n, s, e in ev.ops
           if e > w0 and s < w1]
    busy = union([(s, e) for _, _, s, e in ops])
    busy_s = sum(e - s for s, e in busy) / ev.n_devices
    total: Dict[str, float] = collections.defaultdict(float)
    for p, n, s, e in ops:
        total[f"{p}/{n}"] += e - s
    runs: Dict[str, int] = collections.Counter()
    prog_s: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in ev.programs:
        if w0 <= s < w1:
            runs[n] += 1
            prog_s[n] += min(e, w1) - s
    idle: Dict[str, float] = collections.defaultdict(float)
    host = [h for h in ev.host if h[2] > w0 and h[1] < w1]
    t = w0
    for s, e in busy + [(w1, w1)]:
        if s > t:
            idle[_host_at(host, (t + s) / 2)] += s - t
        t = max(t, e)
    return Reduction(w1 - w0, busy_s, self_times(ops), dict(total),
                     dict(runs), dict(prog_s), dict(idle))

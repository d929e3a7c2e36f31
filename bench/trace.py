"""Reduction of one profiler trace to the numbers the per-layer metrics
read.

The harness puts its own spans (``jax.profiler.TraceAnnotation``) around
each layer it calls: ``bench.prepare:<op>``, ``bench.dispatch:<op>`` and
``bench.finish:<op>``, all inside one ``bench.window``.  They land in the
same trace as the device's events, on one clock.  From them:

* busy time of a chip: the union of the intervals in which one of its
  executables runs (the ``XLA Modules`` line: one event per run of an
  executable), inside the window;
* idle gaps: the rest of the window, each attributed to the harness span
  the host was in (split by overlap; ``between spans`` for the rest);
* host-only dispatch time: time inside the dispatch spans in which no
  chip runs an executable;
* executor time: the device time of the executor executables (names
  that hold ``pim_exec``), out of the device time of every executable,
  by executable name;
* device ops: the time of each operation (the ``XLA Ops`` line), for the
  breakdown only.  Ops nest -- a while loop and the fusions inside it are
  each an event -- so their sum counts nested time twice, and no metric
  reads it.  A traced run compiles without per-op trace events
  (``harness.TRACED_FLAGS``); the breakdown then lists the executables.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Iterable, List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OUTSIDE = "between spans"
EXECUTOR = "pim_exec"

Interval = Tuple[float, float]          # [start, end) in ns
Event = Tuple[str, float, float]        # (name, start, end) in ns


@dataclasses.dataclass
class Raw:
    """The events of one trace that the reduction reads."""
    spans: List[Event]                       # host spans named bench.*
    ops: Dict[int, List[Event]]              # chip -> device op events
    modules: Dict[int, List[Event]]          # chip -> executable runs


def dropped(stats) -> List[str]:
    """The stats of a plane that report events the profiler dropped."""
    out = []
    for name, value in stats:
        if "drop" in str(name).lower():
            try:
                if float(value) > 0:
                    out.append(f"{name}={value}")
            except (TypeError, ValueError):
                out.append(f"{name}={value}")
    return out


def load(path: str) -> Raw:
    """Read an ``.xplane.pb`` written by ``jax.profiler``; a trace whose
    planes report dropped events is refused, since it would undercount
    busy time."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Event] = []
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    for plane in data.planes:
        lost = dropped(plane.stats)
        if lost:
            raise ValueError(f"the profiler dropped events on {plane.name}: "
                             f"{', '.join(lost)}")
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            tail = plane.name[len(DEVICE_PLANE_PREFIX):]
            if not tail.isdigit():
                continue
            chip = int(tail)
            for line in plane.lines:
                into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if into is not None:
                    into.setdefault(chip, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Raw(spans=spans, ops=ops, modules=modules)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same time."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if e > lo and s < hi]


def length(merged: List[Interval]) -> float:
    return sum(e - s for s, e in merged)


def gaps(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi) that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Time covered by both of two disjoint sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def layer_of(span_name: str) -> str:
    """``bench.dispatch:fp_mul`` -> ``dispatch``."""
    return span_name[len(SPAN_PREFIX):].split(":", 1)[0]


@dataclasses.dataclass
class Reduced:
    window_s: float
    chips: int
    busy_s: float                      # mean over the chips
    span_s: Dict[str, float]           # layer -> seconds in its spans
    dispatch_host_s: float             # dispatch time with no device op
    executor_s: float                  # executors' device time, all chips
    kernels: Dict[str, float]          # executable -> seconds, all chips
    device_ops: Dict[str, float]       # op name -> seconds, all chips
    idle_by_span: Dict[str, float]     # host span -> idle s, mean of chips

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.device_ops or self.kernels),
                "idle_gaps": top(self.idle_by_span)}


def reduce(raw: Raw) -> Reduced:
    windows = [(s, e) for n, s, e in raw.spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    leaves = [(n, s, e) for n, s, e in raw.spans if n != WINDOW_SPAN]
    chips = sorted(set(raw.ops) | set(raw.modules))
    if not chips:
        raise ValueError("the trace holds no device events")

    if set(raw.modules) != set(chips):
        raise ValueError(f"chips {sorted(set(chips) - set(raw.modules))} "
                         f"have op events but no executable events")
    busy = {}
    for chip in chips:
        busy[chip] = clip(union((s, e) for _, s, e in raw.modules[chip]),
                          lo, hi)
    any_busy = union(iv for chip in chips for iv in busy[chip])

    span_s: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in leaves:
        span_s[layer_of(n)] += (min(e, hi) - max(s, lo)) / 1e9
    dispatch = union((s, e) for n, s, e in leaves
                     if layer_of(n) == "dispatch")
    dispatch = clip(dispatch, lo, hi)
    dispatch_host_s = (length(dispatch) - overlap(dispatch, any_busy)) / 1e9

    kernels: Dict[str, float] = collections.defaultdict(float)
    device_ops: Dict[str, float] = collections.defaultdict(float)
    for chip in chips:
        for n, s, e in raw.modules[chip]:
            kernels[n] += length(clip([(s, e)], lo, hi)) / 1e9
        for n, s, e in raw.ops.get(chip, []):
            device_ops[n] += length(clip([(s, e)], lo, hi)) / 1e9

    idle: Dict[str, float] = collections.defaultdict(float)
    leaf_iv = [(n, [(max(s, lo), min(e, hi))]) for n, s, e in leaves
               if e > lo and s < hi]
    for chip in chips:
        for g in gaps(busy[chip], lo, hi):
            rest = g[1] - g[0]
            for n, iv in leaf_iv:
                t = overlap([g], iv)
                if t:
                    idle[n] += t / 1e9 / len(chips)
                    rest -= t
            if rest > 0:
                idle[OUTSIDE] += rest / 1e9 / len(chips)

    return Reduced(
        window_s=(hi - lo) / 1e9, chips=len(chips),
        busy_s=sum(length(b) for b in busy.values()) / len(chips) / 1e9,
        span_s=dict(span_s), dispatch_host_s=dispatch_host_s,
        executor_s=sum(v for k, v in kernels.items() if EXECUTOR in k),
        kernels=dict(kernels),
        device_ops=dict(device_ops), idle_by_span=dict(idle))

"""Reduction of the program's own spans in one profiler trace.

While the program's tracer (``repro.runtime.telemetry.TRACER``) is
enabled, its every span is also a ``jax.profiler.TraceAnnotation`` named
``pim.*``: ``pim.prepare`` and its steps ``.cast``, ``.check`` and
``.bind``; ``pim.finish``; and per chunk ``pim.dispatch.pack``, ``.h2d``,
``.launch``, ``.wait``, ``.d2h``, ``.unpack``, then ``pim.dispatch.concat``
once a call.  A block profiled with the tracer on holds them in the same
trace as the harness's ``bench.*`` spans and the device's events, on one
clock.  The harness's own traced run (``harness.run_traced``) leaves the
tracer off, so this reduction is not part of its result line.  From the
spans, inside the block's ``bench.window``:

* per span name, the union of that name's intervals;
* the idle gaps of each chip, each split by the innermost span the host
  was in: a ``pim.*`` span is inner to the ``bench.*`` span around it;
  where no ``pim.*`` span covers a gap, the ``bench.*`` span does, as in
  ``bench/trace.py``; ``between spans`` for the rest.

A trace with no ``pim.*`` spans (the tracer off, or a program without
them) reduces to no span names, so every share of them reads None.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

from . import trace as btrace

PREFIX = "pim."

#: Shares of the window in the program's steps: name -> the spans whose
#: union it reads.
SHARES = {
    "prepare_cast_pct": ("pim.prepare.cast",),
    "prepare_check_pct": ("pim.prepare.check",),
    "host_pack_pct": ("pim.dispatch.pack",),
    "host_unpack_pct": ("pim.dispatch.unpack", "pim.dispatch.concat"),
    "transfer_host_pct": ("pim.dispatch.h2d", "pim.dispatch.d2h"),
    "device_wait_pct": ("pim.dispatch.wait",),
}

Span = Tuple[str, float, float, dict]      # (name, start, end, args), ns


@dataclasses.dataclass
class ProgramSpans:
    window_s: float
    intervals: Dict[str, List[btrace.Interval]]   # name -> union, ns
    idle_by_span: Dict[str, float]   # innermost span -> idle s, chip mean

    def seconds(self, *names: str) -> Optional[float]:
        """Window seconds inside any span of ``names``; None when none of
        them occurs."""
        found = [n for n in names if n in self.intervals]
        if not found:
            return None
        return btrace.length(btrace.union(
            iv for n in found for iv in self.intervals[n])) / 1e9

    def pct(self, *names: str) -> Optional[float]:
        """:meth:`seconds` as a share of the window, in %."""
        s = self.seconds(*names)
        if s is None or self.window_s <= 0:
            return None
        return 100.0 * s / self.window_s

    def shares(self) -> Dict[str, float]:
        """Each of :data:`SHARES` whose spans occur, in % of the window."""
        out = {name: self.pct(*spans) for name, spans in SHARES.items()}
        return {k: v for k, v in out.items() if v is not None}

    def top_idle(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def load(path: str) -> List[Span]:
    """The host events named ``pim.*`` of an ``.xplane.pb``, with their
    args."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def innermost(spans: List[btrace.Event], lo: float,
              hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut into (start, end, name) pieces, each named for the
    innermost span over it: ``pim.*`` before ``bench.*``, then the latest
    started (the spans of one thread nest), ``between spans`` where none
    is."""
    edges = []
    for i, (_, s, e) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1, i), (e, 0, i)]
    edges.sort()
    rank = [(n.startswith(PREFIX), s, -e) for n, s, e in spans]
    active: set = set()
    out: List[Tuple[float, float, str]] = []
    t = lo
    for when, opens, i in edges:
        if when > t:
            name = spans[max(active, key=rank.__getitem__)][0] \
                if active else btrace.OUTSIDE
            if out and out[-1][2] == name and out[-1][1] == t:
                out[-1] = (out[-1][0], when, name)
            else:
                out.append((t, when, name))
            t = when
        (active.add if opens else active.discard)(i)
    if t < hi:
        out.append((t, hi, btrace.OUTSIDE))
    return out


def split(gaps: List[btrace.Interval],
          pieces: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` under each name of ``pieces`` (both sorted
    and disjoint)."""
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            s, e, name = pieces[k]
            out[name] += min(e, ge) - max(s, gs)
            k += 1
    return out


def reduce(raw: btrace.Raw, program: List[Span]) -> ProgramSpans:
    """``program``'s spans (:func:`load`) against the harness's window,
    spans and device events in ``raw`` (``bench/trace.py``)."""
    windows = [(s, e) for n, s, e in raw.spans if n == btrace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {btrace.WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    by_name: Dict[str, list] = collections.defaultdict(list)
    for n, s, e, _ in program:
        by_name[n].append((s, e))
    intervals = {n: btrace.clip(btrace.union(iv), lo, hi)
                 for n, iv in by_name.items()}

    pieces = innermost([(n, s, e) for n, s, e, _ in program] +
                       [ev for ev in raw.spans
                        if ev[0] != btrace.WINDOW_SPAN], lo, hi)
    chips = sorted(raw.modules)
    idle: Dict[str, float] = collections.defaultdict(float)
    for chip in chips:
        busy = btrace.union((s, e) for _, s, e in raw.modules[chip])
        for name, ns in split(btrace.gaps(busy, lo, hi), pieces).items():
            idle[name] += ns / 1e9 / len(chips)
    return ProgramSpans(window_s=(hi - lo) / 1e9, intervals=intervals,
                        idle_by_span=dict(idle))

"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

``read`` turns the file into intervals: per device, the XLA programs that
ran (the "XLA Modules" line) and the operations (the "XLA Ops" line); on
the host, the benchmark's own spans (names starting with ``SPAN``).  The
rest works on intervals alone, so it can be checked on made-up ones:

* ``union`` is the device's busy time: the length of the union of its
  operations' intervals inside the traced window;
* ``program_times`` is the device time and the count of each program;
* ``idle_gaps`` are the stretches of the window with no operation on the
  device, each labelled by the innermost benchmark span open at its
  middle ("no span" where the host was outside every span).

Times are seconds on the trace's clock, which the profiler keeps the same
for host and device lines.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN = "bench:"
NO_SPAN = "no span"


@dataclasses.dataclass(frozen=True)
class Interval:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    programs: Dict[str, List[Interval]]     # device plane -> programs run
    ops: Dict[str, List[Interval]]          # device plane -> operations
    spans: List[Interval]                   # the benchmark's host spans


def read(path: str) -> Trace:
    """Parse one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    programs: Dict[str, List[Interval]] = {}
    ops: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                target = {"XLA Modules": programs, "XLA Ops": ops}.get(line.name)
                if target is None:
                    continue
                target.setdefault(plane.name, []).extend(
                    Interval(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Interval(e.name[len(SPAN):], e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events if e.name.startswith(SPAN))
    for planes in (programs, ops):
        for name in planes:
            planes[name].sort(key=lambda i: i.start)
    spans.sort(key=lambda i: i.start)
    return Trace(programs=programs, ops=ops, spans=spans)


def _clipped(intervals: Iterable[Interval], lo: float, hi: float
             ) -> List[Tuple[float, float]]:
    out = [(max(i.start, lo), min(i.end, hi)) for i in intervals]
    return sorted((a, b) for a, b in out if b > a)


def _merged(intervals: Iterable[Interval], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in _clipped(intervals, lo, hi):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def union(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] covered by at least one interval."""
    return sum(b - a for a, b in _merged(intervals, lo, hi))


def program_times(programs: Iterable[Interval], lo: float, hi: float
                  ) -> Dict[str, Tuple[int, float]]:
    """{program: (runs starting in the window, device seconds in it)}."""
    out: Dict[str, Tuple[int, float]] = {}
    for i in programs:
        if i.end <= lo or i.start >= hi:
            continue
        n, s = out.get(i.name, (0, 0.0))
        out[i.name] = (n + (lo <= i.start), s + min(i.end, hi)
                       - max(i.start, lo))
    return out


def innermost(spans: Sequence[Interval], t: float) -> str:
    """The name of the latest-starting span open at ``t``."""
    best = None
    for s in spans:
        if s.start > t:
            break
        if s.end >= t and (best is None or s.start >= best.start):
            best = s
    return best.name if best is not None else NO_SPAN


def idle_gaps(ops: Iterable[Interval], spans: Sequence[Interval],
              lo: float, hi: float) -> List[Tuple[str, float]]:
    """[(label, seconds)] of every stretch of [lo, hi] with no operation,
    labelled by the host span open at its middle."""
    out, cursor = [], lo
    for a, b in _merged(ops, lo, hi) + [(hi, hi)]:
        if a > cursor:
            out.append((innermost(spans, (cursor + a) / 2), a - cursor))
        cursor = max(cursor, b)
    return out


def by_label(gaps: Iterable[Tuple[str, float]]) -> Dict[str, float]:
    """Idle seconds summed per label."""
    out: Dict[str, float] = {}
    for name, s in gaps:
        out[name] = out.get(name, 0.0) + s
    return out


def top(items: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries as [[name, value], ...]."""
    return [[k, v] for k, v in sorted(items.items(), key=lambda kv: -kv[1])[:n]]

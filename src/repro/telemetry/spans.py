"""Program spans: where the serving loop's host time goes, tick by tick.

A ``Tracer`` keeps the most recent spans in a bounded in-memory ring of
preallocated arrays (``capacity`` records of name id, start, end, parent
record and request uid) plus per-name totals (count, seconds, child
seconds).  Nothing is written out on the hot path: readers take
``records()`` / ``totals()`` when they want them, and the operator export
(``telemetry.export``) renders the totals at export time.

Every span is stamped on ``time.monotonic()`` — the clock of
``Request.*_s`` — and also opens ``jax.profiler.TraceAnnotation`` under
``PREFIX + name``, so a profiler trace shows the program's spans on the
host line beside the device's programs, on the trace's own clock.

Span names are built once, by the code that owns them (an engine builds
its ``"<span>:<model>"`` names at construction); ``span``/``record`` only
look a name up.  No span forces a device sync: a ``*.sync.*`` span times a
read the code blocks on anyway.

The process default ``TRACER`` is on; ``PoolServer``, ``ModelEngine`` and
``GreenServRouter`` take ``tracer=`` and fall back to it.  With
``enabled = False`` a span costs one attribute test.  A tracer follows one
serving loop: its stack of open spans is not shared across threads.
"""
from __future__ import annotations

import array
import math
from time import monotonic
from types import SimpleNamespace
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
from jax import monitoring

PREFIX = "greenserv:"
CAPACITY = 1 << 18
COMPILE = "compile"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# the spans of the served path (docs/SERVING.md "Tracing"); engine spans
# carry a ":<model>" suffix
POOL_SPANS = ("pool.step", "pool.health", "pool.admit", "pool.complete",
              "pool.migrate", "pool.feedback", "pool.telemetry")
ROUTE_SPANS = ("route.features", "route.decide", "route.sync", "route.tilt",
               "feedback.update")
ENGINE_SPANS = ("engine.tick", "engine.admit", "engine.pack",
                "engine.dispatch.chunk", "engine.dispatch.decode",
                "engine.sync.tokens", "engine.advance", "engine.sync.length",
                "engine.capture", "engine.sync.capture", "engine.meter")
REQUEST_SPANS = ("request.queued", "request.slot_wait", "request.prefill",
                 "request.decode")


class SpanRecord(NamedTuple):
    name: str
    start: float          # time.monotonic() seconds
    end: float
    parent: int           # index into the same records() list, -1 = none
    uid: int              # request uid, -1 = none


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL = _NullSpan()
_profiling = jax.profiler.TraceAnnotation.is_enabled


class _Span:
    """One name's context manager.  Spans nest, so ``__exit__`` closes the
    innermost open span: the per-call state lives on the tracer's stacks,
    and one object per name serves every call without a uid."""

    __slots__ = ("tracer", "nid", "uid")

    def __init__(self, tracer: "Tracer", nid: int, uid: int = -1):
        self.tracer, self.nid, self.uid = tracer, nid, uid

    def __enter__(self):
        tr = self.tracer
        ann = None
        if _profiling():
            ann = jax.profiler.TraceAnnotation(tr._labels[self.nid])
            ann.__enter__()
        stack = tr._stack
        i = tr._n
        s = i & tr._mask
        if i > tr._mask:
            tr._drop(i)
        t0 = monotonic()
        tr._ring[s] = (self.nid, stack[-1][0] if stack else -1, self.uid, t0)
        tr._n = i + 1
        stack.append((i, self.nid, t0, ann))
        return self

    def __exit__(self, *exc):
        t1 = monotonic()
        tr = self.tracer
        stack = tr._stack
        i, nid, t0, ann = stack.pop()
        if i >= tr._n - tr.capacity:      # not overwritten while open
            tr._end[i & tr._mask] = t1
        tr._count[nid] += 1
        tr._seconds[nid] += t1 - t0
        if stack:
            tr._child[stack[-1][1]] += t1 - t0
        if ann is not None:
            ann.__exit__(*exc)
        return False


class Tracer:
    """Bounded ring of spans plus per-name totals; see the module doc."""

    def __init__(self, capacity: int = CAPACITY, enabled: bool = True):
        if capacity < 1 or capacity & (capacity - 1):
            raise ValueError(f"capacity {capacity} is not a power of two")
        self.enabled = enabled
        self.capacity = capacity
        self._mask = capacity - 1
        self._ids: Dict[str, int] = {}
        self._names: List[str] = []
        self._labels: List[str] = []
        self._spans: Dict[str, _Span] = {}
        self._count: List[int] = []
        self._seconds: List[float] = []
        self._child: List[float] = []
        # the ring: (name id, parent ring index, uid, start) and end
        self._ring: List[Optional[tuple]] = [None] * capacity
        self._end = array.array("d", bytes(8 * capacity))
        self._n = 0                   # records ever written
        self._dropped_until = -math.inf
        # the open spans, innermost last: (ring index, name id, start,
        # profiler annotation or None)
        self._stack: List[tuple] = []
        for name in POOL_SPANS + ROUTE_SPANS + REQUEST_SPANS + (COMPILE,):
            self.name(name)

    def name(self, name: str) -> str:
        """Register ``name`` (idempotent) and return it; owners call this
        once, at construction, for every span they will open."""
        if name.startswith("bench:"):
            raise ValueError(f"span {name!r}: the bench: prefix is the "
                             f"benchmark's own")
        if name not in self._ids:
            nid = len(self._names)
            self._ids[name] = nid
            self._names.append(name)
            self._labels.append(PREFIX + name)
            self._spans[name] = _Span(self, nid)
            self._count.append(0)
            self._seconds.append(0.0)
            self._child.append(0.0)
        return name

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(self._names)

    # -- the hot path ---------------------------------------------------------

    def span(self, name: str, uid: int = -1):
        """Context manager timing the enclosed block as ``name``."""
        if not self.enabled:
            return NULL
        if uid < 0:
            return self._spans[name]
        return _Span(self, self._ids[name], uid)

    def record(self, name: str, t0: float, t1: float, uid: int = -1,
               nested: bool = False) -> None:
        """Write a finished span after the fact (``nested``: its parent is
        the innermost span open now, whose self time it then takes)."""
        if not self.enabled:
            return
        nid = self._ids[name]
        i = self._n
        s = i & self._mask
        if i > self._mask:
            self._drop(i)
        outer = self._stack[-1] if nested and self._stack else None
        self._ring[s] = (nid, outer[0] if outer else -1, uid, t0)
        self._end[s] = t1
        self._n = i + 1
        self._count[nid] += 1
        self._seconds[nid] += t1 - t0
        if outer:
            self._child[outer[1]] += t1 - t0

    def _drop(self, i: int) -> None:
        """Record ``i`` is about to overwrite its slot's older record: the
        ring no longer covers the time before that record's end (now, if
        it is still open)."""
        old = i - self.capacity
        if any(e[0] == old for e in self._stack):
            end = monotonic()
        else:
            end = self._end[i & self._mask]
        self._dropped_until = max(self._dropped_until, end)

    def _on_compile(self, duration: float) -> None:
        t1 = monotonic()
        self.record(COMPILE, t1 - duration, t1, nested=True)

    # -- readers --------------------------------------------------------------

    def records(self) -> Tuple[List[SpanRecord], float]:
        """(the retained closed spans in the order they were written, the
        time from which on the ring holds every span).  That time is the
        oldest record's start, or once the ring has dropped records, the
        latest end among them: a reader whose interval starts earlier
        would see holes."""
        first = max(self._n - self.capacity, 0)
        still_open = {e[0] for e in self._stack}
        out: List[SpanRecord] = []
        index: Dict[int, int] = {}
        for i in range(first, self._n):
            if i in still_open:
                continue
            s = i & self._mask
            nid, parent, uid, t0 = self._ring[s]
            index[i] = len(out)
            out.append(SpanRecord(self._names[nid], t0, self._end[s],
                                  index.get(parent, -1), uid))
        if self._n > self.capacity:
            oldest = self._dropped_until
        elif self._n:
            oldest = self._ring[0][3]
        else:
            oldest = -math.inf
        return out, oldest

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """{name: (spans closed, seconds)} for every name that ran."""
        return {n: (c, s) for n, c, s in zip(self._names, self._count,
                                              self._seconds) if c}

    def heaviest(self, n: int = 10) -> List[Tuple[str, int, float, float]]:
        """The ``n`` names with the most self time (own seconds minus the
        seconds of their child spans): [(name, count, seconds, self s)]."""
        rows = [(name, c, s, s - ch) for name, c, s, ch in
                zip(self._names, self._count, self._seconds, self._child)
                if c]
        return sorted(rows, key=lambda r: -r[3])[:n]


def engine_span_names(tracer: Tracer, model: str) -> SimpleNamespace:
    """An engine's span names, ``"<span>:<model>"``, registered with
    ``tracer`` and reachable as attributes: ``engine.sync.length`` is
    ``.sync_length``."""
    return SimpleNamespace(**{
        base.split(".", 1)[1].replace(".", "_"):
            tracer.name(f"{base}:{model}") for base in ENGINE_SPANS})


TRACER = Tracer()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE:
        TRACER._on_compile(duration)


monitoring.register_event_duration_secs_listener(_on_duration)

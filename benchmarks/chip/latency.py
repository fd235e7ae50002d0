"""Latency arithmetic on the host clock's stamps.

A request is due at the time the open loop was to send it; its first token
is stamped when the server's step that produced it returned.  Time to
first token runs from the due time, so a stall that delays later requests'
sending or admission counts against them.  Percentiles are nearest-rank:
the smallest value with at least ``q`` of the sample at or below it.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0 < q <= 1) of ``values`` by nearest rank."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(int(math.ceil(q * len(ordered))) - 1, 0)]


def ttft_tail(requests: Iterable[Tuple[float, Optional[float], float]],
              q: float) -> float:
    """The ``q``-quantile of time to first token over ``(due, first token
    stamp or None, censor)`` triples, in the stamps' unit.  A request with
    no first token ranks above every answered one, and counts as waiting
    until ``censor`` (the end of the drain) if the quantile falls on it."""
    keyed = [(first is None, (first if first is not None else censor) - due)
             for due, first, censor in requests]
    return nearest_rank(keyed, q)[1]


def gaps_in(stamps: Sequence[float], lo: float, hi: float) -> List[float]:
    """Gaps between consecutive stamps of one request, both inside [lo, hi]."""
    inside = [t for t in stamps if lo <= t <= hi]
    return [b - a for a, b in zip(inside, inside[1:])]


def count_in(stamps: Iterable[float], lo: float, hi: float) -> int:
    return sum(lo <= t <= hi for t in stamps)

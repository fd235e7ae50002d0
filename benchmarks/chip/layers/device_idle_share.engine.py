"""Engine: the share of the traced slice in which the device ran no XLA
program while the host was inside an ``engine.*`` program span, in %.

The stretches of the slice between the programs that ran on the cell's
devices (``trace["devices"][p]["runs"]``) are labelled by the innermost
program span open at their middle (``trace_reduce.innermost``), the
program's ring times shifted onto the trace's clock by ``trace["offset"]``;
``request.*`` spans, a request's whole life, label nothing.  Where a
program straddles an end of the slice, the stretches before the first
whole run and after the last are left out, as the part that program
filled is unknown.  ``None`` without a device trace, without program
spans, or where the ring no longer holds the whole slice.

Printed to standard error: the slice's idle seconds by innermost span
(top 10) and the unlabelled share; the engine + scheduler + unlabelled
shares beside the idle share of the programs' own intervals; and the
clock check, the median distance between an ``engine.tick:<m>`` span's
start and that of the benchmark's ``engine.step:<m>`` span around it."""
import statistics
import sys

import trace_reduce

SCHEDULER = ("pool.", "route.", "feedback.", "compile")


def stretches(run):
    """{plane: [(label, seconds)]} of the slice's stretches without a
    program, or ``None`` (see the module doc)."""
    tr = run.trace
    if tr is None:
        return None
    planes = {p: tr["devices"][p] for p in run.planes if p in tr["devices"]}
    if not planes:
        return None
    try:
        from repro.telemetry import spans
    except ImportError:
        return None
    recs, oldest = spans.TRACER.records()
    off, lo, hi = tr["offset"], tr["lo"], tr["hi"]
    if oldest + off > lo:
        return None
    named = sorted((trace_reduce.Interval(r.name, r.start + off, r.end + off)
                    for r in recs if not r.name.startswith("request.")
                    and r.end + off >= lo and r.start + off <= hi),
                   key=lambda s: s.start)
    out = {}
    for p, dev in planes.items():
        runs = sorted(dev["runs"], key=lambda i: i.start)
        gaps, cursor = [], lo
        for i in runs + [trace_reduce.Interval("", hi, hi)]:
            if i.start > cursor:
                gaps.append((cursor, i.start))
            cursor = max(cursor, i.end)
        straddled = (sum(s for _, s in dev["programs"].values())
                     - sum(i.end - i.start for i in runs)) > 1e-9
        if straddled:
            gaps = [(a, b) for a, b in gaps if a > lo and b < hi]
        out[p] = _labelled(named, gaps)
    return out


def _labelled(named, gaps):
    """Each gap (in time order) with the innermost span at its middle."""
    out, active, j = [], [], 0
    for a, b in gaps:
        t = (a + b) / 2
        while j < len(named) and named[j].start <= t:
            active.append(named[j])
            j += 1
        active = [s for s in active if s.end >= t]
        out.append((trace_reduce.innermost(active, t), b - a))
    return out


def share(run, by_plane, prefixes):
    """% of the slice whose stretches carry a label starting with one of
    ``prefixes``, averaged over the cell's devices."""
    secs = [sum(s for name, s in gaps if name.startswith(prefixes))
            for gaps in by_plane.values()]
    return 100.0 * sum(secs) / len(secs) / run.trace["window_s"]


def _report(run, by_plane):
    tr = run.trace
    n, w = len(by_plane), tr["window_s"]
    labels = {}
    for gaps in by_plane.values():
        for name, s in trace_reduce.by_label(gaps).items():
            labels[name] = labels.get(name, 0.0) + s / n
    engine = sum(s for k, s in labels.items() if k.startswith("engine."))
    sched = sum(s for k, s in labels.items() if k.startswith(SCHEDULER))
    none = labels.get(trace_reduce.NO_SPAN, 0.0)
    module_idle = sum(w - sum(s for _, s in tr["devices"][p]["programs"]
                              .values()) for p in by_plane) / n
    print(f"[bench] idle by program span: {trace_reduce.top(labels)}; "
          f"unlabelled {none:.6f} s ({100 * none / w:.3f}%)",
          file=sys.stderr)
    print(f"[bench] idle: engine {100 * engine / w:.3f}% + scheduler "
          f"{100 * sched / w:.3f}% + unlabelled {100 * none / w:.3f}% = "
          f"{100 * (engine + sched + none) / w:.3f}% of the slice; no "
          f"program running {100 * module_idle / w:.3f}%", file=sys.stderr)
    from repro.telemetry import spans
    off, lo, hi = tr["offset"], tr["lo"], tr["hi"]
    ticks = [(r.start + off, r.name[len("engine.tick:"):])
             for r in spans.TRACER.records()[0]
             if r.name.startswith("engine.tick:")
             and lo - 1.0 <= r.start + off <= hi + 1.0]
    dist = []
    for s in tr["spans"]:
        if s.name.startswith("engine.step:") and lo <= s.start <= hi:
            model = s.name[len("engine.step:"):]
            inside = [t for t, m in ticks if m == model
                      and s.start - 1e-3 <= t <= s.end]
            if inside:
                dist.append(abs(inside[0] - s.start))
    print(f"[bench] clock check: median |engine.tick start - engine.step "
          f"start| {1e3 * statistics.median(dist):.4f} ms over "
          f"{len(dist)} ticks" if dist else
          "[bench] clock check: no engine.step span in the slice",
          file=sys.stderr)


def read(run):
    by_plane = stretches(run)
    if by_plane is None:
        return None
    _report(run, by_plane)
    return share(run, by_plane, ("engine.",))

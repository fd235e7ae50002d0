"""Engine: device-to-host reads the engine ticks block on, per tick.

The program's ``engine.sync.*`` spans (the token read-back, each finish
test's slot-length read, the prefix capture's copies) under the
``engine.tick:*`` spans that ran a tick program (an ``engine.dispatch.*``
child) inside the window, over the number of those ticks.  ``None`` where
the program has no spans or its ring no longer holds the whole window.

Printed to standard error: the program spans recorded per scheduler step
(``pool.step``) in the window, which sets the tracer's own cost."""
import sys


def read(run):
    try:
        from repro.telemetry import spans
    except ImportError:
        return None
    recs, oldest = spans.TRACER.records()
    lo, hi = run.window
    if oldest > lo:
        return None

    def tick_of(i):
        while i >= 0 and not recs[i].name.startswith("engine.tick:"):
            i = recs[i].parent
        return i

    ran = set()
    for r in recs:
        if r.name.startswith("engine.dispatch."):
            t = tick_of(r.parent)
            if t >= 0 and lo <= recs[t].start and recs[t].end <= hi:
                ran.add(t)
    syncs = sum(tick_of(r.parent) in ran for r in recs
                if r.name.startswith("engine.sync."))
    inside = [r for r in recs if lo <= r.start and r.end <= hi
              and not r.name.startswith("request.")]
    steps = sum(r.name == "pool.step" for r in inside)
    if steps:
        print(f"[bench] program spans per scheduler step: "
              f"{len(inside) / steps:.2f} ({len(inside)} in {steps} steps)",
              file=sys.stderr)
    return syncs / len(ran) if ran else None

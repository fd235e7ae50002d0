"""Model step of the latent-attention expert model (layout ``mla_moe``):
its one-token program's share of the roofline, in %.

The least time the chip could take for every one-token tick of that model
inside the traced slice (the larger of operations over peak FLOP/s and
bytes over peak bandwidth, from ``flops.step_cost`` at the live slots'
cache positions, with the counts of ``arch/mla_moe.py``), summed, over the
device time of that model's ``greedy_step`` program in the slice, summed.

Each engine compiles its own ``greedy_step``, so the slice holds one such
program per model, told apart by name.  A program is the model's whose
``engine.tick:<model>`` program spans, put on the trace's clock by
``trace["offset"]``, overlap its runs the longest: a tick waits for its own
tokens, so each run lies inside its own tick, and a vote over all of a
program's runs stands even where the device's clock and the host's differ
by a few milliseconds (enough to move a run's start into the tick before).
``None`` without a device trace, without such a model or its spans, or
where the spans' ring no longer holds the whole slice.  Printed to
standard error: which program is whose, and which side of the roofline
binds."""
import sys


def _owners(runs, ticks):
    """{program name: model} by the ticks' overlap with its runs."""
    vote = {}
    for i in runs:
        mine = vote.setdefault(i.name, {})
        for a, b, model in ticks:
            overlap = min(i.end, b) - max(i.start, a)
            if overlap > 0:
                mine[model] = mine.get(model, 0.0) + overlap
    return {name: max(v, key=v.get) for name, v in vote.items() if v}


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    models = {n: c for n, c in run.cell.models.items()
              if c.get("layout") == "mla_moe"}
    if not models:
        return None
    try:
        from repro.telemetry import spans
    except ImportError:
        return None
    off, lo, hi = tr["offset"], tr["lo"], tr["hi"]
    recs, oldest = spans.TRACER.records()
    if oldest + off > lo:
        return None
    tick = "engine.tick:"
    ticks = [(r.start + off, r.end + off, r.name[len(tick):]) for r in recs
             if r.name.startswith(tick)
             and r.end + off >= lo and r.start + off <= hi]
    runs = [i for p, d in tr["devices"].items() if p in run.planes
            for i in d["runs"]
            if "greedy_step" in i.name and "chunk" not in i.name]
    owners = _owners(runs, ticks)
    least, n_ticks = 0.0, 0
    bound = {"compute": 0, "bandwidth": 0}
    for c in run.calls:
        if (c.model not in models or c.kind != "decode"
                or c.t0 + off < lo or c.t1 + off > hi):
            continue
        f, nbytes = run.flops.step_cost(models[c.model],
                                        [p for p, _ in c.fed])
        t, which = run.flops.least_time(f, nbytes, run.peaks.flops_bf16,
                                        run.peaks.hbm_bw)
        least += t
        n_ticks += 1
        bound[which] += 1
    mine = [i for i in runs if owners.get(i.name) in models]
    device = sum(i.end - i.start for i in mine)
    if device <= 0 or not n_ticks:
        return None
    counts = {}
    for i in runs:
        counts[i.name] = counts.get(i.name, 0) + 1
    print(f"[bench] greedy_step programs: "
          f"{ {n: (owners.get(n), k) for n, k in sorted(counts.items())} }; "
          f"{', '.join(sorted(models))}: {n_ticks} ticks, {len(mine)} "
          f"device runs, bound by {bound}", file=sys.stderr)
    return 100.0 * least / device

"""Model step: the one-token program's share of its roofline, in %.

The least time the chip could take for every one-token engine tick that
ran inside the traced slice (the larger of operations over peak FLOP/s and
bytes over peak bandwidth, from ``flops.step_cost`` at the live slots'
cache positions), summed, over the device time of the ``greedy_step``
programs that ran inside the slice, summed.  Each tick runs the program
once, so the two sums cover the same runs but for a tick cut by the
slice's ends."""
import sys


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    off, lo, hi = tr["offset"], tr["lo"], tr["hi"]
    models = run.cell.models
    least, ticks = 0.0, 0
    bound = {"compute": 0, "bandwidth": 0}
    for c in run.calls:
        if c.kind != "decode" or c.t0 + off < lo or c.t1 + off > hi:
            continue
        f, nbytes = run.flops.step_cost(models[c.model],
                                        [p for p, _ in c.fed])
        t, which = run.flops.least_time(f, nbytes, run.peaks.flops_bf16,
                                        run.peaks.hbm_bw)
        least += t
        ticks += 1
        bound[which] += 1
    runs = [i for p, d in tr["devices"].items() if p in run.planes
            for i in d["runs"]
            if "greedy_step" in i.name and "chunk" not in i.name]
    device = sum(i.end - i.start for i in runs)
    if device <= 0 or not ticks:
        return None
    print(f"[bench] greedy_step: {ticks} ticks, {len(runs)} device runs, "
          f"bound by {bound}", file=sys.stderr)
    return 100.0 * least / device

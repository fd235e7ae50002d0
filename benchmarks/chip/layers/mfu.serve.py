"""Whole step on the device: model operations of every token the engines
computed in the traced slice (prompt tokens fed and tokens generated,
spliced prefixes and empty-slot padding left out, ``flops.token_flops`` at
each token's position) over the slice's length times the chips times the
peak bf16 rate, in %."""


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    off, lo, hi = tr["offset"], tr["lo"], tr["hi"]
    models = run.cell.models
    total = 0.0
    for c in run.calls:
        if c.t0 + off < lo or c.t1 + off > hi:
            continue
        cfg = models[c.model]
        total += sum(run.flops.token_flops(cfg, p + j)
                     for p, n in c.fed for j in range(n))
    return 100.0 * total / (tr["window_s"] * run.chips * run.peaks.flops_bf16)

"""Engine: mean host milliseconds of the window's ``ModelEngine.step`` calls
that ran the one-token program (``greedy_step``), rwkv's token-wise prefill
included; each call ends by reading its tokens back, so its time spans the
device work."""


def read(run):
    lo, hi = run.window
    ticks = [c.t1 - c.t0 for c in run.calls
             if c.kind == "decode" and lo <= c.t0 and c.t1 <= hi]
    return 1e3 * sum(ticks) / len(ticks) if ticks else None

"""Engine: mean host milliseconds of the window's ``ModelEngine.step`` calls
that ran the chunked-prefill program (``greedy_chunk_step``); each call
ends by reading its tokens back, so its time spans the device work."""


def read(run):
    lo, hi = run.window
    ticks = [c.t1 - c.t0 for c in run.calls
             if c.kind == "chunk" and lo <= c.t0 and c.t1 <= hi]
    return 1e3 * sum(ticks) / len(ticks) if ticks else None

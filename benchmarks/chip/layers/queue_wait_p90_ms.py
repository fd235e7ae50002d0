"""Scheduler admission: the 90th percentile, over the window's admitted
requests, of the wait from the due time to admission (the program's
``Request.submit_s``, stamped when ``_admit_arrivals`` routes it), in ms.
The seconds in which the loop stood still stopping the profiler are taken
out of each wait they fall in."""


def read(run):
    a, b = run.stall
    waits = [r.request.submit_s - r.due
             - max(min(r.request.submit_s, b) - max(r.due, a), 0.0)
             for r in run.in_window()
             if r.request is not None and r.request.submit_s > 0]
    return 1e3 * run.latency.nearest_rank(waits, 0.90) if waits else None

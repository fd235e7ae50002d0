"""Device: the share of the traced slice in which no operation ran, on
each chip the cell uses, averaged over them, in %."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    planes = [tr["devices"][p] for p in run.planes if p in tr["devices"]]
    if not planes:
        return None
    busy = sum(d["busy_s"] for d in planes) / len(planes)
    return 100.0 * (1.0 - busy / tr["window_s"])

"""Router kernels: device milliseconds per call of the routing program
(``_fused_decide``: the featurize and LinUCB kernels), from the trace."""


def read(run):
    if run.trace is None:
        return None
    n, s = 0, 0.0
    for dev in run.trace["devices"].values():
        for name, (count, seconds) in dev["programs"].items():
            if "_fused_decide" in name:
                n, s = n + count, s + seconds
    return 1e3 * s / n if n else None

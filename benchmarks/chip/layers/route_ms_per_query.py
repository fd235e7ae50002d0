"""Router: host milliseconds per routed query over the window, from the
router's own ``decision_ms_total`` and ``n_routed`` counters."""


def read(run):
    a, b = run.counters["start"], run.counters["end"]
    n = b["n_routed"] - a["n_routed"]
    return (b["decision_ms"] - a["decision_ms"]) / n if n > 0 else None

"""Scheduler admission: the share of the traced slice in which the device
ran no XLA program while the host was inside a ``pool.*``, ``route.*``,
``feedback.*`` or ``compile`` program span, in %.  The stretches and their
labels are those of ``device_idle_share.engine``.

Printed to standard error: the host ms per routed query of each
``route.*`` span in the window (the decision clock,
``route_ms_per_query``, runs from ``route.decide``'s start to
``route.tilt``'s end)."""
import importlib.util
import pathlib
import sys

_path = pathlib.Path(__file__).with_name("device_idle_share.engine.py")
_spec = importlib.util.spec_from_file_location("layer_idle_engine", _path)
_engine = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_engine)

ROUTE = ("route.features", "route.decide", "route.sync", "route.tilt")


def _routing(run):
    from repro.telemetry import spans
    lo, hi = run.window
    n = run.counters["end"]["n_routed"] - run.counters["start"]["n_routed"]
    secs = dict.fromkeys(ROUTE, 0.0)
    for r in spans.TRACER.records()[0]:
        if r.name in secs and lo <= r.start and r.end <= hi:
            secs[r.name] += r.end - r.start
    if n > 0:
        print("[bench] routing in the window, host ms per routed query: "
              + ", ".join(f"{k} {1e3 * s / n:.4f}" for k, s in secs.items()),
              file=sys.stderr)


def read(run):
    by_plane = _engine.stretches(run)
    if by_plane is None:
        return None
    _routing(run)
    return _engine.share(run, by_plane, _engine.SCHEDULER)

"""Telemetry & adaptive energy-budget governance for the serving stack.

Turns "routing that measures energy" into "serving that governs energy":

  * ``metrics``  — O(1) counters/gauges/streaming-quantile histograms;
  * ``power``    — per-engine and pool-wide watts time-series;
  * ``budget``   — ``EnergyBudgetGovernor``: a Wh token bucket that
    modulates the router's λ online (``GreenServRouter.set_lambda``);
  * ``events``   — bounded structured event log;
  * ``spans``    — ``Tracer``: program spans at each layer boundary of the
    served path, in a bounded ring, on the profiler trace's host line too;
  * ``export``   — Prometheus text exposition + JSONL trace round-trip;
  * ``hub``      — the ``Telemetry`` facade ``PoolServer`` reports into.
"""
from repro.telemetry.budget import (EnergyBudgetGovernor,
                                    diurnal_carbon_intensity)
from repro.telemetry.events import Event, EventLog
from repro.telemetry.export import (dump_jsonl, load_jsonl, parse_prometheus,
                                    to_prometheus)
from repro.telemetry.hub import Telemetry
from repro.telemetry.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry, P2Quantile)
from repro.telemetry.power import PowerSample, PowerTrace
from repro.telemetry.spans import TRACER, Tracer

__all__ = [
    "EnergyBudgetGovernor", "diurnal_carbon_intensity",
    "Event", "EventLog",
    "dump_jsonl", "load_jsonl", "parse_prometheus", "to_prometheus",
    "Telemetry",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "P2Quantile",
    "PowerSample", "PowerTrace",
    "TRACER", "Tracer",
]

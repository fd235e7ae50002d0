"""Paper Table 4 + Table 3: per-query routing overhead and relative cost.

The overhead is read from the router's ``route.*`` spans (host ms per
routed query): featurization, the decision, its read-back, and the
energy tilts that build the decisions."""
from __future__ import annotations

import time
from typing import List

import numpy as np

from benchmarks.common import make_router, route_span_ms, stream
from repro.configs.pool import PAPER_POOL, make_profile
from repro.data import OutcomeSimulator
from repro.core.types import Feedback


def run(n_queries: int = 300):
    qs = stream(per_task=max(n_queries // 5, 1))[:n_queries]
    routers = {
        "linucb": make_router(algorithm="linucb"),
        "eps_greedy": make_router(algorithm="eps_greedy",
                                  features=(False, False, False)),
        "cts": make_router(algorithm="cts"),
    }
    sim = OutcomeSimulator(seed=3)
    decision_ms = {}
    span_ms = None
    for name, router in routers.items():
        for q in qs:
            d = router.route(q)
            acc, e, lat, _ = sim(q, router.pool[d.model_index].name)
            router.feedback(Feedback(query_uid=q.uid,
                                     model_index=d.model_index, accuracy=acc,
                                     energy_wh=e, latency_ms=lat))
        decision_ms[name] = router.mean_decision_ms
        if name == "linucb":
            span_ms = route_span_ms(router)
    return span_ms, decision_ms


def main(n_queries: int = 300) -> List[str]:
    span_ms, decision_ms = run(n_queries)
    lines = ["component,ms_per_query"]
    for span, ms in span_ms.items():
        lines.append(f"{span},{ms:.3f}")
    for name, ms in decision_ms.items():
        lines.append(f"routing_decision[{name}],{ms:.3f}")
    # the route.* spans tile route_batch, the decision clock included
    total = sum(span_ms.values())
    lines.append(f"total_pre_inference,{total:.3f}")
    lines.append("# paper Table 4: total 6.68-7.77 ms/query")
    # Table 3 analogue: overhead relative to modeled median inference latency
    lines.append("model,median_latency_ms,overhead_pct")
    for name, _, params_b in [(r[0], r[1], r[2]) for r in PAPER_POOL]:
        prof = make_profile(name, "x", params_b)
        lat = prof.latency_estimate_ms(8)     # short-answer tasks
        lines.append(f"{name},{lat:.1f},{100 * total / lat:.1f}%")
    return lines


if __name__ == "__main__":
    print("\n".join(main()))

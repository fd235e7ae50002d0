"""Benchmark aggregator: one section per paper table/figure, CSV output.

    PYTHONPATH=src python -m benchmarks.run [--fast|--full]

A section that raises prints its traceback and the run goes on to the
next one; the run then exits non-zero, naming every failed section.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller streams (CI-speed)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale T=2500 / 5-run settings")
    args = ap.parse_args()
    per_task = 100 if args.fast else 500        # default = paper's T=2,500
    n_runs = 1 if args.fast else (5 if args.full else 2)

    t_start = time.time()

    from benchmarks import (bench_baselines, bench_cache, bench_chaos,
                            bench_disagg, bench_energy_model, bench_features,
                            bench_kernels, bench_lambda_sweep,
                            bench_model_addition, bench_overhead,
                            bench_pool_scale, bench_prefill,
                            bench_routerbench, bench_scenarios,
                            bench_telemetry)

    failed = []

    def section(title, fn):
        t0 = time.time()
        try:
            lines = fn()
        except Exception:  # noqa: BLE001 — recorded, and fails the run
            failed.append(title)
            lines = ["# FAILED", traceback.format_exc()]
        print(f"\n== {title} ({time.time() - t0:.1f}s) ==")
        print("\n".join(lines))
        sys.stdout.flush()

    section("Fig2+3: GreenServ vs baselines",
            lambda: bench_baselines.main(per_task=per_task))
    section("Fig4/A4: lambda sweep",
            lambda: bench_lambda_sweep.main(per_task=max(per_task // 2, 50),
                                            n_runs=n_runs))
    section("Fig5: feature ablation",
            lambda: bench_features.main(per_task=max(per_task // 2, 50),
                                        n_runs=n_runs))
    section("Featurization: host vs device throughput + decision latency",
            lambda: bench_features.perf_main(n_iter=2 if args.fast else 5,
                                             smoke=args.fast))
    section("Fig6: model addition",
            lambda: bench_model_addition.main(per_task=per_task))
    section("Table1: RouterBench",
            lambda: bench_routerbench.main(n_per_task=max(per_task // 2, 50)))
    section("Scenario lab: flash crowd / duplicate flood / pool churn",
            lambda: bench_scenarios.main(smoke=args.fast,
                                         artifact_prefix=None))
    section("Table3+4: overhead",
            lambda: bench_overhead.main(n_queries=per_task))
    section("Telemetry: overhead + energy-budget governance",
            lambda: bench_telemetry.main(per_task=max(per_task // 2, 60)))
    section("Chunked prefill: TTFT steps vs chunk size",
            lambda: bench_prefill.main(
                prompt_len=48 if args.fast else 96,
                chunks=[1, 8] if args.fast else [1, 4, 8, 16]))
    section("GreenCache: hit rates + avoided joules vs --cache-mode off",
            lambda: bench_cache.main(n_queries=36 if args.fast else 120,
                                     smoke=args.fast))
    section("Disaggregated serving: tail TTFT + joules vs monolithic",
            lambda: bench_disagg.main(n_users=240 if args.fast else 2000,
                                      smoke=args.fast, artifact=None))
    section("Fleet: sharded-pool weak scaling under a shard kill",
            lambda: bench_pool_scale.main(
                per_shard=100 if args.fast else 250,
                smoke=args.fast, artifact=None))
    section("Energy cost model: forecast MAE + routing non-regression",
            lambda: bench_energy_model.main(
                n_queries=48 if args.fast else 120, smoke=args.fast,
                artifact=None))
    section("Chaos: reliability layer vs fault storm (goodput + breaker)",
            lambda: bench_chaos.main(
                per_task=20 if args.fast else 60, smoke=args.fast,
                fleet=not args.fast, artifact=None))
    section("Kernels: allclose + ref timing", bench_kernels.main)
    print(f"\n== total {time.time() - t_start:.1f}s ==")
    if failed:
        sys.exit(f"{len(failed)} section(s) failed: {'; '.join(failed)}")


if __name__ == "__main__":
    main()

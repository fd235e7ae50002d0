"""Serving launcher: GreenServ pool server over real JAX models.

Builds a heterogeneous pool of real JAX models (one per requested arch
family; reduced configs by default, published widths with
``--published-widths``), the GreenServ router with all three context
features, the GreenCache reuse layer (``--cache-mode``, default prefix-KV
reuse), and the continuous-batching scheduler; then drives a synthetic
query stream through it with hedging and fault injection available as
flags.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --queries 60 \
        --pool granite-3-8b rwkv6-1.6b qwen2-moe-a2.7b --hedge 40 \
        --prefill-chunk 8 --cache-mode full --semantic-threshold 0.92

    # published widths on one accelerator (weights in bf16)
    PYTHONPATH=src python -m repro.launch.serve --published-widths \
        --pool h2o-danube-3-4b rwkv6-1.6b --max-len 1024 --queries 8
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import numpy as np

from repro.cache import CACHE_MODES, GreenCache
from repro.configs import ARCH_IDS, get_config
from repro.costmodel import EnergyCostModel
from repro.core.pool import ModelPool
from repro.core.router import GreenServRouter
from repro.core.types import ModelProfile, Query, RouterConfig
from repro.data import stream as stream_lib
from repro.data import tokenizer as tok
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ModelEngine, PoolServer
from repro.telemetry import EnergyBudgetGovernor, Telemetry, dump_jsonl


def build_real_pool(arch_ids: List[str], max_batch: int = 4,
                    max_len: int = 192, seed: int = 0,
                    prefill_chunk: int = 8, disaggregate: bool = False,
                    smoke: bool = True):
    """Real engines + matching pool profiles.

    ``smoke`` (default) builds the reduced same-family configs with the
    byte tokenizer's vocabulary; ``smoke=False`` builds each arch at its
    published widths, depth and vocabulary (the byte tokenizer's ids fit
    inside every published vocabulary).  Weights are stored in bf16 either
    way (``ModelEngine``).

    ``prefill_chunk`` (prompt tokens per engine prefill tick, default 8 —
    recorded in ROADMAP conventions) cuts TTFT roughly by the chunk factor
    on attention-cached layouts; recurrent/ring layouts clamp to 1.

    With ``disaggregate`` each member also gets a decode twin sharing the
    primary's params (same weights, zero extra init cost beyond the twin's
    KV cache); the scheduler runs the pair role-specialized with KV
    migration at the phase boundary.  Returns ``(engines, pool,
    decode_engines)`` — the twin dict is empty when disaggregation is off,
    and layouts that can't migrate KV simply stay unified when attached."""
    engines: Dict[str, ModelEngine] = {}
    decode_engines: Dict[str, ModelEngine] = {}
    profiles: List[ModelProfile] = []
    for i, arch in enumerate(arch_ids):
        cfg = (get_config(arch, smoke=True, vocab_size=tok.VOCAB_SIZE,
                          max_seq_len=max_len) if smoke else get_config(arch))
        eng = ModelEngine(arch, cfg, jax.random.PRNGKey(seed + i),
                          max_batch=max_batch, max_len=max_len,
                          detokenize=tok.decode, prefill_chunk=prefill_chunk)
        engines[arch] = eng
        profiles.append(eng.profile)
        if disaggregate:
            twin = ModelEngine(arch, cfg, jax.random.PRNGKey(seed + i),
                               max_batch=max_batch, max_len=max_len,
                               params=eng.params, detokenize=tok.decode,
                               prefill_chunk=prefill_chunk, role="decode")
            decode_engines[arch] = twin
    return engines, ModelPool(profiles), decode_engines


def exact_match_accuracy(query: Query, resp) -> float:
    """EM against the stream's reference (the examples' quality signal —
    untrained smoke models rarely match, which is itself informative: the
    router learns their true (low) quality online)."""
    if not query.reference:
        return 0.0
    return float(query.reference.strip().lower() in resp.text.strip().lower())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pool", nargs="+", default=["granite-3-8b",
                                                  "rwkv6-1.6b",
                                                  "qwen2-moe-a2.7b"],
                    choices=ARCH_IDS)
    ap.add_argument("--queries", type=int, default=40)
    ap.add_argument("--published-widths", action="store_true",
                    help="serve each arch at its published widths, depth "
                         "and vocabulary (default: reduced smoke configs)")
    ap.add_argument("--max-len", type=int, default=192,
                    help="per-slot cache depth in tokens")
    ap.add_argument("--lam", type=float, default=0.4)
    ap.add_argument("--hedge", type=int, default=None,
                    help="hedge after N scheduler steps in queue")
    ap.add_argument("--fail-engine", default=None,
                    help="inject a failure into this engine mid-run")
    ap.add_argument("--energy-budget-wh", type=float, default=None,
                    help="cumulative Wh cap for the run; the governor "
                         "tightens λ online to stay under it")
    ap.add_argument("--metrics-out", default=None,
                    help="write the JSONL telemetry dump to this path")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens consumed per engine prefill tick "
                         "(1 = token-wise legacy path; TTFT drops roughly "
                         "by this factor on attention-cached layouts)")
    ap.add_argument("--cache-mode", default="prefix", choices=CACHE_MODES,
                    help="GreenCache layers: prefix (launcher default — "
                         "cross-query prompt-KV reuse), semantic "
                         "(near-duplicate response cache), full (both), "
                         "off")
    ap.add_argument("--kv-cache-blocks", type=int, default=512,
                    help="per-engine prefix-KV pool capacity in blocks "
                         "(8 tokens each, host memory; LRU-evicted)")
    ap.add_argument("--semantic-threshold", type=float, default=0.92,
                    help="cosine similarity floor for a semantic response "
                         "cache hit (task-type/cluster guards always "
                         "apply)")
    ap.add_argument("--semantic-ttl", type=float, default=None,
                    help="max age in seconds for semantic response-cache "
                         "entries; older answers age out (default: no "
                         "staleness bound)")
    ap.add_argument("--featurize", default="auto",
                    choices=["auto", "host", "device"],
                    help="featurization placement: device = fused Pallas "
                         "featurize→score pipeline (kernels/featurize), "
                         "host = reference numpy path, auto = device on "
                         "TPU (elsewhere Pallas runs in interpret mode)")
    ap.add_argument("--cost-model", default="on", choices=["on", "off"],
                    help="predictive energy cost model (docs/ENERGY.md): "
                         "pre-dispatch Wh forecasts tilt routing, charge "
                         "the governor's in-flight budget, and calibrate "
                         "online from the metered joule ledger")
    ap.add_argument("--admission-planner", action="store_true",
                    help="energy-aware admission: defer arrivals whose "
                         "predicted Wh would breach the governor's "
                         "remaining budget this tick (needs --cost-model "
                         "on and --energy-budget-wh)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="role-specialized serving: each member gets a "
                         "decode twin (shared params); prompts prefill on "
                         "the primary, KV migrates at the phase boundary, "
                         "decode streams from the twin (layouts without a "
                         "full-depth KV cache stay unified)")
    args = ap.parse_args()

    print(f"[serve] compilation cache: {enable_compile_cache()}")
    engines, pool, decode_engines = build_real_pool(
        args.pool, max_len=args.max_len, prefill_chunk=args.prefill_chunk,
        disaggregate=args.disaggregate, smoke=not args.published_widths)
    config = RouterConfig(lam=args.lam, energy_scale_wh=0.05,
                          featurize=args.featurize)
    router = GreenServRouter(config, pool)
    queries = stream_lib.make_stream(per_task=max(args.queries // 5, 1))
    queries = queries[: args.queries]
    governor = None
    if args.energy_budget_wh is not None:
        governor = EnergyBudgetGovernor(args.energy_budget_wh,
                                        horizon_queries=len(queries))
    telemetry = Telemetry(governor=governor)
    cache = GreenCache(mode=args.cache_mode,
                       kv_cache_blocks=args.kv_cache_blocks,
                       semantic_threshold=args.semantic_threshold,
                       semantic_ttl_s=args.semantic_ttl)
    cost_model = (EnergyCostModel() if args.cost_model == "on" else None)
    server = PoolServer(router, engines, tokenizer=tok.encode,
                        hedge_after_steps=args.hedge,
                        accuracy_fn=exact_match_accuracy,
                        telemetry=telemetry,
                        prefill_chunk=args.prefill_chunk,
                        cache=cache,
                        decode_engines=decode_engines or None,
                        cost_model=cost_model,
                        admission_planner=args.admission_planner)
    t0 = time.monotonic()
    # continuous-batching drive: arrivals park in the scheduler's queue and
    # are admitted into free prefill slots each tick (routing happens at
    # admission, so the bandit sees the live queue state)
    for i, q in enumerate(queries):
        server.enqueue(q)
        if args.fail_engine and i == len(queries) // 2:
            engines[args.fail_engine].inject_failure()
        server.step()
    server.run_until_drained()
    wall = time.monotonic() - t0

    counts = router.selection_counts()
    print(f"[serve] {len(server.responses)}/{len(queries)} queries in "
          f"{wall:.1f}s; restarts={server.stats['restarts']} "
          f"hedges={server.stats['hedges']} "
          f"migrations={server.stats['migrations']}")
    for name, c in zip(pool.names, counts):
        print(f"  {name:20s} selected {int(c):4d}×")
    total_wh = sum(r.energy_wh for r in server.responses.values())
    print(f"  total modeled energy: {total_wh:.4f} Wh; mean routing "
          f"overhead {router.mean_decision_ms:.2f} ms/query")
    if args.cache_mode != "off":
        cs = cache.stats()
        sem = cs.get("semantic", {})
        pre = cs.get("prefix", {})
        hit_tokens = sum(p["hit_tokens"] for p in pre.values())
        blocks = sum(p["blocks"] for p in pre.values())
        print(f"  cache[{args.cache_mode}]: semantic hits "
              f"{sem.get('hits', 0)}/{sem.get('lookups', 0)}; prefix hit "
              f"tokens {hit_tokens}; {blocks} KV blocks resident "
              f"({server.stats['cache_hits']} short-circuits)")
    if cost_model is not None:
        cm = cost_model.stats()
        print(f"  cost model: {cm['n_reconciled']}/{cm['n_predicted']} "
              f"forecasts reconciled; MAE {cm['mae_ratio']:.1%} of metered "
              f"Wh; deferred admissions {server.stats['deferred']}")
    print(telemetry.summary())
    if args.metrics_out:
        n = dump_jsonl(args.metrics_out, telemetry.registry, telemetry.power,
                       telemetry.events,
                       meta={"queries": len(queries), "wall_s": wall,
                             "lam": args.lam,
                             "budget_wh": args.energy_budget_wh})
        print(f"[serve] wrote {n} telemetry rows to {args.metrics_out}")


if __name__ == "__main__":
    main()

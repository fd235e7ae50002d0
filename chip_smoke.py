#!/usr/bin/env python3
"""Bring-up smoke test: GreenServ served on a TPU at published widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four one-chip replicas (fleet only)

One chip: ``h2o-danube-3-4b`` and ``rwkv6-1.6b`` at their published
widths, depths and vocabularies, weights in bf16 and drawn from fixed
seeds, behind the GreenServ router (device featurization: the Pallas
``featurize`` and ``linucb`` kernels), GreenCache prefix reuse and
chunked prefill.  Queries from ``data.stream.make_stream`` enter through
``PoolServer.enqueue`` -> ``step`` -> ``run_until_drained``.  Checks:
every query completes with >= 1 token, nothing fails or restarts, a
prefix splice and a chunked-prefill tick happen, the routing program
holds ``tpu_custom_call``, host and device routing decide identically,
one danube prompt's prefill logits match ``api.forward`` within
``LOGIT_TOL``, and the device's peak memory fits its HBM.

Four chips: four replicas of the same pool behind ``FleetController``,
each replica's params, KV cache and router state on its own chip.  Each
shard serves one query at a time, so every query runs exactly the
programs it would run alone; the same queries then run on one replica,
on the model each was routed to in the fleet, and the generated tokens
must be identical.

Every failed check raises, so the exit code is non-zero.  The script
refuses to run unless JAX's first device is a TPU.  Compile seconds
(engine warm-up) are reported apart from serve seconds; the persistent
compilation cache is placed by ``repro.launch.compile_cache``.  The last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cache import GreenCache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.energy import chip_peaks  # noqa: E402
from repro.core.pool import ModelPool  # noqa: E402
from repro.core.router import GreenServRouter  # noqa: E402
from repro.core.types import Feedback, Query, RouterConfig, TaskType  # noqa: E402
from repro.data import stream as stream_lib  # noqa: E402
from repro.data import tokenizer as tok  # noqa: E402
from repro.fleet import base_model_name, build_fleet, plan_fleet  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_real_pool  # noqa: E402
from repro.models import api  # noqa: E402
from repro.serving import ModelEngine, PoolServer  # noqa: E402
from repro.serving.engine import engine_profile  # noqa: E402

POOL = ("h2o-danube-3-4b", "rwkv6-1.6b")
MAX_BATCH = 4
MAX_LEN = 1024          # below danube's 4096 window: full-depth KV cache
PREFILL_CHUNK = 8
# engine-path vs api.forward logits, both computed in bf16: max |diff| over
# max |forward logit|.  2^-4 is 16 bf16 ulps (2^-8) of the largest logit,
# room for 24 layers of differently ordered bf16 sums.
LOGIT_TOL = 2.0 ** -4
FLEET_SHARDS = 4


class SmokeFailure(AssertionError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def tpu_devices(n: int) -> list:
    devices = jax.devices()
    require(devices[0].platform == "tpu",
            f"no TPU: JAX's first device is {devices[0].platform!r}")
    require(len(devices) >= n, f"need {n} TPU chip(s), found {len(devices)}")
    return devices[:n]


def smoke_queries() -> tuple:
    """(first wave, second wave).  Two queries per task, first instances
    first: the whole first wave is admitted in one routing batch, so an
    untrained router breaks its tie toward the first arm and danube's four
    slots overflow into its queue.  The first query to finish (the
    shortest task) frees a slot for its same-task twin, whose prompt
    shares its template prefix: a prefix splice.  The second wave arrives
    after feedback has reached the bandit."""
    tasks = [TaskType.REASONING, TaskType.QA, TaskType.COMPLETION]
    qs = stream_lib.make_stream(per_task=2, seed=0, tasks=tasks)
    by_task = {t: [q for q in qs if q.task == t] for t in tasks}
    first = [by_task[TaskType.REASONING][0], by_task[TaskType.QA][0],
             by_task[TaskType.COMPLETION][0], by_task[TaskType.QA][1],
             by_task[TaskType.REASONING][1], by_task[TaskType.COMPLETION][1]]
    later = stream_lib.make_stream(per_task=1, seed=1,
                                   tasks=[TaskType.MATH, TaskType.QA])
    later = [Query(uid=100 + i, text=q.text, task=q.task,
                   reference=q.reference, max_new_tokens=q.max_new_tokens)
             for i, q in enumerate(later)]
    return first, later


def tree_devices(tree) -> set:
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def describe_engine(eng: ModelEngine) -> dict:
    leaves = jax.tree.leaves(eng.params)
    by_dtype = {}
    for x in leaves:
        by_dtype[str(x.dtype)] = by_dtype.get(str(x.dtype), 0) + x.size
    return {"params": sum(x.size for x in leaves), "by_dtype": by_dtype,
            "bytes": sum(x.nbytes for x in leaves),
            "devices": sorted(str(d) for d in tree_devices(eng.params)),
            "vocab": eng.cfg.vocab_size, "layers": eng.cfg.n_layers,
            "d_model": eng.cfg.d_model}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def check_routing_parity(pool: ModelPool, queries) -> dict:
    """Host (numpy reference) and device (Pallas) featurize->score paths
    decide identically on the smoke batch, after the same warm-up
    feedback (the guarantee tests/test_featurize_parity.py holds on CPU)."""
    routers = {m: GreenServRouter(RouterConfig(featurize=m), pool)
               for m in ("host", "device")}
    require(routers["device"]._device_featurize_active(),
            "device router fell back to host featurization")
    warm = stream_lib.make_stream(per_task=2, seed=7)
    decided = {}
    for mode, router in routers.items():
        for i, q in enumerate(warm):
            d = router.route(q)
            router.feedback(Feedback(
                query_uid=q.uid, model_index=d.model_index,
                accuracy=0.2 + 0.3 * (d.model_index + i % 2),
                energy_wh=0.01 * (d.model_index + 1), latency_ms=5.0))
        decided[mode] = router.route_batch(queries)
    host, dev = decided["host"], decided["device"]
    same = sum(a.model_index == b.model_index
               and a.context.task_label == b.context.task_label
               and a.context.cluster == b.context.cluster
               and a.context.complexity_bin == b.context.complexity_bin
               for a, b in zip(host, dev))
    finite = [np.isfinite(a.ucb_scores) & np.isfinite(b.ucb_scores)
              for a, b in zip(host, dev)]
    max_diff = max(float(np.max(np.abs(a.ucb_scores[f] - b.ucb_scores[f])))
                   for a, b, f in zip(host, dev, finite))
    arms = sorted({d.model_index for d in dev})
    return {"agree": same, "n": len(queries), "arms": arms,
            "max_ucb_diff": max_diff}


def check_prefill_logits(eng: ModelEngine, tokens) -> dict:
    """The engine's prefill path (``api.prefill_chunk`` in chunks of
    ``prefill_chunk`` into a fresh slot cache, the engine's params and
    config) against ``api.forward`` over the whole prompt."""
    cfg, chunk = eng.cfg, eng.prefill_chunk
    # the cache is donated, as the engine's ticks donate theirs: otherwise
    # every chunk enqueued ahead of the device holds a cache of its own
    # (about 20 of danube's 0.38 GB caches in flight, measured on a v5e)
    step = jax.jit(lambda p, c, t, n: api.prefill_chunk(p, t, c, cfg, n),
                   donate_argnums=(1,))
    cache = api.init_cache(cfg, eng.max_batch, eng.max_len)
    for start in range(0, len(tokens), chunk):
        piece = tokens[start:start + chunk]
        slab = np.zeros((eng.max_batch, chunk), np.int32)
        slab[0, :len(piece)] = piece
        n_active = np.zeros((eng.max_batch,), np.int32)
        n_active[0] = len(piece)
        logits, cache = step(eng.params, cache, jnp.asarray(slab),
                             jnp.asarray(n_active))
    got = np.asarray(logits[0, len(piece) - 1], np.float32)
    ref = np.asarray(jax.jit(
        lambda p, t: api.forward(p, {"tokens": t}, cfg).logits[0, -1])(
            eng.params, jnp.asarray([tokens], jnp.int32)), np.float32)
    scale = float(np.max(np.abs(ref)))
    rel = float(np.max(np.abs(got - ref))) / scale
    return {"tokens": len(tokens), "max_abs_ref": scale, "rel_err": rel,
            "top1_equal": bool(np.argmax(got) == np.argmax(ref))}


def serve(server: PoolServer, waves) -> None:
    """The launcher's drive (``repro.launch.serve.main``): enqueue with a
    scheduler step per arrival, then drain; one wave after another."""
    for wave in waves:
        for q in wave:
            server.enqueue(q)
            server.step()
        server.run_until_drained()


def one_chip() -> jax.Device:
    (device,) = tpu_devices(1)
    peaks = chip_peaks(device.device_kind)
    log(f"device: {device.platform} {device.device_kind!r}; peaks "
        f"{peaks.flops_bf16:.3g} FLOP/s bf16, {peaks.hbm_bw:.3g} B/s, "
        f"{peaks.hbm_bytes:.3g} B HBM ({peaks.source})")

    t0 = time.perf_counter()
    engines, pool, _ = build_real_pool(
        list(POOL), max_batch=MAX_BATCH, max_len=MAX_LEN,
        prefill_chunk=PREFILL_CHUNK, smoke=False)
    jax.block_until_ready([e.params for e in engines.values()])
    log(f"init seconds: {time.perf_counter() - t0:.3f}; peak_bytes_in_use "
        f"{device.memory_stats()['peak_bytes_in_use']}")
    for name, eng in engines.items():
        info = describe_engine(eng)
        log(f"engine {name}: {info}")
        # rwkv keeps its per-channel decay/bonus vectors in float32
        require(info["by_dtype"].get("bfloat16", 0) >= 0.99 * info["params"],
                f"{name} weights not stored in bf16")
        require(info["vocab"] == get_config(name).vocab_size,
                f"{name} vocabulary overridden")
    require(engines["h2o-danube-3-4b"].prefill_chunk == PREFILL_CHUNK
            and "k" in engines["h2o-danube-3-4b"].cache,
            "danube lost its full-depth KV cache / chunked prefill")

    compile_s = sum(eng.warmup() for eng in engines.values())
    log(f"compile seconds (engine warm-up): {compile_s:.3f}")

    first, later = smoke_queries()
    parity = check_routing_parity(pool, first + later)
    log(f"routing parity host vs device: {parity}")
    require(parity["agree"] == parity["n"], "host/device routing disagree")

    router = GreenServRouter(RouterConfig(featurize="device"), pool)
    require(router._device_featurize_active(), "router not on device path")
    custom = "tpu_custom_call" in router.lower_decide(first).as_text()
    log(f"route_batch program holds tpu_custom_call: {custom}")
    require(custom, "router kernels are not compiled Pallas calls")

    server = PoolServer(router, engines, tokenizer=tok.encode,
                        prefill_chunk=PREFILL_CHUNK,
                        cache=GreenCache(mode="prefix"))
    t0 = time.perf_counter()
    serve(server, [first, later])
    serve_s = time.perf_counter() - t0
    queries = first + later
    served = {name: sum(r.model_name == name
                        for r in server.responses.values())
              for name in engines}
    hits = {n: e.prefix_hit_count() for n, e in engines.items()}
    chunk_ticks = {n: e.n_chunk_steps for n, e in engines.items()}
    log(f"serve seconds: {serve_s:.3f}")
    log(f"requests: done {server.stats['completed']}/{len(queries)}, "
        f"failed {server.stats['failed']}, restarts "
        f"{server.stats['restarts']}, per engine {served}")
    log(f"prefix hits {hits}; chunked-prefill ticks {chunk_ticks}")
    log("tokens per query: " + str({
        uid: r.output_tokens for uid, r in sorted(server.responses.items())}))
    require(sorted(server.responses) == sorted(q.uid for q in queries),
            "not every query got a response")
    require(all(r.output_tokens >= 1 for r in server.responses.values()),
            "a query finished without a generated token")
    require(server.stats["failed"] == 0 and not server.failed,
            "requests failed")
    require(server.stats["restarts"] == 0, "an engine restarted")
    require(sum(hits.values()) >= 1, "no prefix splice happened")
    require(sum(chunk_ticks.values()) >= 1, "no chunked-prefill tick ran")

    danube = engines["h2o-danube-3-4b"]
    logit = check_prefill_logits(danube, tok.encode(first[1].text))
    log(f"danube prefill logits vs api.forward: {logit} "
        f"(tolerance {LOGIT_TOL})")
    stats = device.memory_stats()
    peak = stats["peak_bytes_in_use"]
    log(f"peak_bytes_in_use: {peak} of {peaks.hbm_bytes:.3g} published "
        f"(device bytes_limit {stats.get('bytes_limit')})")
    require(logit["rel_err"] <= LOGIT_TOL, "prefill logits off forward")
    require(peak < peaks.hbm_bytes, "peak device memory above HBM")
    return device


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def build_pool_fleet(devices):
    """Four replicas of the pool behind ``FleetController``.  Shard 0's
    engines draw the weights; the other shards take copies on their own
    chips (identical weights, so tokens can be compared)."""
    plan = plan_fleet(len(devices), POOL, devices=devices)
    weights = {}

    def engine_factory(profile, spec):
        arch = base_model_name(profile.name)
        eng = ModelEngine(profile.name, get_config(arch),
                          jax.random.PRNGKey(POOL.index(arch)),
                          max_batch=MAX_BATCH, max_len=MAX_LEN,
                          params=weights.get(arch), detokenize=tok.decode,
                          prefill_chunk=PREFILL_CHUNK,
                          device=plan.shard_device(spec))
        weights.setdefault(arch, eng.params)
        return eng

    def router_factory(spec):
        return GreenServRouter(RouterConfig(featurize="device"), ModelPool(
            [engine_profile(a, get_config(a)) for a in spec.models]))

    fleet = build_fleet(plan, router_factory, engine_factory,
                        server_kwargs={"tokenizer": tok.encode,
                                       "prefill_chunk": PREFILL_CHUNK})
    return fleet, weights


def drive_one_per_shard(fleet, queries, max_steps: int = 20_000) -> None:
    """Dispatch one query per shard (least-loaded dispatch), step the fleet
    until all are answered, repeat."""
    n = len(fleet.shards)
    for i in range(0, len(queries), n):
        fleet.dispatch_many(queries[i:i + n])
        for _ in range(max_steps):
            if not fleet.unanswered:
                break
            fleet.step()
        require(not fleet.unanswered, "fleet did not drain")


def four_chips() -> jax.Device:
    devices = tpu_devices(FLEET_SHARDS)
    log(f"devices: {[str(d) for d in devices]} "
        f"{devices[0].device_kind!r}")
    t0 = time.perf_counter()
    fleet, weights = build_pool_fleet(devices)
    log(f"fleet built in {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    for shard in fleet.shards.values():
        for eng in shard.server.engines.values():
            eng.warmup()
    log(f"compile seconds (engine warm-up, all shards): "
        f"{time.perf_counter() - t0:.3f}")

    first, later = smoke_queries()
    queries = (first + later)[:2 * FLEET_SHARDS]
    t0 = time.perf_counter()
    drive_one_per_shard(fleet, queries)
    log(f"fleet serve seconds: {time.perf_counter() - t0:.3f}")

    homes = set()
    for name, shard in fleet.shards.items():
        srv = shard.server
        with shard.placed():
            kmeans = srv.router.context.kmeans.device_state()
        placed = {"router": tree_devices((srv.router.policy.state, kmeans))}
        for eng_name, eng in srv.engines.items():
            placed[eng_name] = tree_devices((eng.params, eng.cache))
        log(f"{name} on {shard.device}: " + ", ".join(
            f"{k} -> {sorted(str(d) for d in v)}" for k, v in placed.items())
            + f"; served {sorted(srv.responses)}; restarts "
            f"{srv.stats['restarts']}")
        require(all(v == {shard.device} for v in placed.values()),
                f"{name}: state off its shard's device")
        require(srv.stats["restarts"] == 0, f"{name}: engine restarted")
        homes.add(shard.device)
    require(len(homes) == FLEET_SHARDS, "replicas share devices")
    log(f"fleet: {fleet.stats}")
    require(fleet.stats["failovers"] == 0, "fleet failed over a shard")
    require(sorted(fleet.responses) == sorted(q.uid for q in queries)
            and not fleet.failures, "fleet lost requests")

    # the same queries through one replica (device 0, shard 0's weights),
    # each on the model the fleet routed it to, one at a time
    by_uid = {q.uid: q for q in queries}
    reference = {}
    for arch in POOL:
        mine = [by_uid[u] for u, r in sorted(fleet.responses.items())
                if r.model_name == arch]
        if not mine:
            continue
        eng = ModelEngine(arch, get_config(arch), jax.random.PRNGKey(0),
                          max_batch=MAX_BATCH, max_len=MAX_LEN,
                          params=weights[arch], detokenize=tok.decode,
                          device=devices[0])
        server = PoolServer(
            GreenServRouter(RouterConfig(featurize="device"),
                            ModelPool([engine_profile(arch,
                                                      get_config(arch))])),
            {arch: eng}, tokenizer=tok.encode, prefill_chunk=PREFILL_CHUNK)
        serve(server, [[q] for q in mine])
        reference.update({u: r.tokens for u, r in server.responses.items()})
    same = sum(fleet.responses[u].tokens == reference[u] for u in reference)
    log(f"tokens identical to the one-replica run: {same}/{len(queries)} "
        f"(models {sorted({r.model_name for r in fleet.responses.values()})})")
    require(same == len(queries), "fleet tokens differ from one replica")
    return devices[0]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica fleet and the "
                         "one-replica run it is compared with")
    args = ap.parse_args()
    tpu_devices(FLEET_SHARDS if args.four_chips else 1)
    log(f"compilation cache: {enable_compile_cache()}")
    device = four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

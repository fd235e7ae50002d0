"""Fleet subsystem: device-resident router state, sharded pools,
all-reduce merge, heartbeat fail-over, fleet checkpointing (-m fleet)."""
import copy

import numpy as np
import pytest

from repro.core.bandits import BanditPolicy
from repro.core.context import OnlineKMeans
from repro.core.pool import ModelPool
from repro.core.router import GreenServRouter
from repro.core.types import (Feedback, ModelProfile, Query, RouterConfig,
                              TaskType)
from repro.data.stream import make_stream
from repro.fleet import (FeedbackAllReduce, TransferLedger, base_model_name,
                         build_fleet, drive_fleet, plan_fleet)

pytestmark = pytest.mark.fleet


def _pool(n=4):
    return ModelPool([ModelProfile(name=f"m{i}", family="t",
                                   params_b=float(i + 1),
                                   ms_per_token=float(i + 1),
                                   prefill_ms=10.0)
                      for i in range(n)])


def _queries(n, seed=0):
    return make_stream(per_task=max(1, n // 5 + 1), seed=seed)[:n]


def _drive(router, queries, accs=None):
    """Route + close the loop for every query."""
    for i, q in enumerate(queries):
        d = router.route(q)
        acc = accs[i] if accs is not None else 0.5 + 0.4 * (i % 2)
        router.feedback(Feedback(query_uid=q.uid, model_index=d.model_index,
                                 accuracy=acc, energy_wh=0.02 + 0.01 * i,
                                 latency_ms=25.0))


# ---------------------------------------------------------------------------
# tentpole (a): device-resident state — zero per-call transfers
# ---------------------------------------------------------------------------

def test_route_batch_zero_state_transfers():
    """Steady-state device-path routing moves no persistent state across
    the host↔device boundary: after warm-up, N route/feedback rounds
    leave both transfer ledgers (bandit + k-means) flat."""
    cfg = RouterConfig(max_arms=16, featurize="device",
                       algorithm="linucb", solve_mode="sherman_morrison")
    router = GreenServRouter(cfg, _pool())
    qs = _queries(40)
    _drive(router, qs[:10])                     # warm-up: jit + initial h2d
    km = router.context.kmeans.transfers.snapshot()
    bd = router.policy.transfers.snapshot()
    _drive(router, qs[10:])                     # steady state
    assert router.context.kmeans.transfers.snapshot() == km, (
        "k-means state crossed host<->device during steady-state routing")
    assert router.policy.transfers.snapshot() == bd, (
        "bandit state crossed host<->device during steady-state routing")
    # reading the state back out is exactly one deliberate d2h per store
    router.state_dict()
    assert router.context.kmeans.transfers.d2h == km["d2h"] + 1
    assert router.policy.transfers.d2h == bd["d2h"] + 1


def test_kmeans_device_cache_identity_and_lazy_sync():
    km = OnlineKMeans(k=3, dim=8)
    rng = np.random.default_rng(0)
    km.update(rng.normal(size=8).astype(np.float32))
    dev1 = km.device_state()
    assert km.transfers.h2d == 1
    assert km.device_state() is dev1            # cached, no re-upload
    assert km.transfers.h2d == 1
    # device-side update: host mirror goes stale with zero transfers
    km.load_device_state(*dev1)
    d2h_before = km.transfers.d2h
    assert km.transfers.d2h == d2h_before
    _ = km.centroids                            # first host read syncs
    assert km.transfers.d2h == d2h_before + 1
    _ = km.counts                               # already synced
    assert km.transfers.d2h == d2h_before + 1


def test_transfer_ledger():
    led = TransferLedger()
    led.count_h2d()
    led.count_d2h(2)
    assert led.snapshot() == {"h2d": 1, "d2h": 2} and led.total == 3
    led.reset()
    assert led.total == 0


@pytest.mark.parametrize("algorithm,solve_mode", [
    ("cts", "sherman_morrison"),
    ("linucb", "cholesky"),
    ("eps_greedy", "sherman_morrison"),
])
def test_scan_select_matches_sequential(algorithm, solve_mode):
    """The batched lax.scan path replicates sequential select exactly —
    same arms, same scores, same final PRNG key (padding rows never
    consume a draw)."""
    cfg = RouterConfig(max_arms=8, algorithm=algorithm,
                       solve_mode=solve_mode, seed=7)
    pol_batch = BanditPolicy(cfg, n_arms=5)
    pol_seq = BanditPolicy(cfg, n_arms=5)
    rng = np.random.default_rng(1)
    X = rng.normal(size=(6, cfg.context_dim)).astype(np.float32)
    feas = np.ones((6, 5), bool)
    feas[2, :3] = False
    arms_b, scores_b = pol_batch.select_batch(X, feas)
    arms_s = []
    for i in range(X.shape[0]):
        arm, _ = pol_seq.select(X[i], feas[i])
        arms_s.append(arm)
    np.testing.assert_array_equal(arms_b, np.asarray(arms_s))
    np.testing.assert_array_equal(np.asarray(pol_batch.state.key),
                                  np.asarray(pol_seq.state.key))
    assert scores_b.shape == (6, cfg.max_arms)


# ---------------------------------------------------------------------------
# satellite: state_dict round-trips every policy variant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm,solve_mode", [
    ("linucb", "sherman_morrison"),
    ("linucb", "cholesky"),
    ("cts", "sherman_morrison"),
    ("eps_greedy", "sherman_morrison"),
])
def test_state_dict_route_equivalence(algorithm, solve_mode):
    """A restored router routes identically to the one that saved —
    including CTS (PRNG key round-trip) and the cholesky solve mode."""
    cfg = RouterConfig(max_arms=16, algorithm=algorithm,
                       solve_mode=solve_mode, seed=3, lam=0.35)
    router = GreenServRouter(cfg, _pool())
    _drive(router, _queries(25))
    saved = copy.deepcopy(router.state_dict())
    clone = GreenServRouter(RouterConfig(max_arms=16, algorithm=algorithm,
                                         solve_mode=solve_mode, seed=99),
                            _pool())
    clone.load_state_dict(saved)
    assert clone.config.lam == pytest.approx(cfg.lam)
    probe = _queries(12, seed=5)
    d_orig = [d.model_index for d in router.route_batch(probe)]
    d_clone = [d.model_index for d in clone.route_batch(probe)]
    assert d_orig == d_clone, (
        f"{algorithm}/{solve_mode}: restored router diverged")


# ---------------------------------------------------------------------------
# tentpole (b): sharded pool — exact all-reduce
# ---------------------------------------------------------------------------

def _replica(seed=11):
    cfg = RouterConfig(max_arms=16, seed=seed, lam=0.4)
    return GreenServRouter(cfg, _pool())


def test_allreduce_exact_merge():
    """After a sync, every replica's arm statistics equal the *sum* of
    both replicas' locally-applied updates (LinUCB stats are additive),
    and the replicas route identically."""
    r1, r2 = _replica(), _replica()
    qs = _queries(30)
    accs = [0.3 + 0.02 * i for i in range(30)]
    # both replicas route the same stream; feedback is split half/half
    for i, q in enumerate(qs):
        d1, d2 = r1.route(q), r2.route(q)
        if i < 15:
            r1.feedback(Feedback(query_uid=q.uid, model_index=d1.model_index,
                                 accuracy=accs[i], energy_wh=0.03,
                                 latency_ms=20.0))
        else:
            r2.feedback(Feedback(query_uid=q.uid, model_index=d2.model_index,
                                 accuracy=accs[i], energy_wh=0.03,
                                 latency_ms=20.0))
    lam_reg, d = r1.config.lambda_reg, r1.config.context_dim
    eye = lam_reg * np.eye(d)
    n = len(r1.pool)
    pre = []
    for r in (r1, r2):
        sd = r.policy.state_dict()
        pre.append({"xxt": np.asarray(sd["A"][:n], np.float64) - eye,
                    "b": np.asarray(sd["b"][:n], np.float64),
                    "counts": np.asarray(sd["counts"][:n], np.float64)})
    expected_A = eye + pre[0]["xxt"] + pre[1]["xxt"]
    expected_b = pre[0]["b"] + pre[1]["b"]
    expected_counts = pre[0]["counts"] + pre[1]["counts"]

    ar = FeedbackAllReduce(lam_reg, d)
    report = ar.sync({"s0": r1, "s1": r2})
    assert report["arms_updated"] == 2 * n
    for r in (r1, r2):
        sd = r.policy.state_dict()
        np.testing.assert_allclose(np.asarray(sd["A"][:n]), expected_A,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(sd["b"][:n]), expected_b,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(sd["counts"][:n]),
                                   expected_counts, rtol=1e-5)
        # maintained inverse rebuilt from the merged design matrix
        np.testing.assert_allclose(
            np.asarray(sd["A_inv"][:n]),
            np.linalg.inv(expected_A), rtol=1e-3, atol=1e-4)
    # idempotence: a second sync with no new feedback is a no-op
    before = np.asarray(r1.policy.state_dict()["A"][:n])
    ar.sync({"s0": r1, "s1": r2})
    np.testing.assert_allclose(np.asarray(r1.policy.state_dict()["A"][:n]),
                               before, rtol=1e-6)
    # behavioral convergence: merged replicas decide identically
    probe = _queries(10, seed=9)
    assert ([x.model_index for x in r1.route_batch(probe)]
            == [x.model_index for x in r2.route_batch(probe)])


def test_allreduce_checkpoint_roundtrip():
    r1, r2 = _replica(), _replica()
    _drive(r1, _queries(10, seed=1))
    _drive(r2, _queries(10, seed=2))
    ar = FeedbackAllReduce(r1.config.lambda_reg, r1.config.context_dim)
    ar.sync({"s0": r1, "s1": r2})
    ar2 = FeedbackAllReduce(r1.config.lambda_reg, r1.config.context_dim)
    ar2.load_state_dict(ar.state_dict())
    assert ar2.syncs == ar.syncs
    for base, stats in ar._global.items():
        for k, v in stats.items():
            np.testing.assert_allclose(ar2._global[base][k], v)


# ---------------------------------------------------------------------------
# fleet planning
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, id):  # noqa: A002 - mirrors jax device attr
        self.id = id


def test_plan_fleet_partitions_devices_disjointly():
    devices = [_FakeDevice(i) for i in range(8)]
    plan = plan_fleet(4, ["a", "b"], devices=devices)
    assert plan.n_shards == 4
    seen = [i for s in plan.shards for i in s.device_ids]
    assert sorted(seen) == list(range(8))       # disjoint, full coverage
    assert all(s.n_devices == 2 for s in plan.shards)
    assert all(s.models == ("a", "b") for s in plan.shards)
    # round-robin: shard i owns devices i, i+n_shards, ...
    assert plan.shards[1].device_ids == (1, 5)


def test_plan_fleet_oversubscribed_devices():
    devices = [_FakeDevice(0)]
    plan = plan_fleet(3, ["a"], devices=devices)
    assert [s.device_ids for s in plan.shards] == [(0,), (0,), (0,)]
    with pytest.raises(ValueError):
        plan_fleet(0, ["a"])
    with pytest.raises(ValueError):
        plan_fleet(2, [])


def test_base_model_name():
    assert base_model_name("qwen2.5-3b") == "qwen2.5-3b"
    assert base_model_name("qwen2.5-3b@shard1") == "qwen2.5-3b"


# ---------------------------------------------------------------------------
# controller: fail-over + checkpoint through the closed loop
# ---------------------------------------------------------------------------

def _small_fleet(n_shards, clk, seed=0, heartbeat_timeout_s=0.3):
    from repro.data.profiles import OutcomeSimulator
    from repro.serving.engine import SimEngine
    from repro.configs.pool import build_paper_pool

    keep_small = ["yi-34b", "gemma-3-27b", "qwen2.5-14b", "phi-4-14b",
                  "gemma-3-12b", "llama-3.1-8b", "qwen2.5-7b", "mistral-7b",
                  "qwen2.5-3b", "gemma-3-4b", "llama-3.2-3b",
                  "phi-4-mini-4b"]
    clock = lambda: clk["t"]  # noqa: E731
    sim = OutcomeSimulator(seed=seed)
    outcome = lambda q, m: sim(q, base_model_name(m))  # noqa: E731
    pool_names = [p.name for p in build_paper_pool(exclude=keep_small)]
    plan = plan_fleet(n_shards, pool_names)

    def router_factory(spec):
        cfg = RouterConfig(max_arms=16, seed=seed + spec.index, lam=0.4,
                           energy_scale_wh=0.45)
        return GreenServRouter(
            cfg, ModelPool(build_paper_pool(exclude=keep_small)))

    def engine_factory(profile, spec):
        return SimEngine(profile, outcome, steps_per_query=2,
                         concurrency=4, clock=clock)

    return plan, build_fleet(plan, router_factory, engine_factory,
                             sync_every=4,
                             heartbeat_timeout_s=heartbeat_timeout_s,
                             clock=clock)


def test_failover_zero_lost_requests():
    """Killing a shard mid-stream loses nothing: queries stranded on the
    dead shard (parked, in-flight, and those dispatched into the
    detection window) are redispatched to survivors, whose pools adopt
    the dead shard's engines as fresh arms."""
    from repro.data.scenarios import poisson_arrivals

    clk = {"t": 0.0}
    plan, ctrl = _small_fleet(2, clk)
    qs = _queries(120)
    arrivals = poisson_arrivals(len(qs), 12.0, seed=1)
    t_kill = arrivals[len(arrivals) // 3]
    victim = plan.shards[1].name
    drive_fleet(ctrl, qs, arrivals, clk,
                events=[(t_kill, lambda: ctrl.kill_shard(victim))])
    assert ctrl.stats["completed"] == len(qs)
    assert not ctrl.unanswered
    assert ctrl.stats["failovers"] == 1
    assert ctrl.stats["redispatched"] > 0, (
        "kill recovered no queries — the fail-over path went untested")
    assert ctrl.stats["adopted_engines"] == 4
    ev = [e for e in ctrl.events if e["kind"] == "failover"]
    assert len(ev) == 1 and ev[0]["shard"] == victim
    # adopted arms live on the survivor under suffixed names
    survivor = ctrl.shards[plan.shards[0].name]
    adopted = [n for n in survivor.server.router.pool.names if "@" in n]
    assert len(adopted) == 4
    assert all(base_model_name(n) in plan.shards[0].models
               for n in adopted)


def test_fleet_checkpoint_roundtrip(tmp_path):
    """Fleet-wide save/restore through distributed.checkpoint: a fresh
    fleet restored from the checkpoint routes exactly like the one that
    saved."""
    from repro.data.scenarios import poisson_arrivals

    clk = {"t": 0.0}
    _, ctrl = _small_fleet(2, clk)
    qs = _queries(60)
    drive_fleet(ctrl, qs, poisson_arrivals(len(qs), 10.0, seed=2), clk)
    assert ctrl.stats["syncs"] > 0
    ctrl.save_checkpoint(str(tmp_path), step=1)

    clk2 = {"t": 0.0}
    _, ctrl2 = _small_fleet(2, clk2, seed=0)
    assert ctrl2.load_checkpoint(str(tmp_path)) == 1
    probe = _queries(10, seed=7)
    for name, shard in ctrl.shards.items():
        a = [d.model_index
             for d in shard.server.router.route_batch(probe)]
        b = [d.model_index
             for d in ctrl2.shards[name].server.router.route_batch(probe)]
        assert a == b, f"restored {name} routes differently"
    assert ctrl2.allreduce.syncs == ctrl.allreduce.syncs


def test_heartbeat_virtual_clock_no_sleep():
    """Satellite: fault.Heartbeat takes an injectable clock — staleness
    is driven by modeled time, no wall-clock sleeping."""
    from repro.distributed.fault import HeartbeatMonitor

    t = {"now": 0.0}
    mon = HeartbeatMonitor(timeout_s=5.0, clock=lambda: t["now"])
    mon.register("s0")
    mon.register("s1")
    t["now"] = 4.0
    mon.beat("s0")
    t["now"] = 6.0
    assert mon.stale() == ["s1"]
    mon.deregister("s1")
    assert mon.stale() == []
    t["now"] = 20.0
    assert mon.stale() == ["s0"]


def test_slow_shard_tick_is_not_a_failover():
    """Shards step one after another, so one shard's long tick (a
    first-call compile) ages every heartbeat stamped before it.  A shard
    that beat during the tick is alive; only one that missed it can be
    failed over."""
    from repro.serving.engine import SimEngine

    clk = {"t": 0.0}
    plan = plan_fleet(2, ["m0", "m1"])

    class Compiling(SimEngine):
        def step(self):
            out = super().step()
            if self.queue and clk["t"] < 60.0:
                clk["t"] += 60.0          # longer than the 5 s timeout
            return out

    def engine_factory(profile, spec):
        cls = Compiling if spec.index == 1 else SimEngine
        return cls(profile, lambda q, m: (0.5, 0.01, 10.0, 4),
                   steps_per_query=2, clock=lambda: clk["t"])

    ctrl = build_fleet(
        plan, lambda spec: GreenServRouter(RouterConfig(max_arms=4),
                                           _pool(2)),
        engine_factory, heartbeat_timeout_s=5.0, clock=lambda: clk["t"])
    ctrl.dispatch_many(_queries(4))
    for _ in range(20):
        if not ctrl.unanswered:
            break
        ctrl.step()
    assert ctrl.stats["failovers"] == 0
    assert ctrl.stats["completed"] == 4

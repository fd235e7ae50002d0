"""Serving runtime: continuous batching, hedging, fault restart, addition."""
import jax
import numpy as np
import pytest

from repro.cache.prefix import PrefixCache
from repro.core.pool import ModelPool
from repro.core.router import GreenServRouter
from repro.core.types import ModelProfile, Query, RouterConfig, TaskType
from repro.configs import get_config
from repro.data import tokenizer as tok
from repro.data.profiles import OutcomeSimulator
from repro.data.stream import make_stream
from repro.serving import (ModelEngine, PoolServer, Request, RequestState,
                           SimEngine)


def _real_engine(name="rwkv6-1.6b", max_batch=3, max_len=96, seed=0):
    cfg = get_config(name, smoke=True, vocab_size=tok.VOCAB_SIZE)
    return ModelEngine(name, cfg, jax.random.PRNGKey(seed),
                       max_batch=max_batch, max_len=max_len,
                       detokenize=tok.decode)


def test_engine_continuous_batching_admits_midstream():
    eng = _real_engine(max_batch=2)
    reqs = [Request(query=Query(uid=i, text=f"q{i} text"),
                    prompt_tokens=tok.encode("hi")[:4], max_new_tokens=3)
            for i in range(4)]
    for r in reqs[:2]:
        eng.submit(r)
    done = []
    for step in range(40):
        done += eng.step()
        if step == 2:                 # queue more while slots are busy
            eng.submit(reqs[2])
            eng.submit(reqs[3])
        if len(done) == 4:
            break
    assert len(done) == 4
    assert {r.uid for r in done} == {0, 1, 2, 3}
    assert all(r.output_tokens <= 3 for r in done)
    assert all(r.energy_wh > 0 for r in done)


def _sim_server(n_models=4, hedge=None, steps_per_query=1, lam=0.4):
    profiles = [ModelProfile(name=f"sim{i}", family="s", params_b=i + 1.0)
                for i in range(n_models)]
    pool = ModelPool(profiles)
    sim = OutcomeSimulator(seed=1)

    def outcome(query, model):
        return 0.5, 0.01, 10.0, 4
    engines = {p.name: SimEngine(p, outcome, steps_per_query=steps_per_query)
               for p in profiles}
    router = GreenServRouter(RouterConfig(lam=lam, max_arms=16), pool)
    return PoolServer(router, engines, hedge_after_steps=hedge), engines


def test_pool_server_routes_and_completes():
    server, _ = _sim_server()
    qs = make_stream(per_task=2)
    for q in qs:
        server.submit(q)
    server.run_until_drained()
    assert len(server.responses) == len(qs)
    assert server.router.policy.state.t == len(qs)   # every query fed back


def test_hedging_fires_for_stuck_queue():
    server, engines = _sim_server(n_models=2, hedge=2, steps_per_query=50)
    qs = make_stream(per_task=2)[:6]
    for q in qs:
        server.submit(q)
    for _ in range(300):
        server.step()
        if not server.inflight:
            break
    assert server.stats["hedges"] > 0
    assert not server.inflight


def test_engine_failure_restart_requeues():
    server, engines = _sim_server(n_models=3)
    qs = make_stream(per_task=3)[:9]
    for i, q in enumerate(qs):
        server.submit(q)
        if i == 4:
            for e in engines.values():
                e.inject_failure()
        server.step()
    server.run_until_drained()
    assert server.stats["restarts"] >= 1
    assert len(server.responses) == 9


def test_restart_does_not_resurrect_answered_query():
    """A hedge loser sitting in a failed engine must not be re-routed:
    its query is already answered, and resurrecting it re-inserts a
    finished uid into inflight (run_until_drained would never drain)."""
    profiles = [ModelProfile(name=f"sim{i}", family="s", params_b=i + 1.0)
                for i in range(2)]
    pool = ModelPool(profiles)

    def outcome(query, model):
        return 0.5, 0.01, 10.0, 4
    # a fresh bandit routes to arm 0 (all scores tie) — make that engine
    # slow so the hedge onto the fast engine wins while the primary is
    # still queued
    engines = {"sim0": SimEngine(profiles[0], outcome, steps_per_query=50),
               "sim1": SimEngine(profiles[1], outcome, steps_per_query=1)}
    router = GreenServRouter(RouterConfig(max_arms=16), pool)
    server = PoolServer(router, engines, hedge_after_steps=1)
    q = make_stream(per_task=1)[0]
    req = server.submit(q)
    assert req.model_name == "sim0"
    for _ in range(10):
        server.step()
        if q.uid in server.responses:
            break
    assert q.uid in server.responses          # hedge won on sim1
    assert req.state == RequestState.CANCELLED
    # the cancelled primary still sits in sim0's queue; fail sim0 so
    # restart() resets it to QUEUED and hands it back for re-routing
    engines["sim0"].inject_failure()
    server.step()
    assert server.stats["restarts"] == 1
    server.run_until_drained(max_steps=200)   # must not TimeoutError
    assert len(server.responses) == 1
    assert not server.inflight


def test_runtime_model_addition_grows_router():
    server, engines = _sim_server(n_models=3)
    assert server.router.policy.n_arms == 3
    prof = ModelProfile(name="late-model", family="s", params_b=9.0)
    server.add_engine(prof, SimEngine(prof, lambda q, m: (0.9, 0.001, 5.0, 4)))
    assert server.router.policy.n_arms == 4
    qs = make_stream(per_task=6)
    for q in qs:
        server.submit(q)
        server.step()
    server.run_until_drained()
    counts = server.router.selection_counts()
    assert counts[3] > 0            # the new arm gets explored (≈adopted)


def test_chunked_prefill_cuts_ttft_by_chunk_factor():
    """A 64-token prompt reaches its first token in ~64 engine steps on the
    seed one-token path and ~64/8 steps with prefill_chunk=8 — the ≥4×
    TTFT reduction the chunked path exists for."""
    prompt = [1 + (i % 250) for i in range(64)]

    def steps_to_first_token(chunk):
        cfg = get_config("granite-3-8b", smoke=True, vocab_size=tok.VOCAB_SIZE)
        eng = ModelEngine("granite-3-8b", cfg, jax.random.PRNGKey(0),
                          max_batch=2, max_len=128, prefill_chunk=chunk)
        req = Request(query=Query(uid=0, text="long prompt"),
                      prompt_tokens=prompt, max_new_tokens=4)
        eng.submit(req)
        steps = 0
        while not req.generated:
            eng.step()
            steps += 1
            assert steps < 200
        assert req.first_token_s > 0      # TTFT recorded at first decode token
        return steps, eng

    steps_tokenwise, _ = steps_to_first_token(1)
    steps_chunked, eng = steps_to_first_token(8)
    assert steps_tokenwise >= 4 * steps_chunked
    assert steps_chunked <= -(-len(prompt) // 8) + 1   # ≈ ceil(64/8)
    # phase-split metering: the chunked run charged real prefill joules
    phases = eng.cumulative_joules_by_phase()
    assert phases["prefill"] > 0


def test_chunked_engine_decode_rides_along_with_prefill():
    """Continuous batching through the chunk step: a decoding request keeps
    producing tokens while a newly admitted long prompt prefills in slabs."""
    cfg = get_config("granite-3-8b", smoke=True, vocab_size=tok.VOCAB_SIZE)
    eng = ModelEngine("granite-3-8b", cfg, jax.random.PRNGKey(1),
                      max_batch=2, max_len=128, prefill_chunk=8)
    first = Request(query=Query(uid=0, text="short"),
                    prompt_tokens=[5, 6, 7], max_new_tokens=30)
    eng.submit(first)
    while not first.generated:            # drive into decode
        eng.step()
    gen_before = len(first.generated)
    second = Request(query=Query(uid=1, text="long"),
                     prompt_tokens=[1 + (i % 250) for i in range(48)],
                     max_new_tokens=2)
    eng.submit(second)
    eng.step()                            # mixed tick: prefill slab + decode
    assert second.n_prompt_fed == 8       # slab consumed
    assert len(first.generated) == gen_before + 1   # decode never stalled
    done = []
    for _ in range(60):
        done += eng.step()
        if len(done) == 2:
            break
    assert {r.uid for r in done} == {0, 1}


# -- SimEngine edge cases (the scenario lab's ModelEngine twin) ---------------


def _sim_req(uid, submit_s=0.0, n_prompt=2):
    return Request(query=Query(uid=uid, text=f"q{uid}"),
                   prompt_tokens=list(range(1, n_prompt + 1)),
                   max_new_tokens=4, submit_s=submit_s)


def test_sim_engine_concurrency_drains_fifo():
    prof = ModelProfile(name="sim", family="s", params_b=1.0)
    eng = SimEngine(prof, lambda q, m: (0.5, 0.01, 10.0, 4), concurrency=2)
    for i in range(5):
        eng.submit(_sim_req(i))
    assert eng.free_capacity == 0
    assert [r.uid for r in eng.step()] == [0, 1]   # head slots drain first
    assert [r.uid for r in eng.step()] == [2, 3]
    assert eng.free_capacity == 1                  # one slot already free
    assert [r.uid for r in eng.step()] == [4]
    assert eng.pending == 0 and eng.free_capacity == 2


def test_sim_engine_midqueue_cancel_frees_slot_same_tick():
    """A CANCELLED request sitting mid-queue (a hedge loser) must be
    dropped in place, freeing its slot for the next waiter on the *same*
    tick, with its pinned outcome discarded and never completed."""
    prof = ModelProfile(name="sim", family="s", params_b=1.0)
    calls = []

    def outcome(query, model):
        calls.append(query.uid)
        return 0.5, 0.01, 10.0, 4
    eng = SimEngine(prof, outcome, steps_per_query=3, concurrency=2)
    reqs = [_sim_req(i) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.step()                        # uids 0,1 hold the two slots
    assert calls == [0, 1]
    reqs[1].state = RequestState.CANCELLED
    eng.step()                        # drop 1 mid-queue...
    assert calls == [0, 1, 2]         # ...and 2 activates the same tick
    assert [r.uid for r in eng.queue] == [0, 2]
    done = []
    for _ in range(4):
        done += eng.step()
    assert [r.uid for r in done] == [0, 2]
    assert calls == [0, 1, 2]         # outcome drawn once per request


def test_sim_engine_steps_per_query_paces_completion_and_clock():
    prof = ModelProfile(name="sim", family="s", params_b=1.0)
    eng = SimEngine(prof, lambda q, m: (0.5, 0.02, 80.0, 4),
                    steps_per_query=4)
    eng.submit(_sim_req(0))
    for _ in range(3):
        assert eng.step() == []       # in service, not yet done
    assert [r.uid for r in eng.step()] == [0]   # exactly step 4
    # each tick advances modeled time by latency/steps: 4 x 20 ms
    assert eng.modeled_time_s() == pytest.approx(0.080)
    assert eng.step() == []           # idle tick leaves the clock alone
    assert eng.modeled_time_s() == pytest.approx(0.080)


def test_sim_engine_injectable_clock_stamps_virtual_time():
    """With an injected clock the lifecycle stamps live on the bench's
    virtual timeline, so queue_ms reflects modeled wait, not wall time."""
    clk = {"t": 50.0}
    prof = ModelProfile(name="sim", family="s", params_b=1.0)
    eng = SimEngine(prof, lambda q, m: (0.5, 0.01, 10.0, 4),
                    clock=lambda: clk["t"])
    req = _sim_req(0, submit_s=47.5)
    eng.submit(req)
    clk["t"] = 53.5
    resp = eng.step()[0]
    assert req.start_s == 53.5 and req.finish_s == 53.5
    assert resp.queue_ms == pytest.approx(6000.0)


def test_single_engine_failure_recovers_and_serves_again():
    """EngineFailure on one pool member: PoolServer must surface it as a
    restart, re-route that engine's inflight work without losing any
    response, and return the engine to service."""
    server, engines = _sim_server(n_models=3, steps_per_query=4)
    qs = make_stream(per_task=2)[:6]
    reqs = [server.submit(q) for q in qs]
    victim = reqs[0].model_name
    on_victim = {r.uid for r in engines[victim].queue}
    assert on_victim
    engines[victim].inject_failure()
    server.step()
    assert server.stats["restarts"] == 1
    server.run_until_drained(max_steps=500)
    assert len(server.responses) == 6
    assert on_victim <= set(server.responses)
    engines[victim].step()            # restarted: stepping no longer raises


def test_real_engine_through_server():
    eng = _real_engine()
    pool = ModelPool([eng.profile])
    router = GreenServRouter(RouterConfig(max_arms=4), pool)
    server = PoolServer(router, {eng.profile.name: eng}, tokenizer=tok.encode)
    qs = make_stream(per_task=1)
    for q in qs:
        server.submit(q)
    server.run_until_drained(max_steps=2000)
    assert len(server.responses) == len(qs)


def test_reused_slot_starts_from_zero_recurrent_state():
    """rwkv's recurrent state is read whatever the slot's length says: a
    request admitted into a slot another request just left must decode
    exactly as it would on a fresh engine."""
    def tokens(eng, prompts):
        out = []
        for uid, text in enumerate(prompts):
            eng.submit(Request(query=Query(uid=uid, text=text),
                               prompt_tokens=tok.encode(text),
                               max_new_tokens=6))
            for _ in range(200):
                done = eng.step()
                if done:
                    out.append(done[0].tokens)
                    break
        return out

    second = "Solve the math word problem step by step."
    reused = tokens(_real_engine(max_batch=1),
                    ["Summarize the following article.", second])
    fresh = tokens(_real_engine(max_batch=1), [second])
    assert reused[1] == fresh[0]


def test_slow_tick_is_not_a_stalled_engine():
    """A tick that outlasts the heartbeat timeout (a first-call compile)
    is progress, not a stall: only an engine that completed no tick since
    the previous health check may be restarted."""
    clk = {"t": 0.0}
    profiles = [ModelProfile(name=f"sim{i}", family="s", params_b=1.0)
                for i in range(2)]

    class Compiling(SimEngine):
        def step(self):
            out = super().step()
            if self.queue and clk["t"] < 60.0:
                clk["t"] += 60.0          # longer than the 30 s timeout
            return out

    engines = {p.name: Compiling(p, lambda q, m: (0.5, 0.01, 10.0, 4),
                                 steps_per_query=3,
                                 clock=lambda: clk["t"])
               for p in profiles}
    server = PoolServer(GreenServRouter(RouterConfig(max_arms=4),
                                        ModelPool(profiles)),
                        engines, clock=lambda: clk["t"])
    for q in make_stream(per_task=1)[:2]:
        server.submit(q)
    server.run_until_drained(max_steps=50)
    assert server.stats["restarts"] == 0
    assert len(server.responses) == 2


# -- the finish test's slot length: host against device ----------------------


def _device_rule(eng):
    """The finish test with the slot length read back from the device."""
    def should_finish(req):
        return (req.generated[-1] == req.eos_id
                or len(req.generated) >= req.max_new_tokens
                or int(eng.cache["length"][req.slot]) >= eng.max_len - 1)
    return should_finish


def _checked_host_rule(lengths):
    """The engine's own finish test, its host slot length checked against
    the device's at every call."""
    def rule(eng):
        host = eng._should_finish

        def should_finish(req):
            length = eng._slot_length(req)
            assert length == int(eng.cache["length"][req.slot]), req.uid
            lengths.append(length)
            return host(req)
        return should_finish
    return rule


def _engine_with(rule, arch, **kw):
    cfg = get_config(arch, smoke=True, vocab_size=tok.VOCAB_SIZE)
    eng = ModelEngine(arch, cfg, jax.random.PRNGKey(3), max_batch=2, **kw)
    eng._should_finish = rule(eng)
    return eng


def _probe(uid, n_prompt, max_new, prompt_of=None):
    """A request whose prompt is drawn from its uid (or ``prompt_of``'s)."""
    base = uid if prompt_of is None else prompt_of
    return Request(query=Query(uid=uid, text=f"probe {uid}"),
                   prompt_tokens=[1 + (base * 37 + i) % 250
                                  for i in range(n_prompt)],
                   max_new_tokens=max_new, eos_id=-1)


def _serve(eng, reqs, events, tick=0, until=300):
    """Submit ``reqs`` and step ``eng`` from ``tick`` until they are answered
    or ``until`` is reached, collecting (tick, uid, tokens) per response;
    returns the next tick."""
    for r in reqs:
        eng.submit(r)
    want = len(events) + len(reqs)
    while len(events) < want and tick < until:
        events += [(tick, r.uid, r.tokens) for r in eng.step()]
        tick += 1
    return tick


def _rwkv_tokenwise(rule):
    eng = _engine_with(rule, "rwkv6-1.6b", max_len=96)
    events = []
    _serve(eng, [_probe(0, 5, 6), _probe(1, 9, 1), _probe(2, 3, 8)], events)
    assert len(events) == 3
    return events


def _dense_chunk_prefix_splice(rule):
    eng = _engine_with(rule, "granite-3-8b", max_len=64, prefill_chunk=8)
    eng.set_prefix_cache(PrefixCache(max_blocks=32, block_tokens=8))
    events = []
    tick = _serve(eng, [_probe(0, 20, 5)], events)
    warm = _probe(1, 20, 7, prompt_of=0)
    _serve(eng, [warm, _probe(2, 11, 9)], events, tick)
    assert warm.prefix_reused == 16 and eng.prefix_hit_count() == 1
    assert len(events) == 3
    return events


def _disaggregated_migration(rule):
    eng = _engine_with(rule, "granite-3-8b", max_len=48, prefill_chunk=4,
                       role="prefill")
    twin = _engine_with(rule, "granite-3-8b", max_len=48, prefill_chunk=4,
                        role="decode", params=eng.params)
    for r in (_probe(0, 10, 5), _probe(1, 13, 1), _probe(2, 6, 4)):
        eng.submit(r)
    events, migrated = [], 0
    for tick in range(100):
        events += [(tick, r.uid, r.tokens) for r in eng.step()]
        for r in eng.drain_migrations():
            twin.submit_migrated(r)
            migrated += 1
        events += [(tick, r.uid, r.tokens) for r in twin.step()]
        if len(events) == 3:
            break
    assert migrated == 2 and len(events) == 3
    return events


def _prompt_past_max_len(rule):
    eng = _engine_with(rule, "granite-3-8b", max_len=32, prefill_chunk=8)
    events = []
    _serve(eng, [_probe(0, 40, 6), _probe(1, 20, 30)], events)
    # both finish on overflow, well inside their budgets: the 40-token
    # prompt at its first token, the other once its length reaches 31
    assert sorted((uid, len(toks)) for _, uid, toks in events) == [
        (0, 1), (1, 12)]
    return events


def _restart_mid_stream(rule):
    eng = _engine_with(rule, "granite-3-8b", max_len=64, prefill_chunk=4)
    events = []
    tick = _serve(eng, [_probe(0, 9, 12), _probe(1, 14, 6), _probe(2, 5, 3)],
                  events, until=6)
    inflight = eng.restart()
    assert inflight
    _serve(eng, inflight, events, tick)
    assert sorted(uid for _, uid, _ in events) == [0, 1, 2]
    return events


@pytest.mark.parametrize("case", [
    _rwkv_tokenwise, _dense_chunk_prefix_splice, _disaggregated_migration,
    _prompt_past_max_len, _restart_mid_stream], ids=lambda c: c.__name__[1:])
def test_the_finish_test_reads_slot_lengths_from_the_host(case):
    """At every finish test the engine's host slot length equals the
    device's ``cache["length"]``, and the engine finishes the same requests
    at the same ticks with the same tokens as with the device read."""
    lengths = []
    got = case(_checked_host_rule(lengths))
    assert lengths
    assert got == case(_device_rule)

"""Host-numpy vs device (Pallas) featurization parity — the documented
guarantee behind ``RouterConfig.featurize``: embeddings agree within
float32 tolerance; task labels, cluster assignments, context one-hots and
routing decisions agree exactly.  Includes ragged batches,
empty/whitespace-only and non-ASCII texts, plus the async-timing fix
(timestamps only after a device sync)."""
import time

import numpy as np
import pytest

import repro.core.router as router_mod
from repro.core.context import ContextGenerator, OnlineKMeans
from repro.core.embedding import EmbeddingModel
from repro.core.pool import ModelPool
from repro.core.router import GreenServRouter
from repro.core.types import Feedback, ModelProfile, Query, RouterConfig
from repro.telemetry import spans

WEIRD_TEXTS = [
    "Answer the question.\nWhat is the boiling point of water?",
    " ",                                  # whitespace-only -> zero features
    "\t\n  \n",                           # ditto, multiline
    "héllo wörld — naïve café über straße",   # non-ASCII, accents
    "数学の問題を解いてください。17個のりんご",        # CJK (tokenizer drops it)
    "a",                                  # single short token
    "Solve step by step.\n17 apples shared among 4 children leaves",
    "answer ANSWER Answer aNsWeR",        # case folding
    "x" * 500,                            # one long token
    "the the the the the the",            # heavy duplicate features
]


def _pool(n=4):
    return ModelPool([ModelProfile(name=f"m{i}", family="t",
                                   params_b=float(i + 1),
                                   ms_per_token=float(i + 1),
                                   prefill_ms=10.0)
                      for i in range(n)])


def _router(featurize, n=4, **kw):
    cfg = RouterConfig(max_arms=16, featurize=featurize, **kw)
    return GreenServRouter(cfg, _pool(n))


def _warm(router, n=8):
    for i in range(n):
        q = Query(uid=50_000 + i, text=f"Summarize the following.\nDoc {i} "
                                       f"on topic {i % 3} with detail words")
        d = router.route(q)
        router.feedback(Feedback(
            query_uid=q.uid, model_index=d.model_index,
            accuracy=0.3 + 0.2 * (d.model_index % 3),
            energy_wh=0.01 * (d.model_index + 1), latency_ms=5.0))


def _queries(texts, uid0=0):
    return [Query(uid=uid0 + i, text=t) for i, t in enumerate(texts)
            if t.strip() or True]         # Query allows whitespace-only


# ---------------------------------------------------------------------------
# Embedding parity
# ---------------------------------------------------------------------------


def test_embeddings_host_vs_device_tolerance():
    em = EmbeddingModel()
    host = em.encode_batch(WEIRD_TEXTS)
    dev = em.encode_batch_device(WEIRD_TEXTS)
    np.testing.assert_allclose(dev, host, atol=1e-5)
    # featureless rows are exactly zero on both paths
    assert np.all(host[1] == 0.0) and np.all(dev[1] == 0.0)
    assert np.all(host[2] == 0.0) and np.all(dev[2] == 0.0)


def test_embeddings_unit_norm_or_zero():
    em = EmbeddingModel()
    dev = em.encode_batch_device(WEIRD_TEXTS)
    norms = np.linalg.norm(dev, axis=1)
    for n in norms:
        assert n == pytest.approx(1.0, abs=1e-5) or n == 0.0


def test_hashed_features_padding_and_memo():
    em = EmbeddingModel()
    ids, w = em.hashed_features(["alpha beta", "", "alpha"])
    assert ids.shape == w.shape and ids.shape[0] == 3
    assert np.all(ids[1] == -1) and np.all(w[1] == 0.0)   # empty row
    assert np.all(w[ids < 0] == 0.0)                      # padding weight 0
    # memoized rehash is identical
    ids2, w2 = em.hashed_features(["alpha beta", "", "alpha"])
    np.testing.assert_array_equal(ids, ids2)
    np.testing.assert_array_equal(w, w2)


def test_empty_batch():
    em = EmbeddingModel()
    assert em.encode_batch_device([]).shape == (0, em.dim)


# ---------------------------------------------------------------------------
# k-means scan parity
# ---------------------------------------------------------------------------


def test_kmeans_scan_matches_sequential_updates():
    rng = np.random.default_rng(0)
    embs = rng.standard_normal((40, 16)).astype(np.float32)
    embs /= np.linalg.norm(embs, axis=1, keepdims=True)
    embs[7] = embs[0]                      # exact duplicate (seed dedup)
    embs[11] = 0.0                         # zero embedding
    km_host, km_dev = OnlineKMeans(3, 16), OnlineKMeans(3, 16)
    host = [km_host.update(e) for e in embs]
    dev = km_dev.update_batch_device(embs).tolist()
    assert host == dev
    assert km_host._initialized == km_dev._initialized
    np.testing.assert_array_equal(km_host.counts, km_dev.counts)
    np.testing.assert_allclose(km_host.centroids, km_dev.centroids,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Full routing parity (the acceptance guarantee)
# ---------------------------------------------------------------------------


def test_route_batch_host_vs_device_decisions_exact():
    r_host, r_dev = _router("host"), _router("device")
    _warm(r_host), _warm(r_dev)
    qs = _queries([t for t in WEIRD_TEXTS])
    d_host = r_host.route_batch(qs)
    d_dev = r_dev.route_batch(qs)
    assert [d.model_index for d in d_host] == [d.model_index for d in d_dev]
    for a, b in zip(d_host, d_dev):
        assert a.context.task_label == b.context.task_label
        assert a.context.cluster == b.context.cluster
        assert a.context.complexity_bin == b.context.complexity_bin
        np.testing.assert_array_equal(a.context.vector, b.context.vector)
        np.testing.assert_allclose(a.ucb_scores, b.ucb_scores, atol=1e-4)
    # the sequential Eq. 10 state evolved identically
    np.testing.assert_array_equal(r_host.context.kmeans.counts,
                                  r_dev.context.kmeans.counts)
    assert (r_host.context.kmeans._initialized
            == r_dev.context.kmeans._initialized)


def test_route_batch_device_matches_sequential_device():
    r_seq, r_bat = _router("device"), _router("device")
    _warm(r_seq), _warm(r_bat)
    qs = _queries(WEIRD_TEXTS, uid0=100)
    seq = [r_seq.route(q) for q in qs]
    bat = r_bat.route_batch(qs)
    assert [d.model_index for d in seq] == [d.model_index for d in bat]
    for a, b in zip(seq, bat):
        np.testing.assert_array_equal(a.context.vector, b.context.vector)


@pytest.mark.parametrize("sizes", [(1,), (3, 1, 5), (2, 7)])
def test_ragged_batches_agree_with_one_big_batch(sizes):
    """Splitting a stream into ragged admission batches must not change
    decisions (each split replays the same sequential state)."""
    texts = [WEIRD_TEXTS[i % len(WEIRD_TEXTS)] + f" v{i}"
             for i in range(sum(sizes))]
    r_one, r_rag = _router("device"), _router("device")
    _warm(r_one), _warm(r_rag)
    qs = _queries(texts, uid0=300)
    one = r_one.route_batch(qs)
    ragged = []
    i = 0
    for s in sizes:
        ragged.extend(r_rag.route_batch(qs[i:i + s]))
        i += s
    assert [d.model_index for d in one] == [d.model_index for d in ragged]


def test_device_respects_feasibility():
    r = _router("device")
    qs = [Query(uid=i, text=f"short question {i}", max_new_tokens=50,
                latency_budget_ms=70.0) for i in range(4)]
    for d in r.route_batch(qs):
        assert d.model_name == "m0"        # only m0 meets the budget


def test_breaker_mask_and_blocked_parity_host_vs_device():
    """The reliability layer's arm-health mask (breaker-OPEN arms) and
    per-row ``blocked`` vetoes ride the feasibility matrix, so masked
    routing must stay decision-identical across scoring backends."""
    r_host, r_dev = _router("host"), _router("device")
    _warm(r_host), _warm(r_dev)
    health = np.array([True, False, True, True])     # arm 1 breaker-OPEN
    r_host.set_arm_health(lambda: health)
    r_dev.set_arm_health(lambda: health)
    qs = _queries(WEIRD_TEXTS, uid0=300)
    blocked = np.zeros((len(qs), 4), bool)
    blocked[::2, 2] = True                 # retries vetoing their failed arm
    d_host = r_host.route_batch(_queries(WEIRD_TEXTS, uid0=300),
                                blocked=blocked)
    d_dev = r_dev.route_batch(qs, blocked=blocked)
    assert [d.model_index for d in d_host] == [d.model_index for d in d_dev]
    assert all(d.model_index != 1 for d in d_host)   # mask actually bites
    for i, d in enumerate(d_host):
        if i % 2 == 0:
            assert d.model_index != 2
    # all arms of a row vetoed -> fall back to the unmasked feasibility
    # row (serving must answer), identically on both backends
    all_blocked = np.ones((2, 4), bool)
    f_host = r_host.route_batch(_queries(WEIRD_TEXTS[:2], uid0=340),
                                blocked=all_blocked)
    f_dev = r_dev.route_batch(_queries(WEIRD_TEXTS[:2], uid0=340),
                              blocked=all_blocked)
    assert [d.model_index for d in f_host] == [d.model_index for d in f_dev]


def test_device_forwarded_features_match_recompute():
    """Forwarding probe embeddings/labels into route_batch is identical to
    recomputing them (the scheduler's cache-probe reuse)."""
    r_a, r_b = _router("device"), _router("device")
    _warm(r_a), _warm(r_b)
    texts = [t + " fwd" for t in WEIRD_TEXTS[:6]]
    qs_a = _queries(texts, uid0=400)
    qs_b = _queries(texts, uid0=400)
    labels, clusters, embs = r_a.context.probe_batch(texts)
    d_a = r_a.route_batch(qs_a, embeddings=embs, task_labels=labels)
    d_b = r_b.route_batch(qs_b)
    assert [d.model_index for d in d_a] == [d.model_index for d in d_b]
    for a, b in zip(d_a, d_b):
        np.testing.assert_array_equal(a.context.vector, b.context.vector)


def test_probe_batch_host_vs_device_and_read_only():
    gen_h = ContextGenerator(RouterConfig(featurize="host"))
    gen_d = ContextGenerator(RouterConfig(featurize="device"))
    # seed identical k-means state through identical updates
    warm = [f"Summarize the following.\nDoc {i} topic {i % 3}"
            for i in range(6)]
    gen_h.batch(warm)
    gen_d.batch(warm)             # device toggle does not change .batch()
    state_before = gen_d.kmeans.state_dict()
    lh, ch, eh = gen_h.probe_batch(WEIRD_TEXTS)
    ld, cd, ed = gen_d.probe_batch(WEIRD_TEXTS)
    np.testing.assert_array_equal(lh, ld)
    np.testing.assert_array_equal(ch, cd)
    np.testing.assert_allclose(ed, eh, atol=1e-5)
    # read-only: no k-means mutation, no classifier mutation
    after = gen_d.kmeans.state_dict()
    np.testing.assert_array_equal(state_before["centroids"],
                                  after["centroids"])
    assert state_before["initialized"] == after["initialized"]


def test_feature_toggle_ablation_parity():
    for feats in [(False, True, True), (True, False, True),
                  (True, True, False), (False, False, False)]:
        r_h, r_d = _router("host"), _router("device")
        r_h.context.set_features(*feats)
        r_d.context.set_features(*feats)
        qs = _queries(WEIRD_TEXTS[:5], uid0=500)
        d_h = r_h.route_batch(_queries(WEIRD_TEXTS[:5], uid0=500))
        d_d = r_d.route_batch(qs)
        assert ([d.model_index for d in d_h]
                == [d.model_index for d in d_d]), feats
        for a, b in zip(d_h, d_d):
            np.testing.assert_array_equal(a.context.vector, b.context.vector)


def test_stochastic_policy_falls_back_to_host():
    r = _router("device", algorithm="cts")
    assert not r._device_featurize_active()
    d = r.route_batch(_queries(WEIRD_TEXTS[:3]))
    assert len(d) == 3                    # host fallback still routes


# ---------------------------------------------------------------------------
# Serving integration: device router behind the PoolServer
# ---------------------------------------------------------------------------


def test_pool_server_serves_on_device_path():
    from repro.cache import GreenCache
    from repro.serving import PoolServer, SimEngine

    profiles = [ModelProfile(name=f"sim{i}", family="s", params_b=i + 1.0)
                for i in range(3)]
    pool = ModelPool(profiles)
    engines = {p.name: SimEngine(p, lambda q, m: (0.5, 0.01, 10.0, 4))
               for p in profiles}
    router = GreenServRouter(
        RouterConfig(max_arms=16, featurize="device"), pool)
    # cluster_guard off: routing's Eq. 10 updates move centroids between
    # the insert-time and repeat-time probes, which is the guard's job to
    # notice — this test is about the device probe/short-circuit mechanics
    cache = GreenCache(mode="semantic", semantic_threshold=0.99,
                       cluster_guard=False)
    server = PoolServer(router, engines, cache=cache)
    texts = [t for t in WEIRD_TEXTS if t.strip()][:6]
    qs = [Query(uid=i, text=t, max_new_tokens=4)
          for i, t in enumerate(texts)]
    server.submit_batch(qs)
    server.run_until_drained()
    assert len(server.responses) == len(qs)
    assert int(router.policy.state.t) == len(qs)      # loop closed
    # an exact repeat now hits the semantic cache: no extra routing
    routed = router.n_routed
    rep = [Query(uid=100 + i, text=t, max_new_tokens=4)
           for i, t in enumerate(texts[:2])]
    reqs = server.submit_batch(rep)
    assert all(r.done for r in reqs)
    assert router.n_routed == routed


# ---------------------------------------------------------------------------
# Async-timing fix: timestamps only after a device sync
# ---------------------------------------------------------------------------


def test_decision_clock_syncs_device_work(monkeypatch):
    synced = []
    real = router_mod._sync
    monkeypatch.setattr(router_mod, "_sync",
                        lambda x: (synced.append(True), real(x))[1])
    r = _router("device")
    t0 = time.perf_counter()
    r.route_batch(_queries(WEIRD_TEXTS[:4], uid0=600))
    wall_ms = (time.perf_counter() - t0) * 1e3
    assert synced, "decision timestamp taken without block_until_ready"
    # the reported decision window is inside the measured wall window
    assert 0.0 < r.decision_ms_total <= wall_ms * 1.05


def test_context_clock_syncs_stage_boundaries(monkeypatch):
    """The host path's stages are spans: the featurizer's, then the
    scorer's, which holds the sync that stops the decision clock."""
    tracer = spans.Tracer()
    r = GreenServRouter(RouterConfig(max_arms=16, featurize="host"),
                        _pool(4), tracer=tracer)
    synced = []
    real = router_mod._sync
    monkeypatch.setattr(router_mod, "_sync", lambda x: (
        synced.append((time.monotonic(),
                       [s.name for s in tracer.records()[0]])), real(x))[1])
    t0 = time.perf_counter()
    r.route_batch(_queries(["Answer this.\nWhat?",
                            "Summarize that.\nLong doc."], uid0=650))
    wall_ms = (time.perf_counter() - t0) * 1e3
    recs, _ = tracer.records()
    assert [s.name for s in recs] == ["route.features", "route.decide",
                                      "route.tilt"]
    assert all(s.parent == -1 for s in recs)
    # one sync, taken inside route.decide, after the featurizer closed
    [(t_sync, closed)] = synced
    assert closed == ["route.features"]
    assert recs[1].start <= t_sync <= recs[1].end
    stage_sum = sum(s.end - s.start for s in recs) * 1e3
    assert 0.0 < stage_sum <= wall_ms * 1.05


def test_device_path_populates_featurize_timing():
    tracer = spans.Tracer()
    r = GreenServRouter(RouterConfig(max_arms=16, featurize="device"),
                        _pool(4), tracer=tracer)
    r.route_batch(_queries(WEIRD_TEXTS[:4], uid0=700))
    totals = tracer.totals()
    assert {k: c for k, (c, _) in totals.items()} == {
        "route.features": 1, "route.decide": 1, "route.sync": 1,
        "route.tilt": 1}
    assert all(sec > 0.0 for _, sec in totals.values())
    assert r.n_routed == 4 and r.mean_decision_ms > 0.0


# ---------------------------------------------------------------------------
# Hypothesis sweep (dev-only dependency, mirrors test_property.py — but the
# deterministic suite above must run even where hypothesis is absent, so
# only this block is conditional rather than importorskip'ing the module)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:                      # dev-only (requirements-dev.txt)
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    # default alphabet: all codepoints minus surrogates — covers ASCII,
    # accents, CJK, emoji, control chars (the tokenizer drops most)
    _TEXT = st.text(min_size=0, max_size=80)

    @given(texts=st.lists(_TEXT, min_size=1, max_size=6))
    @settings(max_examples=15, deadline=None)
    def test_hypothesis_embedding_parity(texts):
        em = EmbeddingModel()
        np.testing.assert_allclose(em.encode_batch_device(texts),
                                   em.encode_batch(texts), atol=1e-5)

    @given(data=st.data())
    @settings(max_examples=10, deadline=None)
    def test_hypothesis_routing_parity(data):
        """Arbitrary ragged unicode streams: labels, clusters, one-hots
        and arm choices identical between the host and device routers."""
        batches = data.draw(st.lists(
            st.lists(_TEXT.filter(lambda t: len(t) > 0), min_size=1,
                     max_size=4),
            min_size=1, max_size=3))
        r_h, r_d = _router("host"), _router("device")
        uid = 0
        for batch in batches:
            qs_h = [Query(uid=uid + i, text=t) for i, t in enumerate(batch)]
            qs_d = [Query(uid=uid + i, text=t) for i, t in enumerate(batch)]
            uid += len(batch)
            d_h = r_h.route_batch(qs_h)
            d_d = r_d.route_batch(qs_d)
            assert ([d.model_index for d in d_h]
                    == [d.model_index for d in d_d])
            for a, b in zip(d_h, d_d):
                assert a.context.task_label == b.context.task_label
                assert a.context.cluster == b.context.cluster
                np.testing.assert_array_equal(a.context.vector,
                                              b.context.vector)

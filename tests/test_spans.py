"""Program spans: the recorder, and the spans the served path writes."""
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.pool import ModelPool
from repro.core.router import GreenServRouter
from repro.core.types import ModelProfile, Query, RouterConfig
from repro.data import tokenizer as tok
from repro.serving import ModelEngine, PoolServer, Request, SimEngine
from repro.serving.request import RequestState
from repro.telemetry import Telemetry, export, spans


def _tracer(capacity=spans.CAPACITY):
    tr = spans.Tracer(capacity)
    for name in ("a", "b", "c"):
        tr.name(name)
    return tr


# -- the recorder ----------------------------------------------------------


def test_spans_nest_with_parent_links_and_self_time():
    tr = _tracer()
    with tr.span("a"):
        with tr.span("b"):
            with tr.span("c", uid=7):
                pass
        with tr.span("b"):
            pass
    recs, oldest = tr.records()
    assert [r.name for r in recs] == ["a", "b", "c", "b"]
    assert [r.parent for r in recs] == [-1, 0, 1, 0]
    assert [r.uid for r in recs] == [-1, -1, 7, -1]
    assert oldest == recs[0].start
    a, b1, c, b2 = recs
    assert a.start <= b1.start <= c.start <= c.end <= b1.end <= b2.start \
        <= b2.end <= a.end
    assert tr.totals()["b"][0] == 2
    heavy = {name: (n, s, self_s) for name, n, s, self_s in tr.heaviest()}
    assert heavy["a"][2] == pytest.approx(
        heavy["a"][1] - heavy["b"][1], abs=1e-9)
    assert heavy["c"][2] == pytest.approx(heavy["c"][1])


def test_an_unfinished_span_is_left_out_and_its_children_kept():
    tr = _tracer()
    with tr.span("a"):
        with tr.span("b"):
            pass
        recs, _ = tr.records()
    assert [(r.name, r.parent) for r in recs] == [("b", -1)]


def test_ring_wrap_reports_from_when_it_holds_every_span():
    tr = _tracer(capacity=8)
    for i in range(12):
        tr.record("a", float(i), i + 0.5)
    recs, oldest = tr.records()
    assert [r.start for r in recs] == [float(i) for i in range(4, 12)]
    assert oldest == 3.5          # the latest end among the dropped
    # a span overwritten while open is lost to the ring, not to the
    # totals, and its children lose the link
    tr = _tracer(capacity=4)
    with tr.span("a"):
        for _ in range(4):
            with tr.span("b"):
                pass
    recs, oldest = tr.records()
    assert [(r.name, r.parent) for r in recs] == [("b", -1)] * 4
    assert recs[-2].end <= oldest <= recs[-1].start
    assert tr.totals()["a"][0] == 1
    with pytest.raises(ValueError):
        spans.Tracer(capacity=6)


def test_a_disabled_tracer_records_nothing():
    tr = _tracer()
    tr.enabled = False
    assert tr.span("a") is tr.span("b") is spans.NULL
    with tr.span("a"):
        tr.record("b", 0.0, 1.0)
    assert tr.records() == ([], -float("inf"))
    assert tr.totals() == {}


def test_spans_are_stamped_on_the_monotonic_clock():
    tr = _tracer()
    t0 = time.monotonic()
    with tr.span("a"):
        time.sleep(0.01)
    t1 = time.monotonic()
    [rec], _ = tr.records()
    assert t0 <= rec.start < rec.end <= t1
    assert rec.end - rec.start >= 0.01


def test_no_program_span_is_named_like_the_benchmarks_own():
    eng = _engine(tracer=spans.TRACER)
    names = spans.TRACER.names
    assert "engine.sync.length:" + eng.name in names
    assert not [n for n in names if n.startswith("bench:")]
    with pytest.raises(ValueError):
        spans.Tracer().name("bench:engine.step:x")


def test_a_backend_compile_is_a_span_under_the_open_one():
    tr = spans.TRACER
    tr.name("a")
    n0 = tr.totals().get("compile", (0, 0.0))[0]
    with tr.span("a"):
        jax.jit(lambda x: x * 3.0 + 1.25)(jnp.ones((3, 5))).block_until_ready()
    assert tr.totals()["compile"][0] > n0
    recs, _ = tr.records()
    ia = max(i for i, r in enumerate(recs) if r.name == "a")
    outer = recs[ia]
    comp = [r for r in recs if r.name == "compile" and r.parent == ia]
    assert comp and all(outer.start <= r.start <= r.end <= outer.end
                        for r in comp)


# -- the engine's spans ------------------------------------------------------


def _engine(max_batch=3, tracer=None):
    cfg = get_config("rwkv6-1.6b", smoke=True, vocab_size=tok.VOCAB_SIZE)
    return ModelEngine("rwkv6-1.6b", cfg, jax.random.PRNGKey(0),
                       max_batch=max_batch, max_len=96,
                       detokenize=tok.decode, tracer=tracer)


def _request(uid, n_prompt=2, max_new=30):
    return Request(query=Query(uid=uid, text=f"q{uid}"),
                   prompt_tokens=tok.encode("hello world")[:n_prompt],
                   max_new_tokens=max_new, eos_id=-1)


def test_a_decode_tick_with_two_decoding_slots_reads_the_tokens_once():
    tr = spans.Tracer()
    eng = _engine(max_batch=3, tracer=tr)
    for uid in (1, 2):                       # two of three slots
        eng.submit(_request(uid))
    for _ in range(4):                       # token-wise prompt, then decode
        eng.step()
    decoding = [r for r in eng.slots
                if r is not None and r.state == RequestState.DECODE]
    assert len(decoding) == 2
    before = len(tr.records()[0])
    eng.step()
    recs = tr.records()[0][before:]
    names = [r.name.split(":")[0] for r in recs]
    assert names.count("engine.tick") == 1
    tick = names.index("engine.tick")

    def under_tick(r):
        while r.parent >= before and r.parent != before + tick:
            r = recs[r.parent - before]
        return r.parent == before + tick
    syncs = [r for r in recs if r.name.startswith("engine.sync.")]
    assert [r.name.split(":")[0] for r in syncs] == ["engine.sync.tokens"]
    assert all(under_tick(r) for r in recs if r is not recs[tick])
    assert "engine.dispatch.decode" in names
    assert "engine.dispatch.chunk" not in names
    assert all(r.name.endswith(":rwkv6-1.6b") for r in recs)


@pytest.mark.parametrize("kind", ["chunk", "decode"])
def test_a_latent_attention_tick_reads_its_tokens_once(kind):
    """deepseek-v2-lite (latent cache, chunked prefill of 8): a chunk tick
    and a decode tick each record the tick, its dispatch and exactly one
    token read, all under ``:<model>``."""
    tr = spans.Tracer()
    name = "deepseek-v2-lite"
    cfg = get_config(name, smoke=True, vocab_size=tok.VOCAB_SIZE)
    eng = ModelEngine(name, cfg, jax.random.PRNGKey(0), max_batch=3,
                      max_len=96, prefill_chunk=8, tracer=tr)
    assert eng.prefill_chunk == 8 and set(eng.cache) == {"k", "length"}
    for uid in (1, 2):
        eng.submit(_request(uid, n_prompt=11))
    eng.step()                               # first slab of 8
    if kind == "decode":
        while any(r is not None and r.state != RequestState.DECODE
                  for r in eng.slots):
            eng.step()
    before = len(tr.records()[0])
    eng.step()
    recs = tr.records()[0][before:]
    names = [r.name.split(":")[0] for r in recs]
    assert names.count("engine.tick") == 1
    assert f"engine.dispatch.{kind}" in names
    assert [n for n in names if n.startswith("engine.sync.")] == \
        ["engine.sync.tokens"]
    assert all(r.name.endswith(f":{name}") for r in recs)


# -- the scheduler's spans ---------------------------------------------------


def test_pool_phases_nest_under_the_step_and_request_spans_share_uids():
    tr = spans.Tracer()
    eng = _engine(max_batch=2, tracer=tr)
    router = GreenServRouter(RouterConfig(max_arms=16, featurize="host"),
                             ModelPool([eng.profile]), tracer=tr)
    server = PoolServer(router, {eng.name: eng}, tokenizer=tok.encode,
                        tracer=tr)
    for uid in (11, 12):
        server.enqueue(Query(uid=uid, text="Answer.\nWhat is x?",
                             max_new_tokens=3))
    server.run_until_drained(max_steps=200)
    assert set(server.responses) == {11, 12}
    recs, _ = tr.records()
    steps = {i for i, r in enumerate(recs) if r.name == "pool.step"}
    assert steps
    for r in recs:
        base = r.name.split(":")[0]
        if base in ("pool.health", "pool.admit", "pool.complete",
                    "pool.feedback", "engine.tick"):
            assert r.parent in steps, r
        if base.startswith("route."):
            assert recs[r.parent].name == "pool.admit", r
        if base == "feedback.update":
            assert recs[r.parent].name == "pool.feedback", r
    assert not [r for r in recs if r.name in ("pool.migrate",
                                              "pool.telemetry")]
    for uid in (11, 12):
        mine = [r for r in recs if r.uid == uid]
        assert [r.name for r in mine] == list(spans.REQUEST_SPANS)
        assert all(r.parent == -1 for r in mine)
        for a, b in zip(mine, mine[1:]):
            assert a.end == b.start
        req = server.responses[uid]
        assert mine[0].end - mine[0].start >= 0.0
        assert req.ttft_ms == pytest.approx(
            1e3 * (mine[2].end - mine[0].start))


def test_the_telemetry_phase_is_a_span_when_telemetry_is_attached():
    tr = spans.Tracer()
    prof = ModelProfile(name="sim", family="s", params_b=1.0)
    eng = SimEngine(prof, lambda q, m: (0.5, 0.01, 10.0, 4))
    server = PoolServer(GreenServRouter(RouterConfig(max_arms=16),
                                        ModelPool([prof]), tracer=tr),
                        {"sim": eng}, telemetry=Telemetry(tracer=tr),
                        tracer=tr)
    server.submit(Query(uid=1, text="Answer.\nWhat?"))
    server.run_until_drained()
    counts = {k: c for k, (c, _) in tr.totals().items()}
    assert counts["pool.telemetry"] == counts["pool.step"] >= 1
    # a simulated engine sets no first token: its prefill/decode spans
    # are left out, the rest of its life is there
    assert counts["request.queued"] == counts["request.slot_wait"] == 1
    assert "request.prefill" not in counts


# -- the arrival stamp -------------------------------------------------------


def test_queue_wait_and_ttft_count_from_the_arrival():
    clk = {"t": 10.0}
    prof = ModelProfile(name="sim", family="s", params_b=1.0)
    eng = SimEngine(prof, lambda q, m: (0.5, 0.01, 10.0, 4),
                    clock=lambda: clk["t"])
    server = PoolServer(GreenServRouter(RouterConfig(max_arms=16),
                                        ModelPool([prof])),
                        {"sim": eng}, clock=lambda: clk["t"])
    server.enqueue(Query(uid=1, text="Answer.\nWhat?"))
    clk["t"] = 12.5                      # admitted, routed and served later
    server.step()
    assert server.responses[1].queue_ms == pytest.approx(2500.0)
    # routed on arrival: the arrival is the submission
    direct = server.submit(Query(uid=2, text="Answer.\nWhy?"))
    assert direct.arrival_s == direct.submit_s == 12.5


# -- the operator's export ---------------------------------------------------


def test_span_totals_reach_prometheus_jsonl_and_the_summary(tmp_path):
    tr = _tracer()
    with tr.span("a"):
        with tr.span("b"):
            time.sleep(0.002)
    tr.record("c", 0.0, 0.5)
    parsed = export.parse_prometheus(export.to_prometheus(
        Telemetry(tracer=tr).registry, tr))
    assert parsed[("greenserv_spans_total", (("span", "a"),))] == 1.0
    assert parsed[("greenserv_span_seconds_total", (("span", "c"),))] == 0.5
    path = str(tmp_path / "m.jsonl")
    export.dump_jsonl(path, spans=tr)
    rows = {r["name"]: r for r in export.load_jsonl(path)["span"]}
    assert set(rows) == {"a", "b", "c"}
    assert rows["a"]["self_seconds"] < rows["a"]["seconds"]
    tr.record("request.queued", 1.0, 1.25, uid=3)
    tr.record("request.queued", 2.0, 2.75, uid=4)
    lines = Telemetry(tracer=tr).summary().splitlines()
    first = next(i for i, ln in enumerate(lines) if "spans" in ln)
    assert [ln.split()[0] for ln in lines[first + 1:-1]] == ["c", "b", "a"]
    assert lines[-1].split() == ["request", "queued", "500.0", "ms",
                                 "(means)"]

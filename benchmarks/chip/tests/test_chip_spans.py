"""The readers of the program's spans: on made-up spans and programs, and
in a tiny traced run of the harness on the CPU."""
import json
import pathlib
import shutil
import sys
import time
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402

import harness  # noqa: E402
import trace_reduce  # noqa: E402
from repro.telemetry import spans  # noqa: E402

I = trace_reduce.Interval
TINY_MIX = {
    "lists": {"topic": ["entropy", "the silk road"],
              "noun": ["plants", "archives"]},
    "tasks": [
        {"task": "QA", "share": 1, "max_new_tokens": 16,
         "template": "Answer the question.\nWhich statement about {topic} "
                     "involves {noun}?\nAnswer:",
         "slots": {"topic": {"from": "topic"}, "noun": {"from": "noun"}}},
        {"task": "SUMMARIZATION", "share": 1, "max_new_tokens": 24,
         "template": "Summarize the article.\nArticle: the committee on "
                     "{topic} studied {noun}.\nSummary:",
         "slots": {"topic": {"from": "topic"}, "noun": {"from": "noun"}}}]}
NEW = ("host_syncs_per_tick", "device_idle_share.engine",
       "device_idle_share.scheduler")
PLANE = "/device:TPU:0"


@pytest.fixture
def tracer(monkeypatch):
    tr = spans.Tracer()
    monkeypatch.setattr(spans, "TRACER", tr)
    return tr


def _names(tr, model="m"):
    return spans.engine_span_names(tr, model)


def _tick(tr, sn, reads, dispatch=True):
    with tr.span(sn.tick):
        if dispatch:
            with tr.span(sn.dispatch_decode):
                pass
            with tr.span(sn.sync_tokens):
                pass
        with tr.span(sn.advance):
            for _ in range(reads):
                with tr.span(sn.sync_length):
                    pass


# -- host_syncs_per_tick -------------------------------------------------------


def test_host_syncs_count_the_reads_of_the_window_ticks_that_ran(tracer):
    read = harness.layer_reader("host_syncs_per_tick")
    sn = _names(tracer)
    _tick(tracer, sn, 9)                 # before the window
    lo = time.monotonic()
    _tick(tracer, sn, 3)                 # 1 + 3 reads
    _tick(tracer, sn, 5)                 # 1 + 5 reads
    _tick(tracer, sn, 0, dispatch=False)  # an idle engine's tick
    hi = time.monotonic()
    _tick(tracer, sn, 9)                 # after it
    run = types.SimpleNamespace(window=(lo, hi))
    assert read(run) == pytest.approx((4 + 6) / 2)
    empty = time.monotonic()
    assert read(types.SimpleNamespace(window=(empty, empty + 1))) is None


def test_host_syncs_are_none_where_the_ring_dropped_the_window(monkeypatch):
    tr = spans.Tracer(capacity=16)
    monkeypatch.setattr(spans, "TRACER", tr)
    read = harness.layer_reader("host_syncs_per_tick")
    sn = _names(tr)
    lo = time.monotonic()
    for _ in range(4):
        _tick(tr, sn, 3)
    run = types.SimpleNamespace(window=(lo, time.monotonic()))
    assert tr.records()[1] > lo
    assert read(run) is None


# -- device_idle_share.engine / .scheduler ---------------------------------------


def _run(offset=100.0, lo=0.0, hi=10.0):
    """Programs on one device at [1, 2), [4, 5) and [6.5, 8) of a slice
    [0, 10), on a trace clock ``offset`` seconds ahead of the ring's."""
    runs = [I("jit_greedy_step", 1.0, 2.0), I("jit_greedy_step", 4.0, 5.0),
            I("jit__fused_decide", 6.5, 8.0)]
    runs = [I(r.name, r.start + offset, r.end + offset) for r in runs]
    programs = {"jit_greedy_step": (2, 2.0), "jit__fused_decide": (1, 1.5)}
    trace = {"lo": lo + offset, "hi": hi + offset, "offset": offset,
             "window_s": hi - lo, "spans": [],
             "devices": {PLANE: {"runs": runs, "programs": programs,
                                 "busy_s": 3.5, "idle": []}}}
    return types.SimpleNamespace(
        trace=trace, planes=frozenset({PLANE}), window=(lo, hi),
        counters={"start": {"n_routed": 0.0}, "end": {"n_routed": 4.0}})


def _spans(tr):
    sn = _names(tr)
    tr.record("pool.step", -0.5, 9.5)
    tr.record(sn.tick, 0.2, 0.8)          # [0, 1): middle 0.5
    tr.record(sn.sync_length, 2.5, 3.5)   # [2, 4): middle 3.0
    tr.record("route.decide", 5.5, 6.0)   # [5, 6.5): middle 5.75
    tr.record("request.decode", 5.0, 9.9, uid=4)   # labels nothing
    # [8, 10): middle 9.0 -> pool.step


def test_idle_stretches_are_labelled_by_the_innermost_program_span(tracer):
    _spans(tracer)
    engine = harness.layer_reader("device_idle_share.engine")
    sched = harness.layer_reader("device_idle_share.scheduler")
    run = _run()
    assert engine(run) == pytest.approx(100 * (1.0 + 2.0) / 10)
    assert sched(run) == pytest.approx(100 * (1.5 + 2.0) / 10)


def test_a_program_across_the_slice_edge_leaves_the_edge_stretches_out(
        tracer):
    _spans(tracer)
    run = _run()
    dev = run.trace["devices"][PLANE]
    dev["programs"]["jit_greedy_step"] = (2, 2.6)   # 0.6 s before the first
    engine = harness.layer_reader("device_idle_share.engine")
    sched = harness.layer_reader("device_idle_share.scheduler")
    assert engine(run) == pytest.approx(100 * 2.0 / 10)
    assert sched(run) == pytest.approx(100 * 1.5 / 10)


def test_idle_readers_are_none_without_a_trace_or_with_a_dropped_slice(
        monkeypatch):
    tr = spans.Tracer(capacity=4)
    monkeypatch.setattr(spans, "TRACER", tr)
    _spans(tr)                          # five records in a ring of four
    assert tr.records()[1] > -0.5
    for name in NEW[1:]:
        read = harness.layer_reader(name)
        assert read(_run()) is None
        assert read(types.SimpleNamespace(trace=None,
                                          planes=frozenset())) is None


def test_the_engine_reader_reports_the_split_and_the_clock_check(
        tracer, capsys):
    _spans(tracer)
    run = _run()
    run.trace["spans"] = [I("engine.step:m", 0.19 + 100.0, 0.81 + 100.0)]
    harness.layer_reader("device_idle_share.engine")(run)
    err = capsys.readouterr().err
    assert "engine 30.000% + scheduler 35.000% + unlabelled 0.000% = " \
        "65.000% of the slice; no program running 65.000%" in err
    assert "median |engine.tick start - engine.step start| 10.0000 ms " \
           "over 1 ticks" in err
    harness.layer_reader("device_idle_share.scheduler")(run)
    assert "route.decide 125.0000, route.sync 0.0000" in \
        capsys.readouterr().err


# -- a tiny traced run through the harness ------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with a tiny cell whose per-layer metrics include the
    three new ones."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(HERE / "traffic", root / "benchmarks/chip/traffic")
    (root / "benchmarks/chip/configs").mkdir()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((HERE / "configs/pool2-danube4b-rwkv1.6b.json")
                     .read_text())
    cfg["name"] = "tiny-pool"
    cfg["serving"].update(slots=4, max_len=128)
    cfg["router"]["featurize"] = "host"
    cfg["check"].update(requests_per_model=2, batch=4)
    tiny = dict(n_layers=2, d_model=128, d_ff=256, vocab_size=512)
    heads = {"h2o-danube-3-4b": dict(n_heads=4, n_kv_heads=2, head_dim=32),
             "rwkv6-1.6b": dict(n_heads=2, n_kv_heads=2, head_dim=64)}
    for m in cfg["models"]:
        m["config"].update(tiny, **heads[m["name"]])
        cfg["check"]["limits"][m["name"]] = {"logit_gap": 0.1,
                                             "mean_gap": 0.003}
    (root / "benchmarks/chip/configs/tiny-pool.json").write_text(
        json.dumps(cfg))
    (root / "benchmarks/chip/traffic/tiny-mix.json").write_text(
        json.dumps(TINY_MIX))
    (root / "benchmarks/chip/traffic/tiny-short.json").write_text(
        json.dumps({"mix": "tiny-mix", "arrivals": "poisson",
                    "rate_qps": 4.0, "stream_seed": 0, "warm_s": 0.5,
                    "drain_limit_s": 60}))
    bench["configs"].append({"name": "tiny-pool", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-pool.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.short", "config": "tiny-pool",
                               "traffic": "tiny-short", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("tiny.short")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_traced_cpu_run_reports_syncs_per_tick_and_no_idle_split(root):
    cell = harness.load_cell("tiny.short", root)
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    res = harness.run(cell, 2 ** 31 + 17, 2.0, True, jax.devices()[:1],
                      time.monotonic())
    metrics = res["metrics"]
    assert metrics["host_syncs_per_tick"]["unit"] == "reads"
    assert metrics["host_syncs_per_tick"]["value"] >= 1.0
    assert "device_idle_share.engine" not in metrics
    assert "device_idle_share.scheduler" not in metrics

"""The trace reduction on made-up intervals and on a trace recorded here."""
import glob
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402

I = tr.Interval


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    ops = [I("a", 0.0, 1.0), I("b", 0.5, 2.0), I("c", 3.0, 4.0),
           I("d", 9.0, 12.0)]
    assert tr.union(ops, 0.0, 10.0) == pytest.approx(2.0 + 1.0 + 1.0)
    assert tr.union(ops, 1.5, 3.5) == pytest.approx(0.5 + 0.5)
    assert tr.union([], 0.0, 1.0) == 0.0


def test_program_times_count_runs_starting_inside_and_clip_time():
    runs = [I("jit_greedy_step", 0.0, 1.0), I("jit_greedy_step", 2.0, 3.0),
            I("jit__fused_decide", 2.5, 2.75), I("jit_greedy_step", 9.5, 11.0)]
    got = tr.program_times(runs, 0.5, 10.0)
    assert got["jit_greedy_step"][0] == 2            # the first began before
    assert got["jit_greedy_step"][1] == pytest.approx(0.5 + 1.0 + 0.5)
    assert got["jit__fused_decide"] == (1, pytest.approx(0.25))


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    ops = [I("op", 1.0, 2.0), I("op", 4.0, 5.0), I("op", 6.5, 8.0)]
    spans = [I("PoolServer.step", 0.0, 7.0),
             I("engine.step:rwkv@pool", 2.5, 3.5),
             I("route_batch", 5.2, 5.4)]
    gaps = tr.idle_gaps(ops, spans, 0.0, 9.0)
    assert [(n, pytest.approx(s)) for n, s in gaps] == [
        ("PoolServer.step", 1.0),             # [0, 1): outer span only
        ("engine.step:rwkv@pool", 2.0),       # [2, 4): middle 3.0
        ("PoolServer.step", 1.5),             # [5, 6.5): middle 5.75
        (tr.NO_SPAN, 1.0)]                    # [8, 9): the host was idle
    totals = tr.by_label(gaps)
    assert totals["PoolServer.step"] == pytest.approx(2.5)
    assert tr.top(totals, 1) == [["PoolServer.step", pytest.approx(2.5)]]
    # busy + idle = the window
    assert tr.union(ops, 0.0, 9.0) + sum(s for _, s in gaps) == \
        pytest.approx(9.0)


def test_read_finds_the_benchmark_spans_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.SPAN + "outer"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation(tr.SPAN + "inner"):
                    f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("not ours"):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    trace = tr.read(path)
    names = [s.name for s in trace.spans]
    assert names.count("outer") == 1 and names.count("inner") == 2
    assert "not ours" not in names
    outer = next(s for s in trace.spans if s.name == "outer")
    for s in trace.spans:
        if s.name == "inner":
            assert outer.start <= s.start <= s.end <= outer.end
            assert tr.innermost(trace.spans, (s.start + s.end) / 2) == "inner"

"""Due-time TTFT, token gaps and percentiles on made-up stamps."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import latency  # noqa: E402


def test_nearest_rank():
    v = list(range(1, 101))
    assert latency.nearest_rank(v, 0.90) == 90
    assert latency.nearest_rank(v, 0.95) == 95
    assert latency.nearest_rank([5.0], 0.9) == 5.0
    assert latency.nearest_rank([3, 1, 2], 0.5) == 2
    with pytest.raises(ValueError):
        latency.nearest_rank([], 0.5)


def test_ttft_runs_from_the_due_time_and_failures_rank_last():
    # (due, first token stamp, censor): 10 answered, 1 never answered
    reqs = [(float(i), i + 0.1 * (i + 1), 100.0) for i in range(10)]
    assert latency.ttft_tail(reqs, 0.5) == pytest.approx(0.5)
    assert latency.ttft_tail(reqs, 1.0) == pytest.approx(1.0)
    failed = reqs + [(50.0, None, 100.0)]
    # the failure ranks above every answer and counts until the censor
    assert latency.ttft_tail(failed, 1.0) == pytest.approx(50.0)
    assert latency.ttft_tail(failed, 0.90) == pytest.approx(1.0)


def test_gaps_and_counts_inside_the_window():
    stamps = [0.5, 1.0, 1.25, 2.0, 3.5]
    assert latency.gaps_in(stamps, 1.0, 3.0) == [0.25, 0.75]
    assert latency.count_in(stamps, 1.0, 3.0) == 3
    assert latency.gaps_in([], 0.0, 1.0) == []


def test_end_to_end_metrics_from_stamps():
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]
                           / "src"))
    import harness
    import mix
    from repro.core.types import Query

    def req(uid, due, stamps):
        a = mix.Arrival(uid, due, "QA", "x", 8, 0.5, 0.0)
        r = harness.Req(arrival=a, query=Query(uid=uid, text="x"),
                        prompt=[1], due=due, sent=due)
        r.stamps = list(stamps)
        return r

    reqs = [req(0, 9.0, [9.5, 10.5, 11.0]),        # due before: gaps only
            req(1, 10.0, [10.2, 10.4, 10.6]),      # TTFT 0.2
            req(2, 11.0, [11.5, 11.6]),            # TTFT 0.5
            req(3, 12.0, []),                      # never answered
            req(4, 13.0, [13.3]),                  # TTFT 0.3
            req(5, 20.0, [20.1])]                  # due after the window
    run = harness.Run(cell=None, peaks=None, chips=1, window=(10.0, 15.0),
                      requests=reqs, calls=[], counters={},
                      prefix_models=frozenset(), planes=frozenset())
    assert [r.uid for r in run.in_window()] == [1, 2, 3, 4]
    m = harness.end_to_end(run, setup_s=42.0, censor=30.0)
    assert m["setup_s"] == 42.0
    assert m["ttft_p90_ms"] == pytest.approx(18000.0)   # 30 - 12, censored
    # gaps: 0.5 (req 0), 0.2, 0.2 (req 1), 0.1 (req 2): p95 = the largest
    assert m["tbt_p95_ms"] == pytest.approx(500.0)
    # stamps inside [10, 15]: 10.5, 11.0, 10.2, 10.4, 10.6, 11.5, 11.6, 13.3
    assert m["output_tokens_per_s"] == pytest.approx(8 / 5.0)

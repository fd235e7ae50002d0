"""The traffic generator: one stream per load file, the same for every
run seed, Poisson arrivals, and prompt and output lengths as the paper
mix states."""
import collections
import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import mix  # noqa: E402

SRC = pathlib.Path(__file__).resolve().parents[3] / "src"
sys.path.insert(0, str(SRC))
from repro.data import tokenizer as tok  # noqa: E402

# prompt tokens per task (byte tokenizer, with BOS), the range of the
# program's own data/stream.make_stream over 20 seeds x 1000 queries, and
# the stream's output budgets
LENGTHS = {"REASONING": (163, 187), "MATH": (196, 197), "QA": (241, 330),
           "COMPLETION": (289, 316), "SUMMARIZATION": (611, 638)}
BUDGETS = {"QA": 8, "COMPLETION": 8, "REASONING": 4, "MATH": 96,
           "SUMMARIZATION": 128}
BIG_SEED = 2 ** 33 + 12345


def load(rate=3.0, stream_seed=None):
    out = dict(mix.load_traffic("paper-mix.steady"), rate_qps=rate)
    if stream_seed is not None:
        out["stream_seed"] = stream_seed
    return out


def test_the_stream_is_the_load_files_own_and_large_seeds_work():
    a = mix.generate(load(stream_seed=BIG_SEED), 40.0)
    assert a == mix.generate(load(stream_seed=BIG_SEED), 40.0)
    assert a != mix.generate(load(stream_seed=BIG_SEED + 1), 40.0)
    # the committed load names its stream, so no run seed reaches it
    assert isinstance(mix.load_traffic("paper-mix.steady")["stream_seed"], int)


def test_another_stream_seed_deals_the_same_tasks_in_another_order():
    s1 = mix.generate(load(stream_seed=1), 40.0)
    s2 = mix.generate(load(stream_seed=2), 40.0)
    assert [a.task for a in s1] != [a.task for a in s2]
    n = min(len(s1), len(s2)) // 5 * 5
    for key in (lambda a: a.task, lambda a: a.max_new_tokens):
        assert sorted(map(key, s1[:n])) == sorted(map(key, s2[:n]))


def test_every_deck_holds_the_shares_and_arrivals_are_poisson():
    rate = 3.0
    s = mix.generate(load(rate=rate, stream_seed=5), 40.0)
    horizon = 20.0 + 40.0                          # warm-in and window
    inside = [a for a in s if a.due_s < horizon]
    assert len(s) % 5 == 0 and len(s) - len(inside) < 5
    for d in range(len(s) // 5):
        assert sorted(a.task for a in s[5 * d:5 * d + 5]) == sorted(BUDGETS)
    assert all(b.due_s > a.due_s for a, b in zip(s, s[1:]))
    # one draw of exponential gaps at the rate, from the load's own seed
    many = mix.poisson_dues(rate, 4000.0, 3)
    gaps = [b - a for a, b in zip([0.0] + many, many)]
    assert sum(gaps) / len(gaps) == pytest.approx(1 / rate, rel=0.03)
    assert sum(g > 3 / rate for g in gaps) / len(gaps) == \
        pytest.approx(math.exp(-3), rel=0.15)
    assert mix.poisson_dues(rate, 60.0, 3) == many[:len(
        mix.poisson_dues(rate, 60.0, 3))]


def test_feedback_draws_are_one_set_per_task():
    s = mix.generate(load(stream_seed=9), 40.0)
    qa = sorted(a.acc_u for a in s if a.task == "QA")
    assert qa == pytest.approx([(i + 0.5) / len(qa) for i in range(len(qa))])
    z = [a.acc_z for a in s if a.task == "SUMMARIZATION"]
    assert abs(sum(z)) < 1e-9 and 0.0 <= min(a.acc_u for a in s)


def test_prompt_and_output_lengths_as_stated():
    seen = collections.defaultdict(list)
    for seed in range(6):
        for a in mix.generate(load(stream_seed=seed), 40.0):
            seen[a.task].append(len(tok.encode(a.text)))
            assert a.max_new_tokens == BUDGETS[a.task]
            assert 0.0 <= a.acc_u < 1.0
    for task, (lo, hi) in LENGTHS.items():
        assert lo <= min(seen[task]) and max(seen[task]) <= hi, task


def test_feedback_accuracy_is_the_requests_own_draw():
    a = mix.Arrival(0, 0.0, "QA", "x", 8, acc_u=0.3, acc_z=0.0)
    table = {"QA": 0.5, "SUMMARIZATION": 0.4}
    assert mix.feedback_accuracy(a, table) == 1.0
    assert mix.feedback_accuracy(a, {"QA": 0.2}) == 0.0
    s = mix.Arrival(1, 0.0, "SUMMARIZATION", "x", 128, acc_u=0.0, acc_z=10.0)
    assert mix.feedback_accuracy(s, table) == 1.0

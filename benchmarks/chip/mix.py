"""The one traffic generator: a load file and its mix, from the load's seed.

A load file ``traffic/<traffic>.json`` gives the arrival process and its
rate, the warm-in and the drain; the mix file it names
(``traffic/<mix>.json``) gives the tasks: a template per task, the word
lists its slots draw from, its share of the stream and its output budget.
Both are data, so a new mix or a new load is a new file and no new code.

Every seed gets the same stream, so that a run's spread is the system's
and not the draw's.  The stream is one draw from the load file's own
``stream_seed``; the run's seed draws the weights (and so every served
token) and the requests that the correctness check compares.  The order
of the tasks over the arrivals is itself work here: the router learns
from every answer in turn, and a long prompt routed to a model that
prefills one token per tick holds its slot for hundreds of ticks, so a
seed that dealt the same tasks in another order would serve other work.

* the arrival times are one draw of a Poisson process at the load file's
  rate: bursts and lulls as a Poisson stream has them;
* the tasks come in decks of each task's exact share (one of each for the
  paper mix), one deck after another, each shuffled, so any stretch of
  the stream holds the shares to within a deck;
* each task's prompts are filled from its word lists by a generator of
  their own, seeded by the task's name, and dealt out in a shuffled order.

Each request also carries the uniform and normal draws that decide the
accuracy fed back to the router for it, so that the feedback a request
earns does not depend on the order in which requests finish.  They too are
one set per task, the distributions' mid-quantiles, dealt out shuffled.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import random
import statistics
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
TASKS = ("QA", "COMPLETION", "REASONING", "MATH", "SUMMARIZATION")


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of the stream: due ``due_s`` after the loop starts."""

    uid: int
    due_s: float
    task: str
    text: str
    max_new_tokens: int
    acc_u: float     # uniform draw: exact-match feedback is ``acc_u < p``
    acc_z: float     # normal draw: summarization feedback ``p + 0.12 z``


def load_traffic(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> dict:
    """The load file ``<name>.json`` with its mix file merged in as ``mix``."""
    load = json.loads((directory / f"{name}.json").read_text())
    load["mix"] = json.loads((directory / f"{load['mix']}.json").read_text())
    return load


def _fill(template: str, slots: Dict[str, dict], lists: Dict[str, List[str]],
          rng: random.Random) -> str:
    words: Dict[str, str] = {}
    for slot, spec in slots.items():
        taken = {words[s] for s in spec.get("unlike", ())}
        words[slot] = rng.choice([w for w in lists[spec["from"]]
                                  if w not in taken])
    return template.format(**words)


def task_deck(tasks: List[dict], n: int, rng: random.Random) -> List[dict]:
    """``n`` tasks in their exact shares (largest remainders), shuffled."""
    total = sum(t["share"] for t in tasks)
    exact = [n * t["share"] / total for t in tasks]
    counts = [int(x) for x in exact]
    by_rest = sorted(range(len(tasks)), key=lambda i: counts[i] - exact[i])
    for i in by_rest[: n - sum(counts)]:
        counts[i] += 1
    deck = [t for t, c in zip(tasks, counts) for _ in range(c)]
    rng.shuffle(deck)
    return deck


def mid_quantiles(n: int, inverse_cdf) -> List[float]:
    """``inverse_cdf`` at ``(i + 1/2) / n`` for i < n."""
    return [inverse_cdf((i + 0.5) / n) for i in range(n)]


def poisson_dues(rate_qps: float, horizon_s: float, seed: int,
                 multiple: int = 1) -> List[float]:
    """Arrival times of one Poisson process at ``rate_qps`` (exponential
    gaps from ``numpy``'s generator seeded by ``seed``, as the program's
    ``data/scenarios.poisson_arrivals`` draws them): those before
    ``horizon_s``, and the few after it that make their count a multiple
    of ``multiple``."""
    n = int(rate_qps * horizon_s + 10 * math.sqrt(rate_qps * horizon_s)) \
        + 2 * multiple + 16
    dues = np.cumsum(np.random.default_rng(seed).exponential(
        1.0 / rate_qps, size=n)).tolist()
    inside = sum(t < horizon_s for t in dues)
    if inside == n:
        raise ValueError("the draw ended before the horizon")
    return dues[:-(-inside // multiple) * multiple]


def generate(load: dict, seconds: float) -> List[Arrival]:
    """The stream for the warm-in and the window, in due order, with the
    few after the window that complete the last deck (never sent)."""
    if load["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {load['arrivals']!r}")
    rng = random.Random(int(load["stream_seed"]))
    mix = load["mix"]
    for task in mix["tasks"]:
        if task["task"] not in TASKS:
            raise ValueError(f"unknown task {task['task']!r}")
    group = sum(int(t["share"]) for t in mix["tasks"])
    dues = poisson_dues(float(load["rate_qps"]), load["warm_s"] + seconds,
                        int(load["stream_seed"]), group)
    deck = []
    for _ in range(len(dues) // group):
        deck += task_deck(mix["tasks"], group, rng)
    dealt = {}
    normal = statistics.NormalDist()
    for task in mix["tasks"]:
        m = sum(t is task for t in deck)
        fill = random.Random(task["task"])
        columns = ([_fill(task["template"], task["slots"], mix["lists"], fill)
                    for _ in range(m)],
                   mid_quantiles(m, lambda q: q),
                   mid_quantiles(m, normal.inv_cdf))
        for c in columns:
            rng.shuffle(c)
        dealt[task["task"]] = list(zip(*columns))
    out = []
    for uid, (task, due) in enumerate(zip(deck, dues)):
        text, acc_u, acc_z = dealt[task["task"]].pop()
        out.append(Arrival(uid=uid, due_s=due, task=task["task"], text=text,
                           max_new_tokens=int(task["max_new_tokens"]),
                           acc_u=acc_u, acc_z=acc_z))
    return out


def feedback_accuracy(arrival: Arrival, table: Dict[str, List[float]]) -> float:
    """The accuracy fed back for ``arrival`` served by a model with mean
    accuracy ``table[task]``: Bernoulli exact match, and a clipped normal
    ROUGE for summarization (the program's ``data/profiles`` outcome
    model, with this request's own draws)."""
    p = table[arrival.task]
    if arrival.task == "SUMMARIZATION":
        return min(max(p + 0.12 * arrival.acc_z, 0.0), 1.0)
    return float(arrival.acc_u < p)

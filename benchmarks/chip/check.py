"""The comparison that decides ``correct``.

After the window closes and the program's state is freed, a sample of the
requests the window finished, drawn from the seed, is run through the
plain reference (``reference.py``) on the benchmark's weights: each
request's prompt with the tokens the timed path served it, teacher-forced.
The number compared, for each model, is the widest gap by which a served
token's logit lies below the reference's best at that position.  A greedy
token that the served path computed right lies at most a rounding away
from the reference's best; a wrong token lies as far below it as a random
token's logit lies below the top one.

The sample takes, from every model that served in the window, the request
with the most served tokens (the longest), the longest of those that
spliced a cached prefix, and others drawn from the seed, up to the
configuration's count: it covers every path the models' longest requests
took, such as chunked prefill, prefix splices and decoding through a
cache, or token-wise prefill and decoding through a recurrent state.  Each
model's reference is its layout's (``arch/<layout>.py``).

The router's decisions in the window are checked beside the tokens
(``route_ref.py``): their numbers sit under the name ``router``.
"""
from __future__ import annotations

import random
from typing import Dict, List, Sequence

import numpy as np

import reference


def _longest(rows: Sequence):
    return max(rows, key=lambda r: (len(r.generated), len(r.prompt), -r.uid))


def sample(finished: Sequence, per_model: int, seed: int) -> List:
    """``per_model`` finished requests of each model: the one with the
    most served tokens, the longest that spliced a cached prefix (if any
    did), and the rest drawn from ``seed``.  ``finished`` items need
    ``uid``, ``model``, ``prompt``, ``generated`` and ``prefix_reused``."""
    groups: Dict[str, list] = {}
    for r in sorted(finished, key=lambda r: r.uid):
        groups.setdefault(r.model, []).append(r)
    rng = random.Random(seed)
    picked = []
    for key in sorted(groups):
        rows = groups[key]
        first = [_longest(rows)]
        spliced = [r for r in rows if r.prefix_reused > 0]
        if spliced and first[0].prefix_reused == 0:
            first.append(_longest(spliced))
        rest = [r for r in rows if all(r is not f for f in first)]
        picked += first[:per_model]
        picked += rng.sample(rest, min(per_model - len(first), len(rest)))
    return picked


def widest_gaps(rows: Sequence, weights: Dict[str, dict],
                models: Dict[str, dict], length: int, n_out: int,
                batch: int, controls: Sequence[str] = ()
                ) -> Dict[str, Dict[str, float]]:
    """{model: {"gap": widest gap, "mean_gap": mean gap, "tokens": tokens
    compared, and for each control format f in ``controls`` ("int8",
    "fp8") "control_gap.f" and "control_mean_gap.f"}} over ``rows`` (items
    with ``model``, ``prompt`` and ``generated``), in blocks of ``batch``
    rows padded to ``length`` tokens and ``n_out`` served tokens, so that
    one compiled reference serves every run."""
    out: Dict[str, Dict[str, float]] = {}
    for name in sorted({r.model for r in rows}):
        mine = [r for r in rows if r.model == name]
        arch = reference.Arch.of(models[name])
        widest: Dict[str, float] = {}
        sums: Dict[str, float] = {}
        tokens = 0
        for i in range(0, len(mine), batch):
            chunk = mine[i:i + batch]
            arrays = reference.pack([(r.prompt, r.generated) for r in chunk],
                                    length, n_out, batch)
            got = dict(reference.served_gaps(weights[name], *arrays, a=arch))
            for fmt in controls:
                ctl = reference.served_gaps(weights[name], *arrays, a=arch,
                                            control=fmt)
                got[f"control_gap.{fmt}"] = ctl["control_gap"]
                got[f"control_gap_sum.{fmt}"] = ctl["control_gap_sum"]
            for key, value in got.items():
                v = np.asarray(value)[:len(chunk)]
                if "_sum" in key:
                    k = key.replace("_sum", "")
                    sums[k] = sums.get(k, 0.0) + float(v.sum())
                else:
                    widest[key] = max(widest.get(key, 0.0), float(v.max()))
            tokens += sum(len(r.generated) for r in chunk)
        res = {"tokens": tokens, **widest}
        for key, total in sums.items():
            res[key.replace("gap", "mean_gap")] = total / max(tokens, 1)
        out[name] = res
    return out


# number -> its key in ``widest_gaps``'s or ``route_ref.compare``'s result
NUMBERS = {"logit_gap": "gap", "mean_gap": "mean_gap",
           "arm_mismatch": "arm_mismatch", "score_gap": "score_gap"}
ROUTER = "router"
ROUTER_CONTROL = "high"


def numbers(gaps: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """The program's reading of each number, per model (and router)."""
    return {name: {num: g[key] for num, key in NUMBERS.items() if key in g}
            for name, g in gaps.items()}


def control_numbers(gaps: Dict[str, Dict[str, float]], fmt: str
                    ) -> Dict[str, Dict[str, float]]:
    """The control's reading of each number: the models' in ``fmt``, the
    router's in ``ROUTER_CONTROL``."""
    out = {}
    for name, g in gaps.items():
        p = ROUTER_CONTROL if name == ROUTER else fmt
        out[name] = {num: g[f"control_{key}.{p}"] for num, key in
                     NUMBERS.items() if f"control_{key}.{p}" in g}
    return out


def verdict(values: Dict[str, Dict[str, float]],
            limits: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """{number: {"value", "limit"}} for every number that has a limit; a
    model or number with no reading gets the value ``None``, which fails."""
    return {f"{name}.{num}": {"value": values.get(name, {}).get(num),
                              "limit": limit}
            for name in sorted(limits)
            for num, limit in sorted(limits[name].items())}


def passes(checks: Dict[str, Dict[str, float]]) -> bool:
    return bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())

"""A plain reference of the router's decision, to check the timed path's.

GreenServ routes each admitted query by the paper's LinUCB over a one-hot
context: the task the prompt's first two lines read as (a linear
classifier over a hashed embedding), the online k-means cluster of the
whole prompt's embedding, and the Flesch reading-ease bin.  This file
recomputes that decision in float64 numpy from the query's text and the
router state the decision read (the bandit's ``A_inv`` and ``theta``, the
k-means centroids and counts), with the embedding's projection and the
untrained classifier drawn from their stated seeds.  It imports nothing
of the program.

A decision is compared where it is well posed: where no argmax on the
way (task, cluster, bin, arm) is a near-tie that the program's stated
arithmetic could flip.  Its featurize and LinUCB kernels run at float32
``highest``; its classifier logits and k-means cosines are float32
matmuls at the default precision, which on a TPU rounds their inputs to
bfloat16, so there a tie is a margin within that rounding's bound.  The numbers are the decisions on which the program's arm differs
from the reference's, and the widest gap between the program's score of
an arm and the reference's.  The control computes the same in ``high``
precision (three bfloat16 passes per product), the step below the
float32 ``highest`` of the router's kernels.
"""
from __future__ import annotations

import hashlib
import re
from typing import Dict, List, Sequence

import numpy as np

TOKEN = re.compile(r"[a-z0-9']+")
SENTENCE = re.compile(r"[.!?]+")
VOWELS = re.compile(r"[aeiouy]+")
HASH_DIM, EMBED_DIM, EMBED_SEED = 2048, 384, 1234
INSTR_LINES = 2
NEG = -1e30
TIE = 1e-4     # margins under this are near-ties, not compared
# a default-precision float32 matmul on a TPU rounds both inputs to
# bfloat16: each product is off by at most about 2**-8 of its size
DEFAULT_ROUNDING = 2.0 ** -8


def _bucket(s: str) -> int:
    digest = hashlib.blake2b(s.encode(), digest_size=8).digest()
    return int.from_bytes(digest, "little") % HASH_DIM


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 (to nearest, ties to even)."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def dot(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """``a @ b`` in float64 (``highest``) or in three bfloat16 passes
    accumulated in float32 (``high``)."""
    if precision == "highest":
        return np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (ah @ bh + ah @ bl + al @ bh).astype(np.float64)


def words(text: str) -> List[str]:
    return TOKEN.findall(text.lower())


def features(text: str) -> np.ndarray:
    """Hashed bag of features: each word 1, each character trigram of
    ``^word$`` 0.5, each pair of adjacent words 0.75."""
    counts = np.zeros(HASH_DIM)
    toks = words(text)
    for t in toks:
        counts[_bucket("w:" + t)] += 1.0
        padded = f"^{t}$"
        for i in range(len(padded) - 2):
            counts[_bucket("c:" + padded[i:i + 3])] += 0.5
    for a, b in zip(toks, toks[1:]):
        counts[_bucket(f"b:{a}_{b}")] += 0.75
    return counts


def syllables(word: str) -> int:
    w = word.strip("'")
    if not w:
        return 0
    n = len(VOWELS.findall(w))
    if w.endswith("e") and n > 1 and not w.endswith(("le", "ee", "ye")):
        n -= 1
    return max(n, 1)


def flesch(text: str) -> float:
    """Flesch reading ease, clipped to [0, 100]; 100 for no words."""
    ws = words(text)
    if not ws:
        return 100.0
    sentences = max(len([s for s in SENTENCE.split(text) if s.strip()]), 1)
    score = (206.835 - 1.015 * len(ws) / sentences
             - 84.6 * sum(map(syllables, ws)) / len(ws))
    return min(max(score, 0.0), 100.0)


def _margin(v: np.ndarray) -> float:
    top = np.sort(v)[::-1]
    return float(top[0] - top[1]) if len(top) > 1 else np.inf


def _clear(v: np.ndarray, bound: np.ndarray) -> bool:
    """Whether ``v``'s argmax beats every other entry by more than both
    entries' rounding ``bound`` and ``TIE``."""
    top = int(np.argmax(v))
    rest = np.arange(len(v)) != top
    return bool(np.all(v[top] - v[rest] > bound[top] + bound[rest] + TIE))


class Reference:
    """The decision for router settings ``rcfg`` (the configuration's
    ``router`` entry) over ``n_models`` arms."""

    def __init__(self, rcfg: dict, n_models: int, precision: str = "highest"):
        self.alpha = float(rcfg["alpha_ucb"])
        self.n_tasks = int(rcfg["n_tasks"])
        self.k = int(rcfg["n_clusters"])
        self.n_bins = int(rcfg["n_complexity_bins"])
        self.n_models = n_models
        self.precision = precision
        self.proj = (np.random.default_rng(EMBED_SEED)
                     .standard_normal((HASH_DIM, EMBED_DIM))
                     / np.sqrt(HASH_DIM))
        self.w_task = (np.random.default_rng(int(rcfg["seed"]))
                       .standard_normal((EMBED_DIM, self.n_tasks)) * 0.01)

    def embed(self, text: str) -> np.ndarray:
        counts = features(text)
        if counts.sum() > 0:
            counts = np.log1p(counts)
        v = dot(counts, self.proj, self.precision)
        n = np.linalg.norm(v)
        return v / n if n > 0 else v

    def decide(self, texts: Sequence[str], state: Dict[str, np.ndarray]
               ) -> List[dict]:
        """Per query: ``scores`` over the arms (``NEG`` where inactive),
        the ``arm``, and ``posed`` (no near-tie on the way); the k-means
        state advances over the batch in arrival order."""
        cent = np.array(state["centroids"], np.float64)
        cnt = np.array(state["counts"], np.float64)
        ini = int(state["initialized"])
        a_inv = np.asarray(state["A_inv"], np.float64)
        theta = np.asarray(state["theta"], np.float64)
        active = np.asarray(state["active"], bool).copy()
        active[self.n_models:] = False
        out, posed = [], True
        for text in texts:
            lines = [ln for ln in text.splitlines() if ln.strip()]
            instr = " ".join(lines[:INSTR_LINES]) if lines else text
            e_instr = self.embed(instr)
            logits = dot(e_instr, self.w_task, self.precision)
            task = int(np.argmax(logits))
            posed &= _clear(logits, DEFAULT_ROUNDING
                            * (np.abs(e_instr) @ np.abs(self.w_task)))
            e = self.embed(text)
            close = np.all(np.abs(cent - e) <= 1e-6 + 1e-5 * np.abs(e),
                           axis=1)
            if ini < self.k and not np.any(close[:ini]):
                cluster = ini
                cent[ini], cnt[ini] = e, 1.0
                ini += 1
            else:
                live = max(ini, 1)
                norms = np.linalg.norm(cent[:live], axis=1) * max(
                    np.linalg.norm(e), 1e-12)
                sims = (cent[:live] @ e) / np.maximum(norms, 1e-12)
                cluster = int(np.argmax(sims))
                posed &= _clear(sims, DEFAULT_ROUNDING * (
                    np.abs(cent[:live]) @ np.abs(e)) / np.maximum(norms, 1e-12))
                cent[cluster] += (e - cent[cluster]) / (cnt[cluster] + 1.0)
                cnt[cluster] += 1.0
            score = flesch(text)
            width = 100.0 / self.n_bins
            comp = min(int(score / width), self.n_bins - 1)
            edge = score / width
            posed &= abs(edge - round(edge)) * width > TIE or score in (
                0.0, 100.0)
            x = np.zeros(self.n_tasks + self.k + self.n_bins + 1)
            x[task] = x[self.n_tasks + cluster] = 1.0
            x[self.n_tasks + self.k + comp] = x[-1] = 1.0
            mean = dot(theta, x, self.precision)
            var = np.maximum(dot(dot(a_inv, x, self.precision), x,
                                 self.precision), 0.0)
            scores = np.where(active, mean + self.alpha * np.sqrt(var), NEG)
            arm = int(np.argmax(scores))
            live = scores[active]
            tied = len(live) > 1 and 0.0 < _margin(live) <= TIE
            out.append({"scores": scores, "arm": arm, "posed": posed,
                        "arm_posed": posed and not tied})
        return out


def compare(calls: Sequence[dict], rcfg: dict, n_models: int,
            controls: Sequence[str] = ()) -> Dict[str, float]:
    """The numbers over ``calls`` (each: ``texts``, ``state``, the
    program's ``arms`` and ``scores``): ``arm_mismatch``, ``score_gap``,
    the decisions ``compared`` and those ``unposed``; and each control
    precision's ``control_arm_mismatch.<p>`` and ``control_score_gap.<p>``.
    """
    refs = {p: Reference(rcfg, n_models, p) for p in ("highest", *controls)}
    res = {"arm_mismatch": 0.0, "score_gap": 0.0, "compared": 0.0,
           "unposed": 0.0}
    for p in controls:
        res[f"control_arm_mismatch.{p}"] = 0.0
        res[f"control_score_gap.{p}"] = 0.0
    for call in calls:
        ref = refs["highest"].decide(call["texts"], call["state"])
        ctl = {p: refs[p].decide(call["texts"], call["state"])
               for p in controls}
        for i, r in enumerate(ref):
            if not r["posed"]:
                res["unposed"] += 1
                continue
            res["compared"] += 1
            live = r["scores"] > NEG / 2
            got = np.asarray(call["scores"][i], np.float64)[:len(live)]
            res["score_gap"] = max(res["score_gap"], float(np.max(
                np.where(live, np.abs(got - r["scores"]), 0.0))))
            if np.any((got > NEG / 2) != live):
                res["score_gap"] = np.inf
            if r["arm_posed"] and int(call["arms"][i]) != r["arm"]:
                res["arm_mismatch"] += 1
            for p in controls:
                c = ctl[p][i]
                res[f"control_score_gap.{p}"] = max(
                    res[f"control_score_gap.{p}"], float(np.max(np.where(
                        live, np.abs(c["scores"] - r["scores"]), 0.0))))
                if r["arm_posed"] and c["arm"] != r["arm"]:
                    res[f"control_arm_mismatch.{p}"] += 1
    return res

"""Query Context Generator (paper §4.2).

Three feature extractors feed the context vector x_t = [l_t, c_t, p_t]:

  * TaskClassifier   — logistic regression over instruction embeddings (§4.2.1)
  * OnlineKMeans     — cosine-assignment online k-means over full-query
                       embeddings with incremental centroid updates (§4.2.2,
                       Eq. 9–10)
  * FleschComplexity — Flesch Reading Ease (Eq. 11) + equal-width binning
                       (§4.2.3)

Categorical features are one-hot encoded with an intercept appended (§4.2.4):
d = N_tasks + K + N_bins + 1.

Two featurization placements share this module (``RouterConfig.featurize``):
the host numpy path (``ContextGenerator.batch`` — the reference
implementation) and the device path, whose pieces live here —
``kmeans_update_scan`` replays the Eq. 10 sequential centroid updates as a
``lax.scan`` in arrival order, ``kmeans_assign_batch`` is the read-only
probe assignment, and ``_probe_pipeline`` fuses featurize+classify+assign
for the scheduler's cache probe.  The router composes them with the
``kernels/featurize`` and ``kernels/linucb`` Pallas kernels into one jitted
decision program; the two placements agree exactly
(tests/test_featurize_parity.py).
"""
from __future__ import annotations

import functools
import re
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.embedding import EmbeddingModel, tokenize
from repro.core.residency import TransferLedger
from repro.core.types import ContextVector, N_TASKS, RouterConfig


# ---------------------------------------------------------------------------
# Task classifier: LR over embeddings, trained with full-batch Adam in JAX.
# ---------------------------------------------------------------------------


def _lr_loss(params, x, y, n_classes, l2=1e-4):
    w, b = params
    logits = x @ w + b
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
    return nll + l2 * jnp.sum(w * w)


@jax.jit
def _lr_predict_logits(w, b, x):
    return x @ w + b


class TaskClassifier:
    """Lightweight LR task-type classifier (paper §4.2.1).

    The instruction text is taken from the first lines of the prompt,
    embedded, and classified into one of N_TASKS labels.
    """

    def __init__(self, embedder: EmbeddingModel, n_classes: int = N_TASKS,
                 instr_lines: int = 2, seed: int = 0):
        self.embedder = embedder
        self.n_classes = n_classes
        self.instr_lines = instr_lines
        rng = np.random.default_rng(seed)
        self.w = jnp.asarray(rng.standard_normal((embedder.dim, n_classes)) * 0.01,
                             dtype=jnp.float32)
        self.b = jnp.zeros((n_classes,), dtype=jnp.float32)
        self._trained = False

    def instruction_text(self, text: str) -> str:
        lines = [ln for ln in text.splitlines() if ln.strip()]
        return " ".join(lines[: self.instr_lines]) if lines else text

    def fit(self, texts: Sequence[str], labels: Sequence[int],
            steps: int = 300, lr: float = 0.05) -> float:
        """Train with full-batch Adam; returns final training accuracy."""
        x = jnp.asarray(self.embedder.encode_batch(
            [self.instruction_text(t) for t in texts]))
        y = jnp.asarray(np.asarray(labels, dtype=np.int32))
        params = (self.w, self.b)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        loss_grad = jax.jit(jax.value_and_grad(
            lambda p: _lr_loss(p, x, y, self.n_classes)))

        @jax.jit
        def adam_step(params, m, v, t):
            loss, g = loss_grad(params)
            m = jax.tree.map(lambda a, b_: 0.9 * a + 0.1 * b_, m, g)
            v = jax.tree.map(lambda a, b_: 0.999 * a + 0.001 * b_ * b_, v, g)
            mh = jax.tree.map(lambda a: a / (1 - 0.9 ** t), m)
            vh = jax.tree.map(lambda a: a / (1 - 0.999 ** t), v)
            params = jax.tree.map(
                lambda p, a, b_: p - lr * a / (jnp.sqrt(b_) + 1e-8), params, mh, vh)
            return params, m, v, loss

        for t in range(1, steps + 1):
            params, m, v, _ = adam_step(params, m, v, jnp.float32(t))
        self.w, self.b = params
        self._trained = True
        pred = np.argmax(np.asarray(_lr_predict_logits(self.w, self.b, x)), axis=1)
        return float(np.mean(pred == np.asarray(y)))

    def predict(self, text: str) -> int:
        return int(self.predict_batch([text])[0])

    def predict_batch(self, texts: Sequence[str]) -> np.ndarray:
        """Classify a batch in one embed + one matmul; (len(texts),) labels."""
        e = jnp.asarray(self.embedder.encode_batch(
            [self.instruction_text(t) for t in texts]))
        return np.argmax(np.asarray(_lr_predict_logits(self.w, self.b, e)),
                         axis=1)

    def state_dict(self) -> dict:
        return {"w": np.asarray(self.w), "b": np.asarray(self.b)}

    def load_state_dict(self, d: dict) -> None:
        self.w = jnp.asarray(d["w"]); self.b = jnp.asarray(d["b"])
        self._trained = True


# ---------------------------------------------------------------------------
# Online k-means (paper Eq. 9-10): cosine assignment, incremental update.
# ---------------------------------------------------------------------------


class OnlineKMeans:
    """Online k-means with cosine assignment and decaying-rate updates.

    Holds TWO synchronized copies of (centroids, counts, initialized):

      * the host numpy mirror — the Eq. 9–10 reference implementation
        (``assign``/``update``) and what ``state_dict`` serializes;
      * a cached device tuple — what the router's fused decision program
        reads and writes.  ``load_device_state`` just swaps the cached
        tuple (no download), so steady-state device routing moves *no*
        k-means state across the host↔device boundary; the host mirror is
        refreshed lazily (``_sync_host``) only when something reads it.

    ``transfers`` counts every actual upload/download of this state —
    the fleet residency convention's audit trail (core/residency.py).
    """

    def __init__(self, k: int, dim: int):
        if k < 1:
            raise ValueError("k must be >= 1")
        self.k = k
        self.dim = dim
        self._h_centroids = np.zeros((k, dim), dtype=np.float32)
        self._h_counts = np.zeros((k,), dtype=np.int64)
        self._h_init = 0  # first K distinct embeddings seed the centroids
        # device residency: cached (centroids f32, counts f32, init i32)
        # tuple, or None when the host mirror is newer / nothing uploaded
        self._dev: Optional[tuple] = None
        self._host_stale = False     # device copy has updates host lacks
        self.transfers = TransferLedger()

    # -- host/device mirror plumbing ----------------------------------------

    def _sync_host(self) -> None:
        """Refresh the host mirror from the device copy (one download)."""
        if self._host_stale:
            cent, cnt, ini = self._dev
            self._h_centroids = np.asarray(cent, dtype=np.float32).copy()
            self._h_counts = np.asarray(np.rint(np.asarray(cnt)),
                                        dtype=np.int64)
            self._h_init = int(ini)
            self._host_stale = False
            self.transfers.count_d2h()

    def _invalidate_device(self) -> None:
        """Host-side mutation: drop the (now stale) device copy."""
        self._dev = None

    @property
    def centroids(self) -> np.ndarray:
        self._sync_host()
        return self._h_centroids

    @property
    def counts(self) -> np.ndarray:
        self._sync_host()
        return self._h_counts

    @property
    def _initialized(self) -> int:
        self._sync_host()
        return self._h_init

    def assign(self, e: np.ndarray) -> int:
        """Eq. 9: argmax_c cos(e, mu_c) over initialized centroids."""
        self._sync_host()
        live = max(self._h_init, 1)
        c = self._h_centroids[:live]
        norms = np.linalg.norm(c, axis=1) * max(np.linalg.norm(e), 1e-12)
        sims = (c @ e) / np.maximum(norms, 1e-12)
        return int(np.argmax(sims))

    def update(self, e: np.ndarray) -> int:
        """Assign, then apply the Eq. 10 incremental centroid update."""
        self._sync_host()
        self._invalidate_device()
        e = np.asarray(e, dtype=np.float32)
        if self._h_init < self.k:
            # seed from the first K distinct embeddings (paper §4.2.2)
            for i in range(self._h_init):
                if np.allclose(self._h_centroids[i], e, atol=1e-6):
                    break
            else:
                idx = self._h_init
                self._h_centroids[idx] = e
                self._h_counts[idx] = 1
                self._h_init += 1
                return idx
        c = self.assign(e)
        n = self._h_counts[c]
        self._h_centroids[c] += (e - self._h_centroids[c]) / (n + 1)
        self._h_counts[c] += 1
        return c

    def state_dict(self) -> dict:
        self._sync_host()
        return {"centroids": self._h_centroids.copy(),
                "counts": self._h_counts.copy(),
                "initialized": self._h_init}

    def load_state_dict(self, d: dict) -> None:
        self._host_stale = False
        self._invalidate_device()
        self._h_centroids = np.asarray(d["centroids"], dtype=np.float32).copy()
        self._h_counts = np.asarray(d["counts"], dtype=np.int64).copy()
        self._h_init = int(d["initialized"])

    # -- device path (fused featurize→score pipeline) -----------------------

    def device_state(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """(centroids, counts, initialized) as device arrays for the jitted
        Eq. 9–10 replay (counts as float32: exact for any realistic stream,
        and the Eq. 10 step divides by them).  Cached: uploads once after a
        host-side mutation, then returns the resident tuple for free."""
        if self._dev is None:
            self._dev = (jnp.asarray(self._h_centroids),
                         jnp.asarray(self._h_counts, jnp.float32),
                         jnp.int32(self._h_init))
            self.transfers.count_h2d()
        return self._dev

    def load_device_state(self, centroids, counts, initialized) -> None:
        """Adopt a jitted update's output arrays as the resident state.
        No download happens here — the host mirror is marked stale and
        refreshed lazily on its next read."""
        self._dev = (centroids, counts, initialized)
        self._host_stale = True

    def update_batch_device(self, embs: np.ndarray) -> np.ndarray:
        """Assign + update a whole batch on device (one jitted scan),
        keeping the state device-resident; returns (Q,) cluster ids.
        Semantically identical to Q sequential ``update`` calls — the
        scan replays the Eq. 10 centroid shifts in arrival order."""
        cent, cnt, ini = self.device_state()
        cent, cnt, ini, clusters = _kmeans_scan_jit(
            cent, cnt, ini, jnp.asarray(embs, jnp.float32))
        self.load_device_state(cent, cnt, ini)
        return np.asarray(clusters, dtype=np.int64)


def kmeans_update_scan(centroids, counts, initialized, embs, valid=None):
    """Eq. 9–10 over a batch as a ``lax.scan`` in arrival order.

    Each step replays exactly what ``OnlineKMeans.update`` does on host:
    seed the next free centroid when fewer than K *distinct* embeddings
    have been seen (distinctness = np.allclose's |c−e| ≤ atol + rtol·|e|
    with atol=1e-6, rtol=1e-5), otherwise cosine-assign over the live
    centroids and apply the incremental update μ_c += (e−μ_c)/(N_c+1).
    The sequential dependency is intrinsic — each update shifts the
    centroid the next assignment sees — which is why this is a scan and
    not a vmap; batched and sequential featurization therefore agree.

    centroids: (K, D) f32; counts: (K,) f32; initialized: () i32;
    embs: (Q, D) f32 → (centroids', counts', initialized', clusters (Q,)).
    ``valid`` (Q,) bool marks real rows — padding rows (callers pad Q to a
    power of two for jit-cache stability) leave the state untouched and
    get cluster 0.
    """
    k = centroids.shape[0]
    idx = jnp.arange(k)
    if valid is None:
        valid = jnp.ones(embs.shape[0], bool)

    def step(carry, xs):
        cent, cnt, ini = carry
        e, v = xs
        close = jnp.all(
            jnp.abs(cent - e[None, :]) <= 1e-6 + 1e-5 * jnp.abs(e)[None, :],
            axis=1)
        is_dup = jnp.any(close & (idx < ini))
        can_seed = (ini < k) & ~is_dup
        # Eq. 9 assignment over the live centroids (at least one)
        live = jnp.maximum(ini, 1)
        norms = jnp.linalg.norm(cent, axis=1) \
            * jnp.maximum(jnp.linalg.norm(e), 1e-12)
        sims = (cent @ e) / jnp.maximum(norms, 1e-12)
        c = jnp.argmax(jnp.where(idx < live, sims, -jnp.inf)).astype(jnp.int32)
        upd_cent = cent.at[c].add((e - cent[c]) / (cnt[c] + 1.0))
        upd_cnt = cnt.at[c].add(1.0)
        seed_at = jnp.minimum(ini, k - 1)        # clamped; unused when full
        seed_cent = cent.at[seed_at].set(e)
        seed_cnt = cnt.at[seed_at].set(1.0)
        new_cent = jnp.where(can_seed, seed_cent, upd_cent)
        new_cnt = jnp.where(can_seed, seed_cnt, upd_cnt)
        cluster = jnp.where(v, jnp.where(can_seed, ini, c), 0)
        return ((jnp.where(v, new_cent, cent),
                 jnp.where(v, new_cnt, cnt),
                 ini + (v & can_seed).astype(ini.dtype)),
                cluster)

    (cent, cnt, ini), clusters = jax.lax.scan(
        step, (centroids, counts, initialized), (embs, valid))
    return cent, cnt, ini, clusters


def kmeans_assign_batch(centroids, initialized, embs):
    """Read-only Eq. 9 assignment for a batch (the cache probe): no state
    change, so the rows vectorize — identical to Q independent ``assign``
    calls on the same centroids."""
    k = centroids.shape[0]
    live = jnp.maximum(initialized, 1)
    cnorm = jnp.linalg.norm(centroids, axis=1)                   # (K,)
    enorm = jnp.maximum(jnp.linalg.norm(embs, axis=1), 1e-12)    # (Q,)
    sims = (embs @ centroids.T) / jnp.maximum(cnorm[None, :] * enorm[:, None],
                                              1e-12)
    sims = jnp.where(jnp.arange(k)[None, :] < live, sims, -jnp.inf)
    return jnp.argmax(sims, axis=1).astype(jnp.int32)


# the resident (centroids, counts, initialized) tuple threads through and
# is immediately replaced by the outputs, so the buffers are donated where
# the backend supports it (no-op copy on CPU — compat.donation_kwargs)
_kmeans_scan_jit = jax.jit(kmeans_update_scan,
                           **compat.donation_kwargs(0, 1, 2))


def _pad_cols(a: np.ndarray, width: int, fill) -> np.ndarray:
    """Right-pad a (Q, L) feature tensor to L=width (stacking full-text and
    instruction rows into one kernel call needs a common L)."""
    if a.shape[1] == width:
        return a
    return np.pad(a, ((0, 0), (0, width - a.shape[1])),
                  constant_values=fill)


def _pad_rows(a: np.ndarray, q_pad: int, fill) -> np.ndarray:
    """Bottom-pad an (n, L) tensor to q_pad rows (padding the batch axis
    to a power of two bounds the compiled jit variants)."""
    if q_pad == a.shape[0]:
        return a
    out = np.full((q_pad, a.shape[1]), fill, dtype=a.dtype)
    out[: a.shape[0]] = a
    return out


@functools.partial(jax.jit, static_argnames=("n", "use_task", "use_cluster"))
def _probe_pipeline(ids, weights, proj, w, b, centroids, initialized, *,
                    n: int, use_task: bool, use_cluster: bool):
    """Fused read-only probe: featurize (full texts, plus instruction
    slices when the task feature is on, stacked into one kernel call) →
    classifier logits → Eq. 9 assignment.  No state is written."""
    from repro.kernels.featurize import hashed_embed
    e = hashed_embed(ids, weights, proj)
    emb, emb_i = e[:n], e[n:]
    if use_task:
        labels = jnp.argmax(emb_i @ w + b, axis=1).astype(jnp.int32)
    else:
        labels = jnp.zeros((n,), jnp.int32)
    if use_cluster:
        clusters = kmeans_assign_batch(centroids, initialized, emb)
    else:
        clusters = jnp.zeros((n,), jnp.int32)
    return labels, clusters, emb


# ---------------------------------------------------------------------------
# Flesch Reading Ease (Eq. 11) + equal-width binning.
# ---------------------------------------------------------------------------

_SENT_SPLIT = re.compile(r"[.!?]+")
_VOWEL_GROUPS = re.compile(r"[aeiouy]+")


def count_syllables(word: str) -> int:
    w = word.lower().strip("'")
    if not w:
        return 0
    groups = _VOWEL_GROUPS.findall(w)
    n = len(groups)
    if w.endswith("e") and n > 1 and not w.endswith(("le", "ee", "ye")):
        n -= 1  # silent final e
    return max(n, 1)


def flesch_counts(text: str) -> Tuple[int, int, int]:
    """(words, sentences, syllables) — the integer sufficient statistics
    of Eq. 11.  The string/regex work stays on host; the (cheap) score
    arithmetic can then run wherever the bins are consumed — the fused
    device pipeline computes score+bin from these counts with the exact
    float32 op order of ``flesch_score_from_counts``.  Sentences are
    clamped to >= 1 so the ratio is always defined."""
    words = tokenize(text)
    sentences = max(len([s for s in _SENT_SPLIT.split(text) if s.strip()]),
                    1)
    syllables = sum(count_syllables(w) for w in words)
    return len(words), sentences, syllables


def flesch_score_from_counts(n_words: int, n_sentences: int,
                             n_syllables: int) -> float:
    """Eq. 11 from the counts, in float32 with a fixed op order — the
    single arithmetic spec both the host reference path and the device
    pipeline implement, so host/device bins agree bitwise.  Zero words
    (empty/punctuation-only text) scores 100.0 (trivially simple)."""
    if n_words == 0:
        return 100.0
    ws = np.float32(n_words) / np.float32(n_sentences)
    sw = np.float32(n_syllables) / np.float32(n_words)
    score = (np.float32(206.835) - np.float32(1.015) * ws
             - np.float32(84.6) * sw)
    return float(np.clip(score, np.float32(0.0), np.float32(100.0)))


def flesch_reading_ease(text: str) -> float:
    """Eq. 11; clamped to [0, 100] as the paper bins in that range."""
    return flesch_score_from_counts(*flesch_counts(text))


class FleschComplexity:
    """Score + equal-width binning into N_bins categories (paper §4.2.3)."""

    def __init__(self, n_bins: int, lo: float = 0.0, hi: float = 100.0):
        if n_bins < 1:
            raise ValueError("n_bins must be >= 1")
        self.n_bins = n_bins
        self.lo, self.hi = lo, hi

    @property
    def bin_width32(self) -> np.float32:
        """Equal-bin width in float32 — the scalar the device binning
        stage consumes (must match ``bin``'s arithmetic exactly)."""
        return np.float32(self.hi - self.lo) / np.float32(self.n_bins)

    def score(self, text: str) -> float:
        return flesch_reading_ease(text)

    def bin(self, score: float) -> int:
        # float32 with int truncation toward zero — mirrored by the device
        # pipeline's (score - lo) / width → int32 cast
        b = int((np.float32(score) - np.float32(self.lo)) / self.bin_width32)
        return int(np.clip(b, 0, self.n_bins - 1))

    def __call__(self, text: str) -> Tuple[float, int]:
        s = self.score(text)
        return s, self.bin(s)


# ---------------------------------------------------------------------------
# Context vectorizer: one-hot + intercept (paper §4.2.4).
# ---------------------------------------------------------------------------


class ContextGenerator:
    """Combines the three extractors into x_t ∈ R^d (d = N_tasks+K+N_bins+1)."""

    def __init__(self, config: RouterConfig, embedder: Optional[EmbeddingModel] = None):
        self.config = config
        self.embedder = embedder or EmbeddingModel()
        self.task_classifier = TaskClassifier(self.embedder, n_classes=config.n_tasks,
                                              seed=config.seed)
        self.kmeans = OnlineKMeans(config.n_clusters, self.embedder.dim)
        self.complexity = FleschComplexity(config.n_complexity_bins)
        # feature toggles for the ablation study (paper §6.2.3)
        self.use_task = True
        self.use_cluster = True
        self.use_complexity = True

    def set_features(self, task: bool = True, cluster: bool = True,
                     complexity: bool = True) -> None:
        self.use_task, self.use_cluster, self.use_complexity = task, cluster, complexity

    @property
    def dim(self) -> int:
        return self.config.context_dim

    def encode(self, task_label: int, cluster: int, comp_bin: int) -> np.ndarray:
        cfg = self.config
        x = np.zeros(cfg.context_dim, dtype=np.float32)
        if self.use_task:
            x[task_label] = 1.0
        if self.use_cluster:
            x[cfg.n_tasks + cluster] = 1.0
        if self.use_complexity:
            x[cfg.n_tasks + cfg.n_clusters + comp_bin] = 1.0
        x[-1] = 1.0  # intercept
        return x

    def __call__(self, text: str) -> ContextVector:
        # the batch-of-one: keeps the sequential and batched featurization
        # paths structurally identical (route_batch's equivalence guarantee)
        return self.batch([text])[0]

    def batch(self, texts: Sequence[str],
              embeddings: Optional[np.ndarray] = None,
              task_labels: Optional[np.ndarray] = None) -> list:
        """Featurize a query batch: List[ContextVector], index-aligned.

        Embedding + task classification are vectorized; the k-means
        centroid updates (Eq. 10) stay sequential in arrival order because
        each update shifts the centroid the next assignment sees — this is
        exactly what Q successive ``__call__``s would compute, so batched
        and sequential featurization agree bitwise.

        ``embeddings`` (n, dim) / ``task_labels`` (n,), optional: reuse
        feature work a caller already did on the same texts (the serving
        scheduler's cache probe embeds and classifies every query before
        routing).  The embedder and classifier are deterministic, so
        passing their own outputs back is bitwise identical to recomputing
        — the k-means *updates* still happen here, in arrival order.
        """
        if not texts:
            return []
        n = len(texts)
        if not self.use_task:
            task_labels = np.zeros(n, dtype=np.int64)
        elif task_labels is None:
            task_labels = self.task_classifier.predict_batch(texts)
        if self.use_cluster:
            embs = (embeddings if embeddings is not None
                    else self.embedder.encode_batch(texts))
            clusters = [self.kmeans.update(e) for e in embs]
        else:
            clusters = [0] * n
        comp = ([self.complexity(t) for t in texts] if self.use_complexity
                else [(100.0, 0)] * n)
        return self.make_contexts(task_labels, clusters, comp)

    def make_contexts(self, task_labels, clusters, comp) -> List[ContextVector]:
        """Index-aligned ContextVectors from per-query (label, cluster,
        (score, bin)) triples — shared by the host and device paths (the
        one-hot layout is ``encode``'s, identically 0/1 on both)."""
        return [ContextVector(
            task_label=int(task_labels[i]), cluster=int(clusters[i]),
            complexity_bin=comp[i][1], complexity_score=comp[i][0],
            vector=self.encode(int(task_labels[i]), int(clusters[i]),
                               comp[i][1]))
            for i in range(len(task_labels))]

    # -- device featurization (the fused featurize→score pipeline) ----------

    @property
    def device_active(self) -> bool:
        """True when featurization should run through the Pallas pipeline
        (``RouterConfig.featurize`` toggle; "auto" = accelerator only)."""
        return self.config.resolve_featurize_device()

    def complexity_counts_batch(self, texts: Sequence[str]) -> np.ndarray:
        """Host half of the Flesch stage: the (Q, 3) int32
        (words, sentences, syllables) count matrix.  Only the
        string/regex tokenization stays on host — the Eq. 11 score and
        equal-width binning run inside the fused device pipeline from
        these counts (float32, op order of ``flesch_score_from_counts``).
        With complexity ablated the counts are all-zero (sentences
        clamped to 1), which the device maps to score 100.0 / bin 0 —
        the same sentinel the host path uses."""
        if self.use_complexity:
            counts = [flesch_counts(t) for t in texts]
        else:
            counts = [(0, 1, 0)] * len(texts)
        return np.asarray(counts, dtype=np.int32).reshape(len(texts), 3)

    def instruction_features(self, texts: Sequence[str]
                             ) -> Tuple[np.ndarray, np.ndarray]:
        """Hashed (ids, weights) for the classifier's instruction slices."""
        return self.embedder.hashed_features(
            [self.task_classifier.instruction_text(t) for t in texts])

    def padded_feature_tensors(self, texts: Sequence[str], want_full: bool,
                               want_instr: bool, q_pad: int
                               ) -> Tuple[np.ndarray, np.ndarray]:
        """Stacked, padded (ids, weights) for the fused device pipelines:
        the full-text half (when ``want_full``) followed by the
        instruction half (when ``want_instr``), each column-padded to one
        power-of-two L (floor 128, fill id −1 / weight 0) and row-padded
        to ``q_pad`` — the single owner of the layout both
        ``_probe_pipeline`` and the router's ``_fused_decide`` slice at
        the padded boundary (``e[:q_pad]`` / ``e[q_pad:]``)."""
        from repro.kernels.featurize.ops import pad_pow2
        halves = []
        if want_full:
            halves.append(self.embedder.hashed_features(texts))
        if want_instr:
            halves.append(self.instruction_features(texts))
        width = pad_pow2(max(h[0].shape[1] for h in halves), floor=128)
        ids = np.concatenate(
            [_pad_rows(_pad_cols(i, width, -1), q_pad, -1)
             for i, _ in halves])
        weights = np.concatenate(
            [_pad_rows(_pad_cols(w, width, 0.0), q_pad, 0.0)
             for _, w in halves])
        return ids, weights

    def classifier_params(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self.task_classifier.w, self.task_classifier.b

    def probe_batch(self, texts: Sequence[str]
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Read-only batched features for cache probes: (task labels,
        cluster assignments, unit embeddings), one featurization pass for
        the whole batch.  Mutates nothing — classifier ``predict`` and
        k-means ``assign`` semantics; Eq. 10 updates stay exclusive to
        routing.  On the device path this is one fused jitted call whose
        embeddings the router then reuses (the query is embedded once per
        lifecycle)."""
        n = len(texts)
        if n == 0:
            z = np.zeros(0, dtype=np.int64)
            return z, z.copy(), np.zeros((0, self.embedder.dim), np.float32)
        if not self.device_active:
            embs = self.embedder.encode_batch(texts)
            labels = (self.task_classifier.predict_batch(texts)
                      if self.use_task else np.zeros(n, dtype=np.int64))
            clusters = (np.asarray([self.kmeans.assign(e) for e in embs],
                                   dtype=np.int64) if self.use_cluster
                        else np.zeros(n, dtype=np.int64))
            return labels, clusters, embs
        from repro.kernels.featurize.ops import pad_pow2
        # Q and L padded to powers of two so the compiled probe variants
        # stay bounded (padding rows are read-only garbage, sliced off)
        q_pad = pad_pow2(n)
        ids, weights = self.padded_feature_tensors(
            texts, want_full=True, want_instr=self.use_task, q_pad=q_pad)
        cent, _, ini = self.kmeans.device_state()
        w, b = self.classifier_params()
        labels, clusters, emb = _probe_pipeline(
            jnp.asarray(ids), jnp.asarray(weights), self.embedder.proj_device,
            w, b, cent, ini, n=q_pad, use_task=self.use_task,
            use_cluster=self.use_cluster)
        return (np.asarray(labels, dtype=np.int64)[:n],
                np.asarray(clusters, dtype=np.int64)[:n],
                np.asarray(emb)[:n])

    def state_dict(self) -> dict:
        return {"task": self.task_classifier.state_dict(),
                "kmeans": self.kmeans.state_dict()}

    def load_state_dict(self, d: dict) -> None:
        self.task_classifier.load_state_dict(d["task"])
        self.kmeans.load_state_dict(d["kmeans"])

"""Contextual multi-armed bandits (paper §4.3) in batched, jittable JAX.

Implements the three policies the paper evaluates:

  * LinUCB (primary, Eq. 13)        — linear reward model + UCB exploration
  * Contextual Thompson Sampling    — Bayesian linear posterior sampling
  * ε-Greedy (contextual & plain)   — decayed random exploration

All state lives in a single ``BanditState`` pytree with *static* arm capacity
(``max_arms``) and an ``active`` mask, so jitted select/update never retrace
when models are added at runtime (paper §6.3.4: zero-calibration addition).

Two solve modes:

  * ``sherman_morrison`` (default, beyond-paper): maintain A_m⁻¹ directly via
    the rank-1 Sherman–Morrison identity — O(d²) per update and O(|M|·d²) per
    decision.  Mathematically identical to inverting A_m.
  * ``cholesky`` (paper-faithful, App. B): re-solve A_m θ = b_m per decision —
    O(|M|·d³).  Kept for the §Perf baseline comparison.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.residency import TransferLedger
from repro.core.types import RouterConfig
from repro.kernels.featurize.ops import pad_pow2
from repro.kernels.linucb.ops import linucb_scores as linucb_scores_kernel

NEG_INF = -1e30


class BanditState(NamedTuple):
    """Per-arm sufficient statistics. Shapes: M=max_arms, d=context dim."""

    A: jax.Array          # (M, d, d) ridge design matrices  A_m = λI + Σ x xᵀ
    A_inv: jax.Array      # (M, d, d) maintained inverses (Sherman–Morrison)
    b: jax.Array          # (M, d)    reward-weighted contexts Σ r x
    theta: jax.Array      # (M, d)    cached θ̂_m = A_m⁻¹ b_m
    reward_sum: jax.Array # (M,)      Σ r      (non-contextual ε-greedy)
    counts: jax.Array     # (M,)      pull counts
    active: jax.Array     # (M,) bool — is this slot a live model?
    eps: jax.Array        # ()        current ε (decayed)
    t: jax.Array          # ()        global step
    key: jax.Array        # PRNG key


def init_state(config: RouterConfig, n_arms: int) -> BanditState:
    m, d = config.max_arms, config.context_dim
    if n_arms > m:
        raise ValueError(f"n_arms={n_arms} exceeds max_arms={m}")
    lam = config.lambda_reg
    eye = jnp.eye(d, dtype=jnp.float32)
    return BanditState(
        A=jnp.tile(eye[None] * lam, (m, 1, 1)),
        A_inv=jnp.tile(eye[None] / lam, (m, 1, 1)),
        b=jnp.zeros((m, d), jnp.float32),
        theta=jnp.zeros((m, d), jnp.float32),
        reward_sum=jnp.zeros((m,), jnp.float32),
        counts=jnp.zeros((m,), jnp.float32),
        active=jnp.arange(m) < n_arms,
        eps=jnp.float32(config.epsilon0),
        t=jnp.int32(0),
        key=jax.random.PRNGKey(config.seed),
    )


def add_arm(state: BanditState, config: RouterConfig) -> Tuple[BanditState, int]:
    """Activate the next free slot with a fresh ridge prior (online addition)."""
    idx = int(np.asarray(jnp.sum(state.active)))
    if idx >= config.max_arms:
        raise ValueError("bandit at capacity; raise RouterConfig.max_arms")
    d = config.context_dim
    eye = jnp.eye(d, dtype=jnp.float32)
    state = state._replace(
        A=state.A.at[idx].set(eye * config.lambda_reg),
        A_inv=state.A_inv.at[idx].set(eye / config.lambda_reg),
        b=state.b.at[idx].set(0.0),
        theta=state.theta.at[idx].set(0.0),
        reward_sum=state.reward_sum.at[idx].set(0.0),
        counts=state.counts.at[idx].set(0.0),
        active=state.active.at[idx].set(True),
    )
    return state, idx


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------


def _masked(scores: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.where(mask, scores, NEG_INF)


def linucb_scores(state: BanditState, x: jax.Array, alpha: float,
                  solve_mode: str = "sherman_morrison") -> jax.Array:
    """Eq. 13: θ̂ᵀx + α·sqrt(xᵀ A⁻¹ x), batched over arms."""
    if solve_mode == "cholesky":
        # Paper-faithful path: factor A per decision (O(M d³)).
        chol = jax.vmap(jnp.linalg.cholesky)(state.A)           # (M, d, d)
        theta = jax.vmap(lambda c, b: jax.scipy.linalg.cho_solve((c, True), b))(
            chol, state.b)                                       # (M, d)
        ainv_x = jax.vmap(lambda c: jax.scipy.linalg.cho_solve((c, True), x))(chol)
    else:
        theta = state.theta
        ainv_x = jnp.einsum("mij,j->mi", state.A_inv, x)         # (M, d)
    mean = theta @ x                                             # (M,)
    var = jnp.maximum(ainv_x @ x, 0.0)
    return mean + alpha * jnp.sqrt(var)


def linucb_scores_batch(state: BanditState, X: jax.Array,
                        alpha: float) -> jax.Array:
    """Eq. 13 over a query batch: (Q, d) contexts → (Q, M) UCB scores.

    Runs the fused Pallas kernel (kernels/linucb) over the maintained
    Sherman–Morrison inverses — one VMEM pass per (Q-block, M-block) tile
    instead of Q separate decision solves.
    """
    return linucb_scores_kernel(state.A_inv, state.theta, X, alpha)


def thompson_scores(state: BanditState, x: jax.Array, sigma: float,
                    key: jax.Array) -> jax.Array:
    """Sample θ~N(θ̂, σ²A⁻¹) per arm and score θᵀx (Agrawal & Goyal 2013)."""
    m, d = state.theta.shape
    # A_inv is SPD; its Cholesky factor maps N(0,I) -> N(0, A_inv).
    chol = jax.vmap(jnp.linalg.cholesky)(
        state.A_inv + 1e-8 * jnp.eye(d, dtype=state.A_inv.dtype)[None])
    z = jax.random.normal(key, (m, d), dtype=state.theta.dtype)
    theta_s = state.theta + sigma * jnp.einsum("mij,mj->mi", chol, z)
    return theta_s @ x


def greedy_ctx_scores(state: BanditState, x: jax.Array) -> jax.Array:
    return state.theta @ x


def greedy_plain_scores(state: BanditState) -> jax.Array:
    return state.reward_sum / jnp.maximum(state.counts, 1.0)


# ---------------------------------------------------------------------------
# Select / update (jitted factories)
# ---------------------------------------------------------------------------


def make_select_fn(config: RouterConfig):
    """Returns jitted select(state, x, feasible) -> (arm, scores, state)."""
    algo = config.algorithm
    alpha = config.alpha_ucb
    sigma = config.cts_sigma
    solve_mode = config.solve_mode

    @jax.jit
    def select(state: BanditState, x: jax.Array, feasible: jax.Array):
        mask = state.active & feasible
        key, k_sel, k_eps = jax.random.split(state.key, 3)
        if algo == "linucb":
            scores = linucb_scores(state, x, alpha, solve_mode)
            arm = jnp.argmax(_masked(scores, mask))
        elif algo == "cts":
            scores = thompson_scores(state, x, sigma, k_sel)
            arm = jnp.argmax(_masked(scores, mask))
        elif algo in ("eps_greedy", "eps_greedy_ctx"):
            scores = (greedy_ctx_scores(state, x) if algo == "eps_greedy_ctx"
                      else greedy_plain_scores(state))
            greedy_arm = jnp.argmax(_masked(scores, mask))
            # uniform over feasible arms for the exploration branch
            probs = mask / jnp.maximum(jnp.sum(mask), 1)
            rand_arm = jax.random.choice(k_sel, mask.shape[0], p=probs)
            explore = jax.random.uniform(k_eps) < state.eps
            arm = jnp.where(explore, rand_arm, greedy_arm)
        else:
            raise ValueError(f"unknown algorithm {algo!r}")
        return arm, _masked(scores, mask), state._replace(key=key)

    return select


def make_select_batch_scan_fn(config: RouterConfig):
    """Returns jitted select_batch(state, X, feasible, valid) →
    (arms, masked scores, state) for the policies the fused LinUCB kernel
    cannot serve (CTS posterior draws, ε-greedy exploration, the
    per-decision Cholesky mode).

    One ``lax.scan`` over the batch with *exact* sequential semantics:
    each row repeats ``make_select_fn``'s body — the same
    ``jax.random.split(key, 3)``, the same score arithmetic, the same
    masked argmax — so Q rows leave the state (PRNG key included)
    bit-identical to Q successive ``select`` calls.  ``valid`` marks real
    rows: callers pad Q to a power of two to bound the compiled variants,
    and a padding row must not consume a draw (the key only advances on
    valid rows), or batched and sequential selection would diverge.

    The state threads through and is replaced by the output, so its
    buffers are donated where the backend supports it — batched CTS
    selection allocates no second copy of A/A⁻¹/θ on device.
    """
    algo = config.algorithm
    alpha = config.alpha_ucb
    sigma = config.cts_sigma
    solve_mode = config.solve_mode

    def step(state: BanditState, xs):
        x, feasible, v = xs
        mask = state.active & feasible
        key, k_sel, k_eps = jax.random.split(state.key, 3)
        if algo == "linucb":
            scores = linucb_scores(state, x, alpha, solve_mode)
            arm = jnp.argmax(_masked(scores, mask))
        elif algo == "cts":
            scores = thompson_scores(state, x, sigma, k_sel)
            arm = jnp.argmax(_masked(scores, mask))
        elif algo in ("eps_greedy", "eps_greedy_ctx"):
            scores = (greedy_ctx_scores(state, x) if algo == "eps_greedy_ctx"
                      else greedy_plain_scores(state))
            greedy_arm = jnp.argmax(_masked(scores, mask))
            probs = mask / jnp.maximum(jnp.sum(mask), 1)
            rand_arm = jax.random.choice(k_sel, mask.shape[0], p=probs)
            explore = jax.random.uniform(k_eps) < state.eps
            arm = jnp.where(explore, rand_arm, greedy_arm)
        else:
            raise ValueError(f"unknown algorithm {algo!r}")
        return (state._replace(key=jnp.where(v, key, state.key)),
                (arm, _masked(scores, mask)))

    @functools.partial(jax.jit, **compat.donation_kwargs(0))
    def select_batch(state: BanditState, X: jax.Array, feasible: jax.Array,
                     valid: jax.Array):
        state, (arms, masked) = jax.lax.scan(step, state,
                                             (X, feasible, valid))
        return arms, masked, state

    return select_batch


def make_update_fn(config: RouterConfig):
    """Returns jitted update(state, arm, x, r) -> state.

    LinUCB/CTS posterior update (paper §4.3):
        A_m ← A_m + x xᵀ ;  b_m ← b_m + r x ;  θ̂_m = A_m⁻¹ b_m
    with A⁻¹ maintained by Sherman–Morrison:
        A⁻¹ ← A⁻¹ − (A⁻¹ x)(A⁻¹ x)ᵀ / (1 + xᵀ A⁻¹ x)
    """
    decay = config.epsilon_decay
    eps_min = config.epsilon_min

    # the state is threaded through and replaced by the caller, so its
    # buffers are donated where the backend supports it (compat helper)
    @functools.partial(jax.jit, **compat.donation_kwargs(0))
    def update(state: BanditState, arm: jax.Array, x: jax.Array,
               r: jax.Array) -> BanditState:
        A_m = state.A[arm] + jnp.outer(x, x)
        ainv = state.A_inv[arm]
        ainv_x = ainv @ x
        denom = 1.0 + x @ ainv_x
        ainv_new = ainv - jnp.outer(ainv_x, ainv_x) / denom
        b_m = state.b[arm] + r * x
        theta_m = ainv_new @ b_m
        return state._replace(
            A=state.A.at[arm].set(A_m),
            A_inv=state.A_inv.at[arm].set(ainv_new),
            b=state.b.at[arm].set(b_m),
            theta=state.theta.at[arm].set(theta_m),
            reward_sum=state.reward_sum.at[arm].add(r),
            counts=state.counts.at[arm].add(1.0),
            eps=jnp.maximum(state.eps * decay, eps_min),
            t=state.t + 1,
        )

    return update


# ---------------------------------------------------------------------------
# Convenience OO wrapper used by the router
# ---------------------------------------------------------------------------


class BanditPolicy:
    """Thin stateful wrapper holding a BanditState + jitted fns."""

    def __init__(self, config: RouterConfig, n_arms: int):
        self.config = config
        self.state = init_state(config, n_arms)
        self._select = make_select_fn(config)
        self._update = make_update_fn(config)
        self._select_batch_scan = None   # built on first stochastic batch
        # residency audit: BanditState lives on device; the ledger counts
        # the deliberate host syncs (state_dict / load / rescalarize) so
        # tests can assert routing itself moves no bandit state
        self.transfers = TransferLedger()

    @property
    def n_arms(self) -> int:
        return int(np.asarray(jnp.sum(self.state.active)))

    def select(self, x: np.ndarray, feasible: np.ndarray) -> Tuple[int, np.ndarray]:
        feas = jnp.asarray(feasible, dtype=bool)
        # pad feasibility to capacity
        if feas.shape[0] < self.config.max_arms:
            feas = jnp.pad(feas, (0, self.config.max_arms - feas.shape[0]))
        arm, scores, self.state = self._select(self.state, jnp.asarray(x), feas)
        return int(arm), np.asarray(scores)

    def select_batch(self, X: np.ndarray,
                     feasible: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized arm selection: X (Q, d), feasible (Q, n) bool →
        (arms (Q,), masked scores (Q, max_arms)).

        LinUCB with maintained inverses is deterministic, so the whole
        batch is scored by one fused kernel call and an argmax per row —
        arm choices are identical to Q sequential ``select`` calls on the
        same state.  Stochastic policies (CTS, ε-greedy) and the
        per-decision Cholesky mode keep sequential per-query *semantics*
        (each query consumes its own PRNG draw / solve) but run as one
        jitted ``lax.scan`` (``make_select_batch_scan_fn``) — decisions
        and the final PRNG key are identical to the sequential loop, and
        the bandit state never leaves the device.
        """
        X = np.asarray(X, dtype=np.float32)
        feas = np.asarray(feasible, dtype=bool)
        q, m = X.shape[0], self.config.max_arms
        if q == 0:
            return (np.zeros(0, dtype=np.int64),
                    np.zeros((0, m), dtype=np.float32))
        if feas.shape[1] < m:
            feas = np.pad(feas, ((0, 0), (0, m - feas.shape[1])))
        if (self.config.algorithm == "linucb"
                and self.config.solve_mode == "sherman_morrison"):
            scores = linucb_scores_batch(self.state, jnp.asarray(X),
                                         self.config.alpha_ucb)
            mask = np.asarray(self.state.active)[None, :] & feas
            masked = np.where(mask, np.asarray(scores), NEG_INF)
            arms = np.argmax(masked, axis=1)
            self.advance_key()
            return arms.astype(np.int64), masked.astype(np.float32)
        if self._select_batch_scan is None:
            self._select_batch_scan = make_select_batch_scan_fn(self.config)
        # Q padded to a power of two (bounded jit variants); padding rows
        # are marked invalid so they never advance the PRNG key
        q_pad = pad_pow2(q)
        x_pad = np.zeros((q_pad, X.shape[1]), np.float32)
        x_pad[:q] = X
        feas_pad = np.zeros((q_pad, m), bool)
        feas_pad[:q] = feas
        valid = np.arange(q_pad) < q
        arms, masked, self.state = self._select_batch_scan(
            self.state, jnp.asarray(x_pad), jnp.asarray(feas_pad),
            jnp.asarray(valid))
        return (np.asarray(arms, dtype=np.int64)[:q],
                np.asarray(masked, dtype=np.float32)[:q])

    def advance_key(self) -> None:
        """Advance the PRNG key so a batched selection is not a state no-op
        (LinUCB never consumes it for scoring; the stream does NOT match
        what Q sequential select() calls would produce).  Shared by the
        host ``select_batch`` fast path and the router's fused device
        pipeline so both leave the bandit state identically."""
        key, _ = jax.random.split(self.state.key)
        self.state = self.state._replace(key=key)

    def update(self, arm: int, x: np.ndarray, reward: float) -> None:
        self.state = self._update(self.state, jnp.int32(arm), jnp.asarray(x),
                                  jnp.float32(reward))

    def add_arm(self) -> int:
        self.state, idx = add_arm(self.state, self.config)
        return idx

    def rescalarize(self, b: np.ndarray, reward_sum: np.ndarray) -> None:
        """Swap in reward statistics recomputed under a new scalarization.

        A_m and A_m⁻¹ depend only on the observed contexts, never on the
        rewards, so a λ change (``GreenServRouter.set_lambda``) can rebuild
        b_m = Σ r(λ')·x exactly from decomposed accuracy/energy sums and
        refresh θ̂ = A⁻¹ b in one shot — the posterior mean reacts to the
        new trade-off immediately instead of averaging it in over
        thousands of fresh pulls.
        """
        b = np.asarray(b, dtype=np.float32)
        a_inv = np.asarray(self.state.A_inv)
        self.transfers.count_d2h()
        theta = np.einsum("mij,mj->mi", a_inv, b)
        self.state = self.state._replace(
            b=self._on_state_device(b),
            theta=self._on_state_device(theta.astype(np.float32)),
            reward_sum=self._on_state_device(
                np.asarray(reward_sum, np.float32)))
        self.transfers.count_h2d()

    def state_dict(self) -> dict:
        self.transfers.count_d2h()
        return {k: np.asarray(v) for k, v in self.state._asdict().items()}

    def load_state_dict(self, d: dict) -> None:
        self.transfers.count_h2d()
        self.state = BanditState(**{k: self._on_state_device(v)
                                    for k, v in d.items()})

    def _on_state_device(self, x) -> jax.Array:
        """``x`` on the device the bandit state lives on: host-side
        rewrites (fleet all-reduce, λ changes) keep a replica's state on
        its own chip, whatever the default device is when they run."""
        return jax.device_put(x, next(iter(self.state.A_inv.devices())))

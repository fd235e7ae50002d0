"""jit'd public wrapper for the hashed-embedding featurization kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.featurize.kernel import hashed_embed_fwd


def _pick_block(n: int, target: int) -> int:
    b = min(n, target)
    while n % b:
        b -= 1
    return b


def pad_pow2(n: int, floor: int = 1) -> int:
    """Next power of two ≥ n (≥ floor) — callers pad batch shapes to this
    so the jit cache holds log2-many program variants, not one per shape."""
    return max(floor, 1 << max(n - 1, 0).bit_length())


# static blocks/interpret: one compiled program per (L, blocks) combination —
# Q and L are both padded to powers of two below, so the jit cache stays
# bounded at log2-many variants instead of one per serving batch shape
@functools.partial(jax.jit, static_argnames=("bq", "lb", "interpret"))
def _embed_jit(ids, weights, proj, bq: int, lb: int, interpret: bool):
    return hashed_embed_fwd(ids, weights, proj, bq=bq, lb=lb,
                            interpret=interpret)


def hashed_embed(ids: jax.Array, weights: jax.Array, proj: jax.Array,
                 block_q: int = 8, block_l: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """ids/weights: (Q, L), id −1 = padding; proj: (hash_dim, dim) →
    (Q, dim) unit embeddings.  Pads Q and L to powers of two (L floored at
    128 for lane alignment) so serving batches of arbitrary shape reuse a
    handful of compiled programs."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q, seq_l = ids.shape
    q_pad = pad_pow2(q)
    l_pad = pad_pow2(seq_l, floor=128)
    if (q_pad, l_pad) != (q, seq_l):
        ids = jnp.pad(ids, ((0, q_pad - q), (0, l_pad - seq_l)),
                      constant_values=-1)
        weights = jnp.pad(weights, ((0, q_pad - q), (0, l_pad - seq_l)))
    bq = _pick_block(q_pad, block_q)
    lb = _pick_block(l_pad, block_l)
    out = _embed_jit(ids.astype(jnp.int32), weights.astype(jnp.float32),
                     proj.astype(jnp.float32), bq=bq, lb=lb,
                     interpret=bool(interpret))
    return out[:q]

"""jit'd public wrapper for the LinUCB scoring kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.linucb.kernel import linucb_scores_fwd


def _pick_block(n: int, target: int) -> int:
    b = min(n, target)
    while n % b:
        b -= 1
    return b


# static alpha/block/interpret: one compiled program per (M, d, Q, block)
# combination — serving batch sizes recur, so steady state is cache hits,
# not per-call retracing of the pallas_call
@functools.partial(jax.jit, static_argnames=("alpha", "bq", "interpret"))
def _scores_jit(a_inv, theta, xq, alpha: float, bq: int, interpret: bool):
    return linucb_scores_fwd(a_inv, theta, xq, alpha, bq=bq,
                             interpret=interpret)


def linucb_scores(a_inv: jax.Array, theta: jax.Array, x: jax.Array,
                  alpha: float, block_q: int = 128,
                  interpret: Optional[bool] = None) -> jax.Array:
    """a_inv: (M, d, d); theta: (M, d); x: (d,) or (Q, d) → (M,) or (Q, M)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    single = x.ndim == 1
    xq = x[None] if single else x
    # pad Q to the next power of two: serving batches take arbitrary sizes,
    # and compiling one program per distinct Q would thrash the jit cache —
    # padding bounds the compiled variants to log2(block cap) shapes
    q = xq.shape[0]
    q_pad = 1 << max(q - 1, 0).bit_length()
    if q_pad != q:
        xq = jnp.concatenate(
            [xq, jnp.zeros((q_pad - q, xq.shape[1]), xq.dtype)])
    bq = _pick_block(q_pad, block_q)
    out = _scores_jit(a_inv.astype(jnp.float32),
                      theta.astype(jnp.float32),
                      xq.astype(jnp.float32), float(alpha),
                      bq=bq, interpret=bool(interpret))[:q]
    return out[0] if single else out

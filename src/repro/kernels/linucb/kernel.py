"""Batched LinUCB arm-scoring kernel (Pallas TPU) — the paper's router as a
fused TPU op.

The paper's Appendix B puts routing at O(|M|·d³) per decision (a Cholesky
solve per arm).  With A⁻¹ maintained by Sherman–Morrison (see
core/bandits.py) scoring is O(|M|·d²), and this kernel fuses the whole
decision — |M| quadratic forms + means + the UCB combine — into one VMEM
pass, batched over a Q-block of query contexts (serving routes *batches*,
not single queries).

Both terms are plain 2-D matmuls on the MXU: with the arm inverses
flattened to ``(M, d²)`` and each context's outer product ``x xᵀ``
flattened to ``(Q, d²)`` (built by the wrapper), the quadratic form is

    var[q, m] = Σ_ij x_qi A⁻¹_mij x_qj = (xx @ A⁻¹_flatᵀ)[q, m]

and the mean is ``x @ θᵀ``.  Every arm is scored in one ``(bq, M)`` output
block, which keeps the lane dimension whole.

At the paper's scale (|M|=16, d=12) this is sub-microsecond; the kernel
exists so the router stays off the host at production batch sizes
(Q=10³ queries × M=64 arms per step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# contract the last dim of both operands: (q, k) · (m, k) -> (q, m)
_NT = (((1,), (1,)), ((), ()))


def _linucb_kernel(ainv_ref, theta_ref, x_ref, xx_ref, o_ref, *,
                   alpha: float):
    hi = jax.lax.Precision.HIGHEST
    mean = jax.lax.dot_general(x_ref[...], theta_ref[...], _NT, precision=hi,
                               preferred_element_type=jnp.float32)
    var = jax.lax.dot_general(xx_ref[...], ainv_ref[...], _NT, precision=hi,
                              preferred_element_type=jnp.float32)
    o_ref[...] = (mean + alpha * jnp.sqrt(jnp.maximum(var, 0.0))
                  ).astype(o_ref.dtype)


def linucb_scores_fwd(a_inv, theta, x, alpha: float, bq: int,
                      interpret: bool):
    """a_inv: (M, d, d); theta: (M, d); x: (Q, d) → (Q, M) scores."""
    m, d, _ = a_inv.shape
    q = x.shape[0]
    xx = (x[:, :, None] * x[:, None, :]).reshape(q, d * d)
    kernel = functools.partial(_linucb_kernel, alpha=alpha)
    return pl.pallas_call(
        kernel,
        grid=(q // bq,),
        in_specs=[
            pl.BlockSpec((m, d * d), lambda qi: (0, 0)),
            pl.BlockSpec((m, d), lambda qi: (0, 0)),
            pl.BlockSpec((bq, d), lambda qi: (qi, 0)),
            pl.BlockSpec((bq, d * d), lambda qi: (qi, 0)),
        ],
        out_specs=pl.BlockSpec((bq, m), lambda qi: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((q, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(a_inv.reshape(m, d * d), theta, x, xx)

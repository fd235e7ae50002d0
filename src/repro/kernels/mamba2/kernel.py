"""Chunked Mamba2 SSD kernel (Pallas TPU).

Grid = (batch, heads, n_chunks); chunk axis sequential, per-head (P, N)
state in f32 VMEM scratch.  The chunk body is the SSD block decomposition
(Dao & Gu 2024): an intra-chunk lower-triangular matmul, an inter-chunk
state read, and a rank-L state update — three MXU contractions per chunk.

Adaptation from the paper's GPU layout: instead of a warpgroup per (chunk,
head) with shared-memory staging, one grid cell owns a head's whole scan and
the state never leaves VMEM; B/C tiles (shared across heads, n_groups=1) are
re-fetched per head — they are (L, N=64..128) tiles, cheap next to x.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hout_ref,
                h_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0, :, :].astype(jnp.float32)

    xb = x_ref[0, :, 0, :].astype(jnp.float32)       # (L, P)
    dtb = dt_ref[0, :, 0].astype(jnp.float32)        # (L,)
    Bb = b_ref[0, :, :].astype(jnp.float32)          # (L, N)
    Cb = c_ref[0, :, :].astype(jnp.float32)          # (L, N)
    A = a_ref[0, 0]                                  # scalar (negative)
    h = h_ref[...]                                   # (P, N)

    dA = dtb * A                                     # (L,) log-decay ≤ 0
    cum = jnp.cumsum(dA)                             # inclusive
    # intra-chunk: M[t,s] = (C_t·B_s) · exp(cum_t − cum_s) · dt_s,  s ≤ t
    G = jax.lax.dot_general(Cb, Bb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, L)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    M = jnp.where(cols <= rows, G * decay * dtb[None, :], 0.0)
    y_intra = jax.lax.dot_general(M, xb, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # inter-chunk: y_t += exp(cum_t) · C_t · h
    Ch = jax.lax.dot_general(Cb, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, P)
    y = y_intra + Ch * jnp.exp(cum)[:, None]
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state update: h ← h·exp(cum_L) + Σ_s exp(cum_L−cum_s)·dt_s · x_s ⊗ B_s
    scale = jnp.exp(cum[-1] - cum) * dtb             # (L,)
    h = h * jnp.exp(cum[-1]) + jax.lax.dot_general(
        xb * scale[:, None], Bb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (P, N)
    h_ref[...] = h

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0, 0, :, :] = h


def ssd_fwd(x, dt, B, C, A, h0, chunk: int, interpret: bool):
    """x: (b, s, h, p); dt: (b, s, h); B, C: (b, s, n); A: (h,);
    h0: (b, h, p, n) f32."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    grid = (b, h, s // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bb, hh, ci: (bb, ci, hh, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bb, hh, ci: (bb, ci, hh)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, ci: (bb, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, ci: (bb, ci, 0)),
            pl.BlockSpec((1, 1), lambda bb, hh, ci: (hh, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bb, hh, ci: (bb, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bb, hh, ci: (bb, ci, hh, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bb, hh, ci: (bb, hh, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, B, C, A.reshape(-1, 1), h0)

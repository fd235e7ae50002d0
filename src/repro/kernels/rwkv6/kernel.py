"""Chunked RWKV6 WKV scan kernel (Pallas TPU).

Grid = (batch, heads, n_chunks); the chunk axis is sequential and the
(K, V) linear-attention state lives in f32 VMEM scratch.  Inside a chunk
the recurrence is factored into three MXU matmuls (inter-chunk, intra-chunk
lower-triangular, diagonal-bonus) using the exp(±cumsum log w)
factorization — safe at chunk length 32–64 because per-step |log w| is
bounded by the decay parameterization.

This adapts the CUDA wkv6 kernel's warp-per-head layout to the TPU: one
grid cell per (batch, head), chunk loop in-core, state never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                s_ref, *, chunk: int, kd: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0, :, :].astype(jnp.float32)

    rb = r_ref[0, :, 0, :].astype(jnp.float32)          # (L, K)
    kb = k_ref[0, :, 0, :].astype(jnp.float32)
    vb = v_ref[0, :, 0, :].astype(jnp.float32)
    wb = w_ref[0, :, 0, :].astype(jnp.float32)          # log-decay ≤ 0
    u = u_ref[0, :].astype(jnp.float32)                 # (K,)
    S = s_ref[...]                                      # (K, V=K)

    cum = jnp.cumsum(wb, axis=0)                        # inclusive Σ log w
    cum_prev = cum - wb
    r_dec = rb * jnp.exp(cum_prev)
    # inter-chunk: y_t += (r_t ⊙ exp(cum_prev_t)) @ S
    y_inter = jax.lax.dot_general(r_dec, S, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # intra-chunk (s < t): att[t,s] = Σ_k r_dec[t,k] · k[s,k]·exp(-cum[s,k])
    k_dec = kb * jnp.exp(-cum)
    att = jax.lax.dot_general(r_dec, k_dec, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (L, L)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(cols < rows, att, 0.0)
    y_intra = jax.lax.dot_general(att, vb, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # diagonal with bonus u: y_t += (Σ_k r_t·u·k_t) · v_t
    coeff = jnp.sum(rb * u[None, :] * kb, axis=-1, keepdims=True)
    y = y_inter + y_intra + coeff * vb
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state: S ← S·exp(cum_L)[:,None] + Σ_s (k_s·exp(cum_L - cum_s)) ⊗ v_s
    k_carry = kb * jnp.exp(cum[-1:, :] - cum)
    S = S * jnp.exp(cum[-1])[:, None] + jax.lax.dot_general(
        k_carry, vb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = S

    @pl.when(ci == nc - 1)
    def _finish():
        sout_ref[0, 0, :, :] = S


def wkv_fwd(r, k, v, logw, u, s0, chunk: int, interpret: bool):
    """r,k,v,logw: (B, S, H, K); u: (H, K); s0: (B, H, K, K) f32."""
    b, s, h, kd = r.shape
    grid = (b, h, s // chunk)
    seq_spec = pl.BlockSpec((1, chunk, 1, kd), lambda bb, hh, ci: (bb, ci, hh, 0))
    kernel = functools.partial(_wkv_kernel, chunk=chunk, kd=kd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, kd), lambda bb, hh, ci: (hh, 0)),
                  pl.BlockSpec((1, 1, kd, kd), lambda bb, hh, ci: (bb, hh, 0, 0))],
        out_specs=[seq_spec,
                   pl.BlockSpec((1, 1, kd, kd), lambda bb, hh, ci: (bb, hh, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(r.shape, r.dtype),
                   jax.ShapeDtypeStruct((b, h, kd, kd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((kd, kd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u, s0)

"""Flash-attention prefill kernel (Pallas TPU).

Grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
innermost sequential ("arbitrary") axis so the online-softmax running state
(acc, m, l) lives in VMEM scratch across kv iterations.  GQA is handled in
the k/v index maps (kv head = q head // group), the causal + sliding-window
mask is computed from broadcasted iotas, and ``window`` arrives as a dynamic
SMEM scalar so gemma3's per-layer local/global windows work under one
compiled kernel.

Fully-masked kv blocks are skipped with ``pl.when`` (their DMAs still run —
grid pruning with a *dynamic* window isn't expressible; noted in §Perf).

VMEM working set per grid step: q/k/v/o tiles (bq+2·bk+bq)·hd·2B plus
(bq·hd + 2·bq·128) f32 scratch — e.g. bq=bk=512, hd=128: ~1.1 MB, well
under the ~16 MB v5e VMEM budget, with MXU-aligned (≥128) matmul dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38
LANES = 128   # TPU lane width: running stats are stored (bq, LANES)


def _fa_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
               *, bq: int, bk: int, causal: bool, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    win = win_ref[0]
    q_first = qi * bq                   # first q position of this block
    k_first = ki * bk
    # block visibility: any (q, k) pair with k <= q (causal) and k > q - win
    visible = (k_first + bk - 1) > (q_first - win)
    if causal:
        visible &= k_first <= (q_first + bq - 1)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale      # (bq, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos > q_pos - win
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                   # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                          # (bq, 1)
        l_ref[...] = jnp.broadcast_to(l_prev * corr +
                                      jnp.sum(p, axis=-1, keepdims=True),
                                      l_ref.shape)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        window: jax.Array, bq: int, bk: int,
                        causal: bool, interpret: bool) -> jax.Array:
    """q: (B, Sq, Hq, hd); k, v: (B, Sk, Hk, hd); window: i32[1] (dynamic)."""
    b, sq, hq, hd = q.shape
    sk, hk = k.shape[1], k.shape[2]
    group = hq // hk
    grid = (b, hq, sq // bq, sk // bk)

    kernel = functools.partial(_fa_kernel, bq=bq, bk=bk, causal=causal,
                               scale=hd ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, 1, hd), lambda bb, h, qi, ki: (bb, qi, h, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda bb, h, qi, ki: (bb, ki, h // group, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda bb, h, qi, ki: (bb, ki, h // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd),
                               lambda bb, h, qi, ki: (bb, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(window, q, k, v)

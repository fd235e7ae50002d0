"""Flash-decode kernel (Pallas TPU): one query token vs. a long KV cache.

Grid = (batch, kv_heads, kv_blocks); the kv-block axis is sequential so the
online-softmax state (acc (G, hd), m, l) lives in VMEM scratch.  All ``G``
query heads of a kv head are processed together — the (G, bk) score matrix
keeps the MXU busy even at decode (G=6 for grok's 48q/8kv).

``cache_len`` and ``window`` are dynamic SMEM scalars; kv blocks entirely
outside [cache_len - window, cache_len) are skipped via ``pl.when`` — for a
32k cache at cache_len=1k, 31/32 blocks do no compute.

VMEM per step: 2·bk·hd·2B (k+v tiles) + G·hd·4B ≈ 0.5 MB at bk=1024, hd=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38
LANES = 128


def _fd_kernel(scalars_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
               *, bk: int, group: int, scale: float):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    cache_len = scalars_ref[0]
    window = scalars_ref[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_first = ki * bk
    lo = jnp.maximum(cache_len - window, 0)
    visible = (k_first < cache_len) & (k_first + bk > lo)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale      # (G, hd)
        k = k_ref[0, :, 0, :]                                  # (bk, hd)
        v = v_ref[0, :, 0, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bk)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (group, bk), 1)
        mask = (k_pos < cache_len) & (k_pos >= cache_len - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        pv = jax.lax.dot_general(p, v.astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


def flash_decode_fwd(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     scalars: jax.Array, bk: int,
                     interpret: bool) -> jax.Array:
    """q: (B, 1, Hq, hd) reshaped to (B, Hk, G, hd) outside; caches
    (B, S, Hk, hd); scalars = [cache_len, window] i32."""
    b, _, hq, hd = q.shape
    s, hk = k_cache.shape[1], k_cache.shape[2]
    group = hq // hk
    q4 = q.reshape(b, hk, group, hd)
    grid = (b, hk, s // bk)

    kernel = functools.partial(_fd_kernel, bk=bk, group=group,
                               scale=hd ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, group, hd), lambda bb, h, ki: (bb, h, 0, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda bb, h, ki: (bb, ki, h, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda bb, h, ki: (bb, ki, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda bb, h, ki: (bb, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, hd), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, q4, k_cache, v_cache)
    return out.reshape(b, 1, hq, hd)

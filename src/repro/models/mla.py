"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) over one
latent cache row per token.

Each token's keys and values are kept as one row of ``kv_lora_rank +
qk_rope_head_dim`` numbers: the RMS-normed latent ``c_kv`` and the rope key
``k_pe`` shared by every head.  Attention runs in the absorbed form: each
head's no-rope query is folded through its key up-projection into latent
space, so a score is ``q_lat . c_kv + q_pe . k_pe``; the softmax-weighted
sum is taken over ``c_kv`` and mapped out through the value
up-projection.  Neither per-head keys nor values are ever formed, and the
value is read from the first ``kv_lora_rank`` columns of the same row.

Rope is YaRN (``yarn_*`` in ``ModelConfig``): inverse frequencies blended
between the interpolated and the extrapolated ones by a linear ramp over
the rotary dimensions, and the softmax scale multiplied by the square of
YaRN's ``mscale``.  The rotation is on the two halves of the rope part of
each head; the published code first reorders interleaved pairs into
halves, a fixed permutation of the projection's columns.
"""
from __future__ import annotations

import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import NEG_INF
from repro.models.config import ModelConfig
from repro.models.layers import Params, rms_norm, sds

ShardFn = Callable[[jax.Array, str], jax.Array]
_id_shard: ShardFn = lambda x, name: x


def mla_shapes(cfg: ModelConfig) -> Params:
    dt = cfg.param_dtype
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"wq": sds((d, h, nope + rope), dt),
            "wkv_a": sds((d, r + rope), dt),
            "norm_kv": sds((r,), dt),
            "wkv_b": sds((r, h, nope + v), dt),
            "wo": sds((h, v, d), dt)}


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """(qk_rope_head_dim / 2,) inverse frequencies."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.yarn_factor <= 1:
        return extra.astype(np.float32)

    def corr_dim(rotations):
        return (dim * math.log(cfg.yarn_original_len
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inter = extra / cfg.yarn_factor
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_scale(cfg: ModelConfig) -> float:
    """YaRN's factor on the rotated parts (1 where both mscales agree)."""
    return (_yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_mscale_all_dim:
        scale *= _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def apply_rope(x: jax.Array, positions: jax.Array,
               cfg: ModelConfig) -> jax.Array:
    """x (B, C, [H,] rope): rotate the two halves by position."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_inv_freq(cfg))                                   # (B, C, rope/2)
    if x.ndim == 4:
        ang = ang[:, :, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return (out * rope_scale(cfg)).astype(x.dtype)


# ---------------------------------------------------------------------------
# The absorbed form
# ---------------------------------------------------------------------------


def project(params: Params, x: jax.Array, positions: jax.Array,
            cfg: ModelConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, C, d) -> (q_nope (B, C, H, nope), q_pe (B, C, H, rope) roped,
    latent rows (B, C, r + rope): normed ``c_kv`` then roped ``k_pe``)."""
    dt = cfg.jnp_dtype()
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("bcd,dhk->bchk", x, params["wq"].astype(dt))
    kv = jnp.einsum("bcd,dk->bck", x, params["wkv_a"].astype(dt))
    c_kv = rms_norm(kv[..., :r], params["norm_kv"], cfg.norm_eps)
    k_pe = apply_rope(kv[..., r:], positions, cfg)
    q_pe = apply_rope(q[..., nope:], positions, cfg)
    return q[..., :nope], q_pe, jnp.concatenate([c_kv, k_pe], -1)


def attend(params: Params, q_nope: jax.Array, q_pe: jax.Array,
           rows: jax.Array, positions: jax.Array,
           cfg: ModelConfig) -> jax.Array:
    """Queries at ``positions`` (B, C) over latent ``rows`` (B, S, r + rope);
    key s is visible to a query at p iff s <= p.  Returns (B, C, d).

    Scores and the softmax are float32; the rows stay in their storage
    dtype (the contractions accumulate in float32)."""
    dt = cfg.jnp_dtype()
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    w_kv = params["wkv_b"].astype(dt)
    q_lat = jnp.einsum("bchn,rhn->bchr", q_nope, w_kv[..., :nope])
    q = jnp.concatenate([q_lat, q_pe.astype(dt)], -1)       # (B, C, H, r+rope)
    scores = jnp.einsum("bchk,bsk->bhcs", q, rows,
                        preferred_element_type=jnp.float32)
    scores = scores * softmax_scale(cfg)
    pos = jnp.arange(rows.shape[1], dtype=jnp.int32)
    valid = pos[None, None] <= positions[:, :, None]          # (B, C, S)
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhcs,bsr->bchr", p, rows[..., :r],
                       preferred_element_type=jnp.float32)
    o = jnp.einsum("bchr,rhv->bchv", o_lat.astype(dt), w_kv[..., nope:])
    return jnp.einsum("bchv,hvd->bcd", o, params["wo"].astype(dt))


def attention_chunk(params: Params, x: jax.Array, cache: jax.Array,
                    lengths: jax.Array, active: jax.Array, cfg: ModelConfig,
                    shard: ShardFn = _id_shard
                    ) -> Tuple[jax.Array, jax.Array]:
    """One layer over a C-token slab at per-slot offsets ``lengths`` (B,):
    the slab's latent rows are written into ``cache`` (B, S, r + rope)
    where ``active`` (B, C) is set, then every query attends through the
    cache (write, then read, so a chunk sees its own earlier tokens).  A
    one-token decode is the slab of one.  Returns (out (B, C, d), cache)."""
    with jax.named_scope("mla.attend"):
        c = x.shape[1]
        positions = lengths[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        q_nope, q_pe, rows = project(params, x, positions, cfg)
        onehot = ((jnp.arange(cache.shape[1], dtype=jnp.int32)[None, None]
                   == positions[:, :, None]) & active[:, :, None])  # (B, C, S)
        scat = jnp.einsum("bcs,bck->bsk", onehot.astype(jnp.float32),
                          rows.astype(jnp.float32))
        cache = jnp.where(onehot.any(axis=1)[:, :, None],
                          scat.astype(cache.dtype), cache)
        out = attend(params, q_nope, q_pe, cache, positions, cfg)
    return shard(out, "act_btd"), cache


def attention_full(params: Params, x: jax.Array, cfg: ModelConfig,
                   shard: ShardFn = _id_shard) -> jax.Array:
    """Causal attention over a whole sequence x (B, S, d) from position 0."""
    with jax.named_scope("mla.attend"):
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        q_nope, q_pe, rows = project(params, x, positions, cfg)
        out = attend(params, q_nope, q_pe, rows, positions, cfg)
    return shard(out, "act_btd")

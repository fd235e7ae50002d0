"""GQA attention: chunked flash-reference prefill, chunked serving prefill
against a decode cache, and single-token decode.

One code path serves full, sliding-window, and local:global attention — the
per-layer ``window`` scalar parameterizes the mask (window == seq_len ⇒ full
causal attention), which is what lets heterogeneous stacks (gemma3 5:1
local:global) run under a single ``lax.scan`` over stacked layer params.

The prefill path is a *flash-structured reference*: query blocks × streamed
KV blocks with online softmax, so activation memory stays O(block²) instead
of O(seq²) — this is both the memory-safe jnp path used by the dry-run and
the numerical oracle for the Pallas kernel in ``kernels/flash_attention``.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import Params, apply_rope, sds

ShardFn = Callable[[jax.Array, str], jax.Array]
_id_shard: ShardFn = lambda x, name: x

NEG_INF = -2.3819763e38  # most-negative bf16-representable; avoids nan softmax


def attn_shapes(cfg: ModelConfig, d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    dt = cfg.param_dtype
    hq = cfg.compute_heads   # llava: 56 padded to 64 for the 16-wide TP axis
    return {
        "wq": sds((d, hq, cfg.head_dim), dt),
        "wk": sds((d, cfg.n_kv_heads, cfg.head_dim), dt),
        "wv": sds((d, cfg.n_kv_heads, cfg.head_dim), dt),
        "wo": sds((hq, cfg.head_dim, d), dt),
    }


def project_qkv(params: Params, x: jax.Array, positions: jax.Array,
                cfg: ModelConfig, rope: bool = True):
    dt = cfg.jnp_dtype()
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Prefill: chunked flash-reference with online softmax.
# ---------------------------------------------------------------------------


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, window,
                  chunk: int, causal: bool = True,
                  q_offset: int = 0) -> jax.Array:
    """q: (B, Sq, Hq, hd); k,v: (B, Sk, Hk, hd); window: scalar (traced ok).

    Returns (B, Sq, Hq, hd).  Blocks over both Sq and Sk; online softmax in
    fp32.  ``window`` counts how many past positions (incl. self) are visible.
    """
    b, sq, hq, hd = q.shape
    sk, hk = k.shape[1], k.shape[2]
    group = hq // hk
    scale = hd ** -0.5
    n_q = max(sq // chunk, 1)
    if sq % n_q:
        n_q = 1
    q_chunk = sq // n_q
    n_k = max(sk // chunk, 1)
    if sk % n_k:
        n_k = 1
    k_chunk = sk // n_k

    # inputs stay in storage dtype; contractions accumulate fp32 (MXU-native)
    qb = q.reshape(b, n_q, q_chunk, hk, group, hd)
    kb = k.reshape(b, n_k, k_chunk, hk, hd)
    vb = v.reshape(b, n_k, k_chunk, hk, hd)
    win = jnp.asarray(window, jnp.int32)

    def q_block(qi, q_tile):
        # q_tile: (b, q_chunk, hk, group, hd)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inputs):
            acc, m, l = carry
            ki, k_tile, v_tile = inputs
            k_pos = ki * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_tile, k_tile,
                           preferred_element_type=jnp.float32) * scale
            mask = k_pos[None, :] <= q_pos[:, None] if causal else jnp.ones(
                (q_chunk, k_chunk), bool)
            mask &= k_pos[None, :] > q_pos[:, None] - win
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, v_tile)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, hk, group, q_chunk, hd), jnp.float32)
        m0 = jnp.full((b, hk, group, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hk, group, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.arange(n_k), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.transpose(out, (0, 3, 1, 2, 4))  # (b, q_chunk, hk, group, hd)

    out = jax.lax.map(lambda args: q_block(*args),
                      (jnp.arange(n_q), jnp.moveaxis(qb, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode: one new token against a full KV cache.
# ---------------------------------------------------------------------------


def lengths_vec(cache_len, b: int) -> jax.Array:
    """Cache lengths as (B,): scalar lengths broadcast (dry-run serve_step),
    per-slot vectors pass through (continuous-batching engine)."""
    cl = jnp.asarray(cache_len, jnp.int32)
    if cl.ndim == 0:
        cl = jnp.broadcast_to(cl, (b,))
    return cl


def decode_attend(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  window, cache_len) -> jax.Array:
    """q: (B, 1, Hq, hd); caches: (B, S, Hk, hd).  ``cache_len`` is a scalar
    or per-slot (B,) vector; slot b attends to positions
    [cache_len_b - window, cache_len_b).

    K/V stay in their storage dtype — the contractions accumulate in fp32
    via ``preferred_element_type`` instead of materializing fp32 copies of
    the cache (an eager ``.astype`` here cost ~0.35 GB/chip/layer of temp
    and doubled decode HBM traffic)."""
    b, _, hq, hd = q.shape
    s, hk = k_cache.shape[1], k_cache.shape[2]
    group = hq // hk
    scale = hd ** -0.5
    q4 = q.reshape(b, hk, group, hd)
    scores = jnp.einsum("bhgd,bshd->bhgs", q4, k_cache,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(s)
    cl = lengths_vec(cache_len, b)[:, None]             # (B, 1)
    # slot b's querying token sits at position cl_b - 1; a window of w
    # covers positions [cl_b - w, cl_b)
    valid = (pos[None] < cl) & (pos[None] >= cl - window)   # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def decode_attend_ring(q: jax.Array, k_ring: jax.Array, v_ring: jax.Array,
                       lengths: jax.Array) -> jax.Array:
    """Ring-buffer decode attention: the ring *is* the window, so the only
    mask is warm-up validity (entry i live iff i < min(len+1, W)); softmax
    is permutation-invariant so ring order needs no unscrambling (RoPE was
    applied at write time)."""
    b, _, hq, hd = q.shape
    w, hk = k_ring.shape[1], k_ring.shape[2]
    group = hq // hk
    scale = hd ** -0.5
    q4 = q.reshape(b, hk, group, hd)
    scores = jnp.einsum("bhgd,bshd->bhgs", q4, k_ring,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(w)[None] < jnp.minimum(lengths + 1, w)[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_ring,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def attention_decode_ring(params: Params, x: jax.Array, k_ring: jax.Array,
                          v_ring: jax.Array, cache_len, cfg: ModelConfig,
                          shard: ShardFn = _id_shard):
    """Sliding-window decode against a ring buffer of size W — the memory
    layout that makes gemma3's 52 local layers hold 1,024 KV entries instead
    of 32k (uniform caches put gemma3-27b decode_32k at 35 GiB/chip; rings
    bring it under 10)."""
    dt = cfg.jnp_dtype()
    lengths = lengths_vec(cache_len, x.shape[0])
    positions = lengths[:, None]
    q, k_new, v_new = project_qkv(params, x, positions, cfg)
    w = k_ring.shape[1]
    slot = lengths % w
    sel = (jnp.arange(w)[None] == slot[:, None])[:, :, None, None]
    k_ring = jnp.where(sel, k_new.astype(k_ring.dtype), k_ring)
    v_ring = jnp.where(sel, v_new.astype(v_ring.dtype), v_ring)
    out = decode_attend_ring(q, k_ring, v_ring, lengths)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return shard(out, "act_btd"), (k_ring, v_ring)


# ---------------------------------------------------------------------------
# Chunked prefill against a decode cache (serving).
# ---------------------------------------------------------------------------


def chunk_attend(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 window, positions: jax.Array) -> jax.Array:
    """Multi-query generalization of ``decode_attend``: a slab of C new
    tokens attends into a full-depth cache.

    q: (B, C, Hq, hd); caches: (B, S, Hk, hd); positions: (B, C) absolute
    position of each query token (tokens), so slot b's query c attends to
    cache positions (positions[b,c] - window, positions[b,c]] — exactly the
    visibility ``decode_attend`` gives a lone token at cache length
    positions[b,c].  Within a chunk, earlier chunk tokens are visible to
    later ones because their K/V were scattered into the cache *before*
    this attend (see ``attention_prefill_chunk``).

    Scores accumulate fp32; K/V stay in storage dtype (same rationale as
    ``decode_attend``).  A row whose mask is empty (inactive padding slot)
    degrades to a uniform softmax over NEG_INF scores — finite garbage the
    caller discards, never NaN.
    """
    b, c, hq, hd = q.shape
    s, hk = k_cache.shape[1], k_cache.shape[2]
    group = hq // hk
    scale = hd ** -0.5
    q5 = q.reshape(b, c, hk, group, hd)
    scores = jnp.einsum("bchgd,bshd->bhgcs", q5, k_cache,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(s, dtype=jnp.int32)
    win = jnp.asarray(window, jnp.int32)
    valid = ((pos[None, None] <= positions[:, :, None])
             & (pos[None, None] > positions[:, :, None] - win))   # (B, C, S)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgcs,bshd->bchgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, hq, hd).astype(q.dtype)


def attention_prefill_chunk(params: Params, x: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, window, lengths: jax.Array,
                            active: jax.Array, cfg: ModelConfig,
                            shard: ShardFn = _id_shard, rope: bool = True,
                            cross: bool = False):
    """One attention layer over a C-token prompt slab at per-slot offsets.

    x: (B, C, d) slab activations; ``lengths``: (B,) tokens already in the
    cache per slot (the slab lands at positions lengths..lengths+C-1);
    ``active``: (B, C) bool — position c is a real token iff
    c < n_active[b].  Inactive (padding) positions write nothing into the
    cache and their outputs are discarded by the caller.

    Self-attention (``cross=False``) scatters the slab's K/V into the cache
    at per-slot offsets first (a one-hot einsum — the chunk counterpart of
    the masked-``where`` append in ``attention_decode``, equally gather-free
    under SPMD), then attends write-then-read so intra-chunk causality comes
    from the position mask alone.  Cross-attention reads the static
    encoder-side cache and writes nothing.

    Returns (out (B, C, d), (k_cache, v_cache)).
    """
    dt = cfg.jnp_dtype()
    b, c, _ = x.shape
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = lengths[:, None] + offs[None, :]            # (B, C)
    q, k_new, v_new = project_qkv(params, x, positions, cfg, rope=rope)
    if cross:
        n_f = k_cache.shape[1]
        # every encoder position visible to every query token
        pos_all = jnp.broadcast_to(jnp.int32(n_f - 1), positions.shape)
        out = chunk_attend(q, k_cache, v_cache, jnp.int32(n_f), pos_all)
    else:
        s = k_cache.shape[1]
        onehot = ((jnp.arange(s, dtype=jnp.int32)[None, None]
                   == positions[:, :, None])
                  & active[:, :, None])                      # (B, C, S)
        w = onehot.astype(jnp.float32)
        k_scat = jnp.einsum("bcs,bchd->bshd", w, k_new.astype(jnp.float32))
        v_scat = jnp.einsum("bcs,bchd->bshd", w, v_new.astype(jnp.float32))
        touched = onehot.any(axis=1)[:, :, None, None]       # (B, S, 1, 1)
        k_cache = jnp.where(touched, k_scat.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(touched, v_scat.astype(v_cache.dtype), v_cache)
        out = chunk_attend(q, k_cache, v_cache, window, positions)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return shard(out, "act_btd"), (k_cache, v_cache)


def splice_rows(cache: jax.Array, slot: int, block: jax.Array) -> jax.Array:
    """Write a reused prompt-prefix block into one slot's cache rows.

    cache: (L, B, S, ...) stacked per-layer cache (k, v, or a latent);
    block: (L, P, ...) host-captured rows for prompt positions [0, P).  The
    admission-time counterpart of the chunk path's scatter-at-offset write
    (``attention_prefill_chunk``): the block lands at positions 0..P-1 of
    slot ``slot`` and every other slot's rows are untouched, so a splice
    mid-serving never perturbs neighbours.  Runs eagerly on the host path
    (slot admission), where ``slot``/``P`` are Python ints — no jit
    retrace pressure.
    """
    block = jnp.asarray(block).astype(cache.dtype)
    return cache.at[:, slot, :block.shape[1]].set(block)


# ---------------------------------------------------------------------------
# Full attention block (projections + attend + output)
# ---------------------------------------------------------------------------


def attention_prefill(params: Params, x: jax.Array, positions: jax.Array,
                      window, cfg: ModelConfig, shard: ShardFn = _id_shard,
                      rope: bool = True,
                      kv_override: Optional[Tuple[jax.Array, jax.Array]] = None):
    """Returns (out (B,S,d), (k, v)) — k/v returned for cache population."""
    dt = cfg.jnp_dtype()
    q, k, v = project_qkv(params, x, positions, cfg, rope=rope)
    if kv_override is not None:
        k, v = kv_override  # cross-attention: encoder-provided KV
    q = shard(q, "act_bshd")
    k = shard(k, "act_bskd")
    v = shard(v, "act_bskd")
    if cfg.use_pallas:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, window=window, chunk=cfg.attn_chunk,
                                     causal=kv_override is None)
    else:
        out = flash_prefill(q, k, v, window, cfg.attn_chunk,
                            causal=kv_override is None)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return shard(out, "act_btd"), (k, v)


def attention_decode(params: Params, x: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, window, cache_len,
                     cfg: ModelConfig, shard: ShardFn = _id_shard,
                     rope: bool = True, cross: bool = False):
    """x: (B, 1, d). Returns (out, (k_cache, v_cache)) with the new KV
    appended at ``cache_len`` (self-attention) or caches untouched (cross)."""
    dt = cfg.jnp_dtype()
    lengths = lengths_vec(cache_len, x.shape[0])        # (B,)
    positions = lengths[:, None]
    q, k_new, v_new = project_qkv(params, x, positions, cfg, rope=rope)
    if not cross:
        if cfg.kv_update == "where" or jnp.ndim(cache_len) > 0:
            # masked elementwise append: the only gather-free update when the
            # cache sequence dim is sharded (kv_heads don't divide the model
            # axis), and the only form that supports per-slot lengths
            # (continuous batching) — dynamic_update_slice on a sharded dim
            # makes SPMD materialize the full unsharded cache.
            sel = (jnp.arange(k_cache.shape[1])[None] ==
                   lengths[:, None])[:, :, None, None]
            k_cache = jnp.where(sel, k_new.astype(k_cache.dtype), k_cache)
            v_cache = jnp.where(sel, v_new.astype(v_cache.dtype), v_cache)
        else:
            # in-place append (cache S-dim local on every device)
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k_new.astype(k_cache.dtype), cache_len, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v_new.astype(v_cache.dtype), cache_len, axis=1)
        attend_len = lengths + 1
    else:
        attend_len = k_cache.shape[1]
    if (cfg.use_pallas and k_cache.shape[1] >= 2048
            and jnp.ndim(cache_len) == 0 and not cross):
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(q, k_cache, v_cache, window=window,
                                      cache_len=cache_len + 1)
    else:
        out = decode_attend(q, k_cache, v_cache, window, attend_len)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return shard(out, "act_btd"), (k_cache, v_cache)

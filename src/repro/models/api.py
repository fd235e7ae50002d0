"""Unified model API over every architecture family.

One surface for the training loop, the serving engine, and the dry-run:

    param_shapes(cfg)                     ShapeDtypeStruct param tree
    init_params(cfg, key)                 materialized params
    forward(params, batch, cfg, shard)    full-sequence logits
    loss_fn(params, batch, cfg, shard)    chunked-CE loss (+ MoE aux)
    prefill(params, batch, cfg, shard)    last-position logits + (no cache)
    cache_shapes / init_cache             decode cache pytrees
    serve_step(params, token, cache, cfg) one-token decode
    prefill_chunk(params, toks, cache, …) C-token prompt slab into the cache
    reset_slot(cache, slot)               zero one slot before reuse
    splice_prefix(cache, slot, k, v)      reused prompt-prefix KV (or latent
                                          rows, v unused) into a slot
    supports_chunked_prefill(cfg)         which layouts take the chunked path

``batch`` is a dict: tokens/labels (+ frames for enc-dec audio,
frontend_embeddings for vlm).  Dispatch on ``cfg.layout``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, lm
from repro.models.config import ModelConfig
from repro.models.layers import Params, rms_norm, unembed
from repro.models.lm import ForwardOut, ShardFn, _id_shard

Batch = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> Params:
    if cfg.layout == "encdec":
        return encdec.encdec_shapes(cfg)
    return lm.lm_shapes(cfg)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    if cfg.layout == "encdec":
        return encdec.init_encdec(cfg, key)
    return lm.init_lm(cfg, key)


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


def forward(params: Params, batch: Batch, cfg: ModelConfig,
            shard: ShardFn = _id_shard) -> ForwardOut:
    if cfg.layout == "encdec":
        return encdec.forward(params, batch["frames"], batch["tokens"], cfg,
                              shard)
    return lm.forward(params, batch["tokens"], cfg, shard,
                      frontend_embeddings=batch.get("frontend_embeddings"))


def chunked_cross_entropy(x: jax.Array, params: Params, labels: jax.Array,
                          cfg: ModelConfig, chunk: int = 512) -> jax.Array:
    """Cross-entropy from *final hidden states* with sequence chunking.

    Materializing (B, S, V) fp32 logits for a 262k vocab at 4k×256 is ~4 TB;
    scanning over S-chunks caps the live logits at (B, chunk, V_shard).
    x: (B, S, d) final normed hiddens; labels: (B, S) targets.
    """
    b, s, _ = x.shape
    n = max(s // chunk, 1)
    if s % n:
        n = 1
    c = s // n
    xc = jnp.moveaxis(x.reshape(b, n, c, -1), 1, 0)       # (n, B, c, d)
    yc = jnp.moveaxis(labels.reshape(b, n, c), 1, 0)      # (n, B, c)

    def step(tot, xs):
        xb, yb = xs
        logits = unembed(params["tok"], xb, cfg.jnp_dtype()).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (xc, yc))
    return total / (b * s)


def loss_fn(params: Params, batch: Batch, cfg: ModelConfig,
            shard: ShardFn = _id_shard, aux_weight: float = 0.01,
            loss_chunk: int = 512) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE (labels aligned with tokens) + MoE aux loss."""
    labels = batch["labels"]
    if cfg.layout == "encdec":
        hidden, aux = encdec.forward_hidden(params, batch["frames"],
                                            batch["tokens"], cfg, shard)
    else:
        hidden, aux = lm.forward_hidden(
            params, batch["tokens"], cfg, shard,
            frontend_embeddings=batch.get("frontend_embeddings"))
    st = labels.shape[1]
    hidden_text = hidden[:, -st:]              # drop frontend positions
    ce = chunked_cross_entropy(hidden_text, params, labels, cfg,
                               chunk=loss_chunk)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(params: Params, batch: Batch, cfg: ModelConfig,
            shard: ShardFn = _id_shard) -> jax.Array:
    """Returns next-token logits for the *last* position only (B, V) —
    serving never materializes the full (B, S, V) logits tensor.

    This one-shot form recomputes from scratch and fills no cache; the
    serving engine uses ``prefill_chunk`` below, which populates the
    decode cache slab-by-slab at per-slot offsets."""
    if cfg.layout == "encdec":
        hidden, _ = encdec.forward_hidden(params, batch["frames"],
                                          batch["tokens"], cfg, shard)
    else:
        hidden, _ = lm.forward_hidden(
            params, batch["tokens"], cfg, shard,
            frontend_embeddings=batch.get("frontend_embeddings"))
    return unembed(params["tok"], hidden[:, -1:], cfg.jnp_dtype())[:, 0]


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    if cfg.layout == "encdec":
        return encdec.cache_shapes(cfg, batch, max_len)
    return lm.cache_shapes(cfg, batch, max_len)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    if cfg.layout == "encdec":
        return encdec.init_cache(cfg, batch, max_len)
    return lm.init_cache(cfg, batch, max_len)


def serve_step(params: Params, token: jax.Array, cache: Params,
               cfg: ModelConfig, shard: ShardFn = _id_shard
               ) -> Tuple[jax.Array, Params]:
    """One new token against a seq_len-deep cache: (logits (B,1,V), cache)."""
    if cfg.layout == "encdec":
        return encdec.decode_step(params, token, cache, cfg, shard)
    return lm.decode_step(params, token, cache, cfg, shard)


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Whether ``prefill_chunk`` exists for this architecture family.

    True for the attention-cached layouts (dense / moe / mla_moe / encdec)
    whose decode cache is a full-depth positional store (k and v, or
    mla_moe's one latent row per token under ``k``) — a prompt slab can be
    scattered in at arbitrary per-slot offsets.  Recurrent layouts
    (rwkv, mamba_hybrid) carry per-token state whose batched chunking
    needs per-token activity gating inside the scan, and ring-buffer
    (windowed) caches would overwrite in-window keys mid-chunk; both keep
    the one-token path (the engine additionally checks the materialized
    cache for a ``k`` entry to exclude ring layouts).
    """
    return cfg.layout in ("dense", "moe", "mla_moe", "encdec")


def reset_slot(cache: Params, slot) -> Params:
    """Zero one decode slot: its length and its row of every state leaf.

    Every cache leaf is laid out ``(layers, batch, ...)`` except
    ``length`` ``(batch,)``.  Positional KV beyond a slot's length is
    masked anyway, but recurrent state (rwkv ``wkv``/shifts, mamba
    ``conv``/``ssm``) is read unconditionally — a reused slot must not
    inherit the previous request's state."""
    return {name: (leaf.at[slot].set(0) if name == "length"
                   else leaf.at[:, slot].set(0))
            for name, leaf in cache.items()}


def splice_prefix(cache: Params, slot: int, k_block, v_block) -> Params:
    """Splice a reused prompt-prefix KV block into one decode slot.

    ``k_block``/``v_block``: (L, P, Hk, hd) KV captured from a completed
    prompt whose first P tokens match this slot's prompt.  Valid exactly
    where chunked prefill is (full-depth positional ``k``/``v`` caches —
    dense/moe/encdec; the enc-dec splice covers the decoder self-KV only,
    cross-KV is per-request).  A latent cache (mla_moe) has ``k`` alone,
    (L, P, r + rope) rows, and ``v_block`` is not read.  The slot's length
    is set to P, so the engine's next chunk step continues prefill at
    offset P — the cached prefix is never recomputed.
    """
    if "k" not in cache:
        raise ValueError(
            f"prefix splice needs a full-depth positional KV cache; got "
            f"cache keys {sorted(cache)} (ring-buffer and recurrent "
            f"layouts recompute their prompts)")
    from repro.models import attention as attn
    out = dict(cache, length=cache["length"].at[slot].set(k_block.shape[1]))
    for name, block in (("k", k_block), ("v", v_block)):
        if name in cache:
            out[name] = attn.splice_rows(cache[name], slot, block)
    return out


def prefill_chunk(params: Params, tokens: jax.Array, cache: Params,
                  cfg: ModelConfig, n_active: jax.Array,
                  shard: ShardFn = _id_shard) -> Tuple[jax.Array, Params]:
    """Populate the decode cache with a (B, C) slab of prompt tokens at
    per-slot offsets ``cache["length"]``; ``n_active`` (B,) gates how many
    of the C positions are real per slot (0 = idle slot this step).

    Returns (logits (B, C, V), new cache).  The logits at position
    n_active[b]-1 are the next-token logits slot b would have produced by
    feeding the same tokens one at a time through ``serve_step`` — the
    chunked/one-shot equivalence asserted by tests/test_prefill_chunk.py.
    """
    if cfg.layout == "encdec":
        return encdec.prefill_chunk_step(params, tokens, cache, cfg,
                                         n_active, shard)
    return lm.prefill_chunk_step(params, tokens, cache, cfg, n_active, shard)

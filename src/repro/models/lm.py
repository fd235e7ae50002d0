"""Decoder-only LM assembly: dense / MoE / MLA+MoE / RWKV6 / Mamba2-hybrid
layouts.

All homogeneous layer stacks run under a single ``lax.scan`` over stacked
parameters (compile-time hygiene: one traced layer body regardless of depth —
grok's 64 MoE layers and zamba2's 81 hybrid layers compile in seconds).
Heterogeneous attention patterns (gemma3 5:1 local:global) are expressed as a
per-layer ``window`` vector consumed inside the scan, and zamba2's shared
attention block fires every ``attn_every`` layers via ``lax.cond`` with
weights closed over (shared = same params every application, per the paper's
description of Zamba2).  The MLA+MoE layout (DeepSeek-V2) stacks its
attention over every layer and its feed-forward halves apart, the leading
dense MLPs (``layers/mlp``) and the expert layers (``layers/moe``), and
runs all of them in one scan with the latent cache in its carry.

Public surface:
    lm_shapes(cfg)                          parameter ShapeDtypeStruct tree
    init_lm(cfg, key)                       materialized params
    forward(params, tokens, cfg, ...)       logits (+ aux loss), full-sequence
    init_cache / cache_shapes               decode cache pytrees
    decode_step(params, token, cache, cfg)  one-token serve step
    prefill_chunk_step(params, toks, ...)   C-token prompt slab into the cache
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mla
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig
from repro.models.layers import (Params, embed, embed_shapes, materialize,
                                 mlp, mlp_shapes, rms_norm, rms_norm_shapes,
                                 sds, unembed)

ShardFn = Callable[[jax.Array, str], jax.Array]
_id_shard: ShardFn = lambda x, name: x


def _stack(tree: Params, n: int) -> Params:
    return jax.tree.map(lambda s: sds((n,) + tuple(s.shape), s.dtype), tree)


# ---------------------------------------------------------------------------
# Parameter shapes
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: ModelConfig) -> Params:
    dt = cfg.param_dtype
    if cfg.layout == "dense":
        return {"norm_attn": rms_norm_shapes(cfg.d_model, dt),
                "attn": attn.attn_shapes(cfg),
                "norm_mlp": rms_norm_shapes(cfg.d_model, dt),
                "mlp": mlp_shapes(cfg.d_model, cfg.d_ff, dt)}
    if cfg.layout == "moe":
        return {"norm_attn": rms_norm_shapes(cfg.d_model, dt),
                "attn": attn.attn_shapes(cfg),
                "norm_mlp": rms_norm_shapes(cfg.d_model, dt),
                "moe": moe_lib.moe_shapes(cfg)}
    if cfg.layout == "mla_moe":
        return {"norm_attn": rms_norm_shapes(cfg.d_model, dt),
                "attn": mla.mla_shapes(cfg),
                "norm_mlp": rms_norm_shapes(cfg.d_model, dt)}
    if cfg.layout == "rwkv":
        return {"ln1": rms_norm_shapes(cfg.d_model, dt),
                "ln2": rms_norm_shapes(cfg.d_model, dt),
                "rwkv": rwkv_lib.rwkv_shapes(cfg)}
    if cfg.layout == "mamba_hybrid":
        return {"norm": rms_norm_shapes(cfg.d_model, dt),
                "mamba": ssm_lib.mamba_shapes(cfg)}
    raise ValueError(cfg.layout)


def lm_shapes(cfg: ModelConfig) -> Params:
    dt = cfg.param_dtype
    shapes: Params = {
        "tok": embed_shapes(cfg.vocab_size, cfg.d_model, dt, cfg.tie_embeddings),
        "norm_f": rms_norm_shapes(cfg.d_model, dt),
        "layers": _stack(_layer_shapes(cfg), cfg.n_layers),
    }
    if cfg.layout == "mla_moe":
        n_dense = cfg.first_k_dense
        if n_dense:
            shapes["layers"]["mlp"] = _stack(
                mlp_shapes(cfg.d_model, cfg.d_ff, dt), n_dense)
        shapes["layers"]["moe"] = _stack(moe_lib.moe_shapes(cfg),
                                         cfg.n_layers - n_dense)
    if cfg.layout == "mamba_hybrid":
        shapes["shared_attn"] = {
            "norm_attn": rms_norm_shapes(cfg.d_model, dt),
            "attn": attn.attn_shapes(cfg),
            "norm_mlp": rms_norm_shapes(cfg.d_model, dt),
            "mlp": mlp_shapes(cfg.d_model, cfg.d_ff, dt)}
    return shapes


def init_lm(cfg: ModelConfig, key: jax.Array) -> Params:
    return materialize(key, lm_shapes(cfg))


# ---------------------------------------------------------------------------
# Layer bodies (shared between prefill scan and decode scan)
# ---------------------------------------------------------------------------


def _dense_block(layer: Params, x, positions, window, cfg, shard):
    h, _ = attn.attention_prefill(layer["attn"], rms_norm(x, layer["norm_attn"],
                                                          cfg.norm_eps),
                                  positions, window, cfg, shard)
    x = x + h
    x = x + mlp(layer["mlp"], rms_norm(x, layer["norm_mlp"], cfg.norm_eps),
                cfg.jnp_dtype())
    return shard(x, "act_btd")


def _moe_block(layer: Params, x, positions, window, cfg, shard):
    h, _ = attn.attention_prefill(layer["attn"], rms_norm(x, layer["norm_attn"],
                                                          cfg.norm_eps),
                                  positions, window, cfg, shard)
    x = x + h
    m, aux = moe_lib.moe_block(layer["moe"],
                               rms_norm(x, layer["norm_mlp"], cfg.norm_eps),
                               cfg, use_pallas=cfg.use_pallas)
    return shard(x + m, "act_btd"), aux


def _mla_layers(params: Params, x, cache, attend, cfg: ModelConfig,
                shard: ShardFn, remat: bool = False):
    """Every MLA+MoE layer in one scan.  ``attend(attn params, normed x,
    layer index, cache) -> (out, cache)`` is the attention half; the
    feed-forward half is layer i's dense MLP for i < first_k_dense, its
    held experts after.  ``cache`` rides the scan's carry.

    One scan, not one per stack: a loop of one dense layer is unrolled,
    and its attention then asks for a layout of the whole cache other than
    the loop's, a copy of the cache in and out of every tick.  The expert
    half runs on the dense layers too (on the first expert layer's weights)
    and the ``lax.cond`` keeps the dense MLP's result there: an expert
    half inside a ``cond`` branch makes the compiler lay the whole expert
    stacks out anew on every chunk tick.  So a tick computes one expert
    layer more than the model needs."""
    stacks = params["layers"]
    n_dense = cfg.first_k_dense

    def take(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), tree)

    def body(carry, xs):
        x, cache = carry
        layer, i = xs
        h, cache = attend(layer["attn"],
                          rms_norm(x, layer["norm_attn"], cfg.norm_eps), i,
                          cache)
        x = x + h
        h = rms_norm(x, layer["norm_mlp"], cfg.norm_eps)
        out = moe_lib.held_experts_block(
            take(stacks["moe"], jnp.maximum(i - n_dense, 0)), h, cfg)
        if n_dense:
            out = jax.lax.cond(
                i < n_dense,
                lambda h, out: mlp(take(stacks["mlp"], jnp.minimum(
                    i, n_dense - 1)), h, cfg.jnp_dtype()),
                lambda h, out: out, h, out)
        return (shard(x + out, "act_btd"), cache), None

    if remat:
        body = jax.checkpoint(body)
    per_layer = {k: stacks[k] for k in ("norm_attn", "attn", "norm_mlp")}
    (x, cache), _ = jax.lax.scan(
        body, (x, cache),
        (per_layer, jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return x, cache


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------


class ForwardOut(NamedTuple):
    logits: jax.Array
    aux_loss: jax.Array


def forward_hidden(params: Params, tokens: jax.Array, cfg: ModelConfig,
                   shard: ShardFn = _id_shard,
                   frontend_embeddings: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S_text).  For vlm/audio decoder-only archs,
    ``frontend_embeddings`` (B, n_front, d) is prepended (stub frontend).

    Returns (final-normed hidden states (B, S_total, d), aux_loss) — the LM
    head is applied by the caller (training chunks it; serving takes the
    last position only)."""
    dtype = cfg.jnp_dtype()
    x = embed(params["tok"], tokens, dtype)
    if frontend_embeddings is not None:
        x = jnp.concatenate([frontend_embeddings.astype(dtype), x], axis=1)
    x = shard(x, "act_btd")
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    windows = jnp.asarray(cfg.layer_windows(s), jnp.int32)
    aux0 = jnp.zeros((), jnp.float32)

    if cfg.layout in ("dense", "moe"):
        def body(x, xs):
            layer, window = xs
            if cfg.layout == "dense":
                return _dense_block(layer, x, positions, window, cfg, shard), aux0
            x, aux = _moe_block(layer, x, positions, window, cfg, shard)
            return x, aux
        if cfg.remat:
            body = jax.checkpoint(body)
        x, auxes = jax.lax.scan(lambda c, xs: body(c, xs), x,
                                (params["layers"], windows))
        aux = jnp.sum(auxes)

    elif cfg.layout == "mla_moe":
        x, _ = _mla_layers(
            params, x, (),
            lambda p, h, i, c: (mla.attention_full(p, h, cfg, shard), c),
            cfg, shard, remat=cfg.remat)
        aux = aux0

    elif cfg.layout == "rwkv":
        states = rwkv_lib.init_rwkv_state(cfg, b)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), states)

        def body(x, xs):
            layer, st = xs
            h, st = rwkv_lib.rwkv_time_mix(layer["rwkv"],
                                           rms_norm(x, layer["ln1"], cfg.norm_eps),
                                           st, cfg, shard=shard)
            x = x + h
            h, st = rwkv_lib.rwkv_channel_mix(layer["rwkv"],
                                              rms_norm(x, layer["ln2"], cfg.norm_eps),
                                              st, cfg)
            return shard(x + h, "act_btd"), aux0
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, (params["layers"], stacked))
        aux = aux0

    elif cfg.layout == "mamba_hybrid":
        shared = params["shared_attn"]
        every = cfg.attn_every

        def body(x, xs):
            layer, idx = xs
            h, _ = ssm_lib.mamba_prefill(layer["mamba"],
                                         rms_norm(x, layer["norm"], cfg.norm_eps),
                                         cfg)
            x = x + h

            def with_attn(x):
                return _dense_block(shared, x, positions,
                                    jnp.int32(s), cfg, shard)
            x = jax.lax.cond((idx + 1) % every == 0, with_attn, lambda x: x, x)
            return shard(x, "act_btd"), aux0
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, (params["layers"],
                                      jnp.arange(cfg.n_layers)))
        aux = aux0
    else:
        raise ValueError(cfg.layout)

    return rms_norm(x, params["norm_f"], cfg.norm_eps), aux


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
            shard: ShardFn = _id_shard,
            frontend_embeddings: Optional[jax.Array] = None) -> ForwardOut:
    """Full-logits forward (tests / small models — training uses the
    chunked-CE path over ``forward_hidden`` instead)."""
    x, aux = forward_hidden(params, tokens, cfg, shard, frontend_embeddings)
    logits = unembed(params["tok"], x, cfg.jnp_dtype())
    return ForwardOut(shard(logits, "act_btv"), aux)


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """ShapeDtypeStruct tree for the serve-step cache (dry-run input specs).

    Sliding-window / local:global stacks hold RING buffers of size W for
    their local layers instead of full-depth KV — gemma3-27b decode_32k is
    2.1 TB of KV with uniform caches and 0.4 TB with rings."""
    L = cfg.n_layers
    if cfg.layout == "mla_moe":
        # one latent row per token per layer: c_kv then the rope key
        return {"k": sds((L, batch, max_len, cfg.latent_dim), cfg.dtype),
                "length": sds((batch,), "int32")}
    if cfg.layout in ("dense", "moe"):
        windowed = (cfg.layout == "dense"
                    and cfg.attn_pattern in ("swa", "local_global")
                    and max_len > cfg.window)
        if windowed:
            windows = cfg.layer_windows(max_len)
            n_local = sum(1 for w in windows if w < max_len)
            n_global = L - n_local
            w = min(cfg.window, max_len)
            kd = (batch, cfg.n_kv_heads, cfg.head_dim)
            shapes = {
                "k_local": sds((n_local, batch, w) + kd[1:], cfg.dtype),
                "v_local": sds((n_local, batch, w) + kd[1:], cfg.dtype),
                "length": sds((batch,), "int32"),
            }
            if n_global:
                shapes["k_global"] = sds((n_global, batch, max_len) + kd[1:],
                                         cfg.dtype)
                shapes["v_global"] = sds((n_global, batch, max_len) + kd[1:],
                                         cfg.dtype)
            return shapes
        kv = sds((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
        return {"k": kv, "v": kv, "length": sds((batch,), "int32")}
    if cfg.layout == "rwkv":
        h, kd = rwkv_lib.rwkv_dims(cfg)
        return {"shift_tm": sds((L, batch, cfg.d_model), "float32"),
                "shift_cm": sds((L, batch, cfg.d_model), "float32"),
                "wkv": sds((L, batch, h, kd, kd), "float32"),
                "length": sds((batch,), "int32")}
    if cfg.layout == "mamba_hybrid":
        d_inner, h, n = ssm_lib.mamba_dims(cfg)
        conv_dim = d_inner + 2 * n
        n_sites = cfg.n_layers // cfg.attn_every
        return {"conv": sds((L, batch, cfg.ssm_conv - 1, conv_dim), cfg.dtype),
                "ssm": sds((L, batch, h, ssm_lib.MAMBA_HEAD_DIM, n), "float32"),
                "attn_k": sds((n_sites, batch, max_len, cfg.n_kv_heads,
                               cfg.head_dim), cfg.dtype),
                "attn_v": sds((n_sites, batch, max_len, cfg.n_kv_heads,
                               cfg.head_dim), cfg.dtype),
                "length": sds((batch,), "int32")}
    raise ValueError(cfg.layout)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_shapes(cfg, batch, max_len))


# ---------------------------------------------------------------------------
# Chunked serving prefill (dense / moe / mla_moe, full-depth caches)
# ---------------------------------------------------------------------------


def prefill_chunk_step(params: Params, tokens: jax.Array, cache: Params,
                       cfg: ModelConfig, n_active: jax.Array,
                       shard: ShardFn = _id_shard
                       ) -> Tuple[jax.Array, Params]:
    """Populate the decode cache with a C-token prompt slab per slot.

    tokens: (B, C) int32 prompt tokens; slot b's slab lands at cache
    positions cache["length"][b] .. +n_active[b]-1.  ``n_active``: (B,)
    int32 — how many of the C positions are real tokens for each slot
    (0 = slot idle this step; positions past n_active are padding whose
    cache writes are masked out and whose logits are garbage).

    Returns (logits (B, C, V), new cache with per-slot lengths advanced by
    n_active).  With C == 1 and n_active == 1 this computes exactly what
    ``decode_step`` computes — the serving engine exploits that to run
    mixed ticks where decode slots ride along in slot 0 of the slab.

    Only full-depth caches chunk (dense/moe k and v, or the mla_moe
    latent ``k``);
    ring-buffer (windowed) caches and recurrent layouts fall back to the
    one-token path — see ``api.supports_chunked_prefill``.
    """
    if cfg.layout not in ("dense", "moe", "mla_moe") or "k" not in cache:
        raise ValueError(
            f"chunked prefill unsupported for layout={cfg.layout!r} / "
            f"cache keys {sorted(cache)} (ring-buffer caches and recurrent "
            f"state need per-token gating; use the one-token decode path)")
    dtype = cfg.jnp_dtype()
    c = tokens.shape[1]
    lengths = cache["length"]
    active = (jnp.arange(c, dtype=jnp.int32)[None, :]
              < n_active[:, None])                           # (B, C)
    x = shard(embed(params["tok"], tokens, dtype), "act_btd")

    if cfg.layout == "mla_moe":
        x, new_cache = _mla_chunk(params, x, cache["k"], lengths, active,
                                  cfg, shard)
    else:
        x, new_cache = _kv_chunk(params, x, cache, lengths, active, cfg,
                                 shard)
    new_cache["length"] = lengths + n_active
    x = rms_norm(x, params["norm_f"], cfg.norm_eps)
    logits = unembed(params["tok"], x, dtype)
    return shard(logits, "act_btv"), new_cache


def _kv_chunk(params: Params, x, cache: Params, lengths, active,
              cfg: ModelConfig, shard: ShardFn):
    """A slab through dense / moe layers over full-depth k and v caches."""
    dtype = cfg.jnp_dtype()
    s_max = cache["k"].shape[2]
    windows = jnp.asarray(cfg.layer_windows(s_max), jnp.int32)

    def body(x, xs):
        layer, k_c, v_c, window = xs
        h = rms_norm(x, layer["norm_attn"], cfg.norm_eps)
        h, (k_c, v_c) = attn.attention_prefill_chunk(
            layer["attn"], h, k_c, v_c, window, lengths, active, cfg, shard)
        x = x + h
        h = rms_norm(x, layer["norm_mlp"], cfg.norm_eps)
        if cfg.layout == "dense":
            x = x + mlp(layer["mlp"], h, dtype)
        else:
            # padding rows flow through MoE dispatch but cannot evict real
            # tokens: ``active`` is a prefix of the slab and the capacity
            # sort is stable, so real tokens always rank first within an
            # expert (invariant documented at moe._dispatch_group); their
            # outputs land on padding rows the caller discards.
            m, _ = moe_lib.moe_block(layer["moe"], h, cfg,
                                     use_pallas=cfg.use_pallas)
            x = x + m
        return shard(x, "act_btd"), (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], windows))
    return x, {"k": k_new, "v": v_new}


def _mla_chunk(params: Params, x, k_cache, lengths, active,
               cfg: ModelConfig, shard: ShardFn):
    """A slab through the MLA+MoE layers over the latent cache (L, B, S,
    r + rope); each layer updates its own rows of it in place."""
    def attend(p, h, i, k_all):
        h, rows = mla.attention_chunk(
            p, h, jax.lax.dynamic_index_in_dim(k_all, i, 0, False), lengths,
            active, cfg, shard)
        return h, jax.lax.dynamic_update_index_in_dim(k_all, rows, i, 0)

    x, k_cache = _mla_layers(params, x, k_cache, attend, cfg, shard)
    return x, {"k": k_cache}


# ---------------------------------------------------------------------------
# Single-token decode (serve_step body)
# ---------------------------------------------------------------------------


def _layer_slice(tree: Params, l: int) -> Params:
    return jax.tree.map(lambda a: a[l], tree)


def _dense_decode_local(layer, x, kr, vr, length, cfg, shard, dtype):
    h = rms_norm(x, layer["norm_attn"], cfg.norm_eps)
    h, (kr, vr) = attn.attention_decode_ring(layer["attn"], h, kr, vr,
                                             length, cfg, shard)
    x = x + h
    x = x + mlp(layer["mlp"], rms_norm(x, layer["norm_mlp"], cfg.norm_eps),
                dtype)
    return shard(x, "dec_btd"), kr, vr


def _dense_decode_global(layer, x, k_c, v_c, length, s_max, cfg, shard,
                         dtype):
    h = rms_norm(x, layer["norm_attn"], cfg.norm_eps)
    h, (k_c, v_c) = attn.attention_decode(layer["attn"], h, k_c, v_c,
                                          jnp.int32(s_max), length, cfg,
                                          shard)
    x = x + h
    x = x + mlp(layer["mlp"], rms_norm(x, layer["norm_mlp"], cfg.norm_eps),
                dtype)
    return shard(x, "dec_btd"), k_c, v_c


def _decode_windowed(params: Params, x: jax.Array, cache: Params,
                     cfg: ModelConfig, shard: ShardFn):
    """Decode through a windowed (ring-buffer) cache.

    swa: every layer attends through a W-slot ring.  local_global: the
    5:1 pattern is scanned as uniform groups of (local_per_global rings +
    one full-depth global layer); trailing local layers get their own scan.
    """
    dtype = cfg.jnp_dtype()
    length = cache["length"]
    L = cfg.n_layers

    def local_scan(x, layers_tree, kl, vl):
        def body(x, xs):
            layer, kr, vr = xs
            x, kr, vr = _dense_decode_local(layer, x, kr, vr, length, cfg,
                                            shard, dtype)
            return x, (kr, vr)
        return jax.lax.scan(body, x, (layers_tree, kl, vl))

    if "k_global" not in cache:          # pure sliding-window (danube)
        x, (kl, vl) = local_scan(x, params["layers"], cache["k_local"],
                                 cache["v_local"])
        return x, {"k_local": kl, "v_local": vl, "length": length + 1}

    p = cfg.local_per_global + 1
    g = L // p
    n_loc_grouped = g * (p - 1)
    grouped = jax.tree.map(
        lambda a: a[: g * p].reshape(g, p, *a.shape[1:]), params["layers"])
    local_params = jax.tree.map(lambda a: a[:, : p - 1], grouped)
    global_params = jax.tree.map(lambda a: a[:, p - 1], grouped)
    kl = cache["k_local"]
    vl = cache["v_local"]
    kl_g = kl[:n_loc_grouped].reshape(g, p - 1, *kl.shape[1:])
    vl_g = vl[:n_loc_grouped].reshape(g, p - 1, *vl.shape[1:])
    s_max = cache["k_global"].shape[2]

    def group_body(x, xs):
        lp, gp, kl_i, vl_i, kg_i, vg_i = xs
        x, (kl_i, vl_i) = local_scan(x, lp, kl_i, vl_i)
        x, kg_i, vg_i = _dense_decode_global(gp, x, kg_i, vg_i, length,
                                             s_max, cfg, shard, dtype)
        return x, (kl_i, vl_i, kg_i, vg_i)

    x, (kl_new, vl_new, kg_new, vg_new) = jax.lax.scan(
        group_body, x, (local_params, global_params, kl_g, vl_g,
                        cache["k_global"], cache["v_global"]))
    kl_new = kl_new.reshape(n_loc_grouped, *kl.shape[1:])
    vl_new = vl_new.reshape(n_loc_grouped, *vl.shape[1:])

    if L % p:                            # trailing local layers
        tail_params = jax.tree.map(lambda a: a[g * p:], params["layers"])
        x, (kl_t, vl_t) = local_scan(x, tail_params, kl[n_loc_grouped:],
                                     vl[n_loc_grouped:])
        kl_new = jnp.concatenate([kl_new, kl_t])
        vl_new = jnp.concatenate([vl_new, vl_t])
    return x, {"k_local": kl_new, "v_local": vl_new, "k_global": kg_new,
               "v_global": vg_new, "length": length + 1}


def decode_step(params: Params, token: jax.Array, cache: Params,
                cfg: ModelConfig, shard: ShardFn = _id_shard
                ) -> Tuple[jax.Array, Params]:
    """token: (B, 1) int32.  Returns (logits (B, 1, V), new cache).

    Layers run under ``lax.scan`` with the per-layer cache as xs/ys — the
    jit-level cache donation lets XLA alias the ys output buffer with the
    input cache so the append is in place.  (An unrolled ``.at[l].set``
    variant was measured to COPY the full cache per layer — 64×4 GiB of
    HBM traffic for grok decode_32k — because straight-line DUS on a buffer
    with later reads defeats XLA's in-place analysis; see EXPERIMENTS §Perf.)
    """
    if cfg.layout == "mla_moe":
        # a one-token decode is the one-token slab, every slot fed
        return prefill_chunk_step(params, token, cache, cfg,
                                  jnp.ones(token.shape[:1], jnp.int32), shard)
    dtype = cfg.jnp_dtype()
    x = shard(embed(params["tok"], token, dtype), "dec_btd")
    length = cache["length"]

    if cfg.layout in ("dense", "moe") and "k_local" in cache:
        x, new_cache = _decode_windowed(params, x, cache, cfg, shard)

    elif cfg.layout in ("dense", "moe"):
        s_max = cache["k"].shape[2]
        windows = jnp.asarray(cfg.layer_windows(s_max), jnp.int32)

        def body(x, xs):
            layer, k_c, v_c, window = xs
            h = rms_norm(x, layer["norm_attn"], cfg.norm_eps)
            h, (k_c, v_c) = attn.attention_decode(layer["attn"], h, k_c, v_c,
                                                  window, length, cfg, shard)
            x = x + h
            h = rms_norm(x, layer["norm_mlp"], cfg.norm_eps)
            if cfg.layout == "dense":
                x = x + mlp(layer["mlp"], h, dtype)
            else:
                m, _ = moe_lib.moe_block(layer["moe"], h, cfg,
                                         use_pallas=cfg.use_pallas)
                x = x + m
            return shard(x, "dec_btd"), (k_c, v_c)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], windows))
        new_cache = {"k": k_new, "v": v_new, "length": length + 1}

    elif cfg.layout == "rwkv":
        def body(x, xs):
            layer, s_tm, s_cm, wkv = xs
            st = rwkv_lib.RwkvLayerState(s_tm, s_cm, wkv)
            h, st = rwkv_lib.rwkv_time_mix(layer["rwkv"],
                                           rms_norm(x, layer["ln1"], cfg.norm_eps),
                                           st, cfg, decode=True)
            x = x + h
            h, st = rwkv_lib.rwkv_channel_mix(layer["rwkv"],
                                              rms_norm(x, layer["ln2"], cfg.norm_eps),
                                              st, cfg)
            return x + h, (st.shift_tm, st.shift_cm, st.wkv)

        x, (s_tm, s_cm, wkv) = jax.lax.scan(
            body, x, (params["layers"], cache["shift_tm"], cache["shift_cm"],
                      cache["wkv"]))
        new_cache = {"shift_tm": s_tm, "shift_cm": s_cm, "wkv": wkv,
                     "length": length + 1}

    elif cfg.layout == "mamba_hybrid":
        shared = params["shared_attn"]
        every = cfg.attn_every
        s_max = cache["attn_k"].shape[2]
        n_sites = cache["attn_k"].shape[0]
        L = cfg.n_layers

        # mamba sub-stack between attention sites runs under a scan; the
        # (few, large) shared-attention sites are unrolled so their KV xs/ys
        # slicing stays per-site.
        def mamba_span(x, lo, hi):
            span = lambda t: jax.tree.map(lambda a: a[lo:hi], t)

            def body(x, xs):
                layer, conv_s, ssm_s = xs
                h = rms_norm(x, layer["norm"], cfg.norm_eps)
                h, (conv_s, ssm_s) = ssm_lib.mamba_decode(layer["mamba"], h,
                                                          conv_s, ssm_s, cfg)
                return x + h, (conv_s, ssm_s)

            return jax.lax.scan(body, x, (span(params["layers"]),
                                          cache["conv"][lo:hi],
                                          cache["ssm"][lo:hi]))

        conv_parts, ssm_parts, k_parts, v_parts = [], [], [], []
        lo = 0
        for site in range(n_sites):
            hi = (site + 1) * every
            x, (conv_s, ssm_s) = mamba_span(x, lo, hi)
            conv_parts.append(conv_s)
            ssm_parts.append(ssm_s)
            h = rms_norm(x, shared["norm_attn"], cfg.norm_eps)
            h, (k_c, v_c) = attn.attention_decode(
                shared["attn"], h, cache["attn_k"][site],
                cache["attn_v"][site], jnp.int32(s_max), length, cfg, shard)
            x = x + h
            x = x + mlp(shared["mlp"], rms_norm(x, shared["norm_mlp"],
                                                cfg.norm_eps), dtype)
            k_parts.append(k_c[None])
            v_parts.append(v_c[None])
            lo = hi
        if lo < L:                          # trailing mamba-only layers
            x, (conv_s, ssm_s) = mamba_span(x, lo, L)
            conv_parts.append(conv_s)
            ssm_parts.append(ssm_s)
        new_cache = {"conv": jnp.concatenate(conv_parts),
                     "ssm": jnp.concatenate(ssm_parts),
                     "attn_k": jnp.concatenate(k_parts),
                     "attn_v": jnp.concatenate(v_parts),
                     "length": length + 1}
    else:
        raise ValueError(cfg.layout)

    x = rms_norm(x, params["norm_f"], cfg.norm_eps)
    logits = unembed(params["tok"], x, dtype)
    return shard(logits, "dec_btv"), new_cache

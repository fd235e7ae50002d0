"""Mixture-of-Experts: sort-based capacity-bounded dispatch + shared experts.

Dispatch strategy (TPU-native, DESIGN §5): tokens are *sorted by assigned
expert* and regrouped into an (E, C, d) tensor, experts run as one batched
einsum, and results are scattered back.  FLOPs scale with top_k (not with
n_experts), so the roofline MODEL_FLOPS/HLO_FLOPs ratio stays honest — a
one-hot-einsum MoE would inflate compiled FLOPs by E/top_k.

A second layer, ``held_experts_block``, serves one chip's share of the
experts (``ModelConfig.experts_first`` / ``experts_held``): it routes over
all ``n_experts``, computes every token routed to an expert it holds, with
no capacity and so no dropped token, and adds the shared experts, which
every chip computes alike.  The shares' routed parts add up to the whole
layer's.

Sharding: experts are small for the assigned archs (grok: 8, qwen2-moe: 60),
so we use expert-tensor-parallelism — every device holds all experts with the
expert hidden dim sharded over the "model" axis, and tokens stay local to
their "data" shard.  This avoids all-to-all entirely; the only collective is
the same psum a dense TP MLP needs.  (An all-to-all EP variant is evaluated
in EXPERIMENTS §Perf.)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import Params, mlp, mlp_shapes, sds


def moe_shapes(cfg: ModelConfig) -> Params:
    dt = cfg.param_dtype
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_held_experts
    shapes = {
        "router": sds((d, cfg.n_experts), dt),
        "wi_gate": sds((e, d, f), dt),
        "wi_up": sds((e, d, f), dt),
        "wo": sds((e, f, d), dt),
    }
    if cfg.n_shared_experts:
        shapes["shared"] = mlp_shapes(d, f * cfg.n_shared_experts, dt)
    return shapes


def top_k_gating(logits: jax.Array, k: int, renormalize: bool = True
                 ) -> Tuple[jax.Array, jax.Array]:
    """Returns (weights (T,k), indices (T,k)) of the k largest logits: the
    softmax over the chosen k (``renormalize``), or their share of the
    softmax over every expert."""
    gates, idx = jax.lax.top_k(logits, k)
    if renormalize:
        return jax.nn.softmax(gates, axis=-1), idx
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.take_along_axis(probs, idx, axis=-1), idx


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    c = int(n_tokens * top_k * capacity_factor / n_experts)
    return max(((c + 7) // 8) * 8, 8)  # pad to 8 for TPU lane alignment


def _dispatch_group(params: Params, xf: jax.Array, cfg: ModelConfig,
                    use_pallas: bool) -> Tuple[jax.Array, jax.Array]:
    """One dispatch group.  xf: (t, d) -> (out (t, d), aux_loss scalar).

    Sort-based dispatch: route, sort by expert, bucket into (E, C, d) with
    capacity C; overflow tokens are dropped (standard capacity-bounded MoE;
    capacity_factor gives slack).
    """
    t, d = xf.shape
    dt = cfg.jnp_dtype()
    e, k = cfg.n_experts, cfg.top_k

    logits = jnp.einsum("td,de->te", xf, params["router"].astype(dt)).astype(jnp.float32)
    if use_pallas:
        from repro.kernels.moe_gating import ops as gate_ops
        weights, idx = gate_ops.topk_gating(logits, k)
    else:
        weights, idx = top_k_gating(logits, k, cfg.norm_topk_prob)

    # load-balancing auxiliary loss (Switch-style): E * Σ_e f_e · p_e
    probs = jax.nn.softmax(logits, axis=-1)
    me = jnp.mean(probs, axis=0)
    one_hot_top1 = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux_loss = e * jnp.sum(me * ce)

    c = expert_capacity(t, e, k, cfg.capacity_factor)

    # --- sort-based dispatch -------------------------------------------------
    # The serving chunked-prefill path feeds slabs whose *trailing* rows may
    # be padding (lm.prefill_chunk_step masks them out afterwards).  Two
    # properties keep padding from ever evicting a real token here: the
    # argsort below is STABLE (jnp default), so within an expert group
    # earlier slab positions — the real tokens, always a prefix — rank
    # first; and expert_capacity lane-pads to >= 8 >= t for slabs of <= 8,
    # so capacity cannot bind at the default chunk size at all.  Guarded by
    # tests/test_prefill_chunk.py::test_moe_mixed_tick_padding_is_harmless.
    flat_expert = idx.reshape(-1)                      # (t*k,)
    flat_token = jnp.repeat(jnp.arange(t), k)          # source token per slot
    flat_weight = weights.reshape(-1)
    order = jnp.argsort(flat_expert)                   # group by expert
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_weight = flat_weight[order]
    # rank within expert group = position - first position of that expert
    expert_start = jnp.searchsorted(sorted_expert, jnp.arange(e))
    rank = jnp.arange(t * k) - expert_start[sorted_expert]
    keep = rank < c
    slot = sorted_expert * c + rank                    # flat (e*c) bucket slot
    slot = jnp.where(keep, slot, e * c)                # overflow -> scratch row

    gathered = xf[sorted_token]                                      # (t*k, d)
    buckets = jnp.zeros((e * c + 1, d), dt).at[slot].set(
        jnp.where(keep[:, None], gathered, 0))
    buckets = buckets[:-1].reshape(e, c, d)

    # --- batched expert FFN ---------------------------------------------------
    g = jnp.einsum("ecd,edf->ecf", buckets, params["wi_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buckets, params["wi_up"].astype(dt))
    h = jax.nn.silu(g) * u
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(dt))

    # --- combine (scatter back with gate weights) -----------------------------
    flat_out = expert_out.reshape(e * c, d)
    contrib = flat_out[jnp.minimum(slot, e * c - 1)] * (
        sorted_weight * keep).astype(dt)[:, None]
    out = jnp.zeros((t, d), dt).at[sorted_token].add(contrib)
    return out, aux_loss


def moe_block(params: Params, x: jax.Array, cfg: ModelConfig,
              use_pallas: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Dispatch is *grouped per batch row* for S > 1 (prefill/train): capacity is
    computed within each sequence and the sort/scatter stays local to the
    row, so SPMD partitioning along the (sharded) batch dim never needs a
    global argsort/all-gather.  Decode (S == 1) routes all rows as one group
    — the token count is tiny there.
    """
    b, s, d = x.shape
    if s > 1:
        out, aux = jax.vmap(
            lambda xg: _dispatch_group(params, xg, cfg, use_pallas))(
                x.reshape(b, s, d))
        aux_loss = jnp.mean(aux)
    else:
        out, aux_loss = _dispatch_group(params, x.reshape(b * s, d), cfg,
                                        use_pallas)
        out = out.reshape(b, s, d)

    if cfg.n_shared_experts:
        out = out + mlp(params["shared"], x.reshape(b, s, d), cfg.jnp_dtype())
    return out.reshape(b, s, d), aux_loss


def held_experts_block(params: Params, x: jax.Array,
                       cfg: ModelConfig) -> jax.Array:
    """x: (B, S, d) -> (B, S, d): this chip's share of a routed layer plus
    the shared experts.

    The gate is float32 over all ``n_experts``; each token's top-k weights
    (``top_k_gating``) land on the held experts among its choices.  Every
    held expert runs on every token and its output is weighted by that
    token's weight for it, zero where the token did not choose it: no
    capacity, no dropped token, at ``held`` expert passes a token (one
    step reads each held expert's weights once whatever the batch)."""
    b, s, d = x.shape
    dt = cfg.jnp_dtype()
    xf = x.reshape(b * s, d)
    with jax.named_scope("moe.gate"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        weights, idx = top_k_gating(logits, cfg.top_k, cfg.norm_topk_prob)
        held = cfg.experts_first + jnp.arange(cfg.n_held_experts)
        w = jnp.sum(jnp.where(idx[:, :, None] == held, weights[:, :, None],
                              0.0), axis=1)                  # (T, held)
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("td,edf->etf", xf, params["wi_gate"].astype(dt))
        u = jnp.einsum("td,edf->etf", xf, params["wi_up"].astype(dt))
        h = (jax.nn.silu(g) * u).astype(jnp.float32) * w.T[:, :, None]
        out = jnp.einsum("etf,efd->td", h.astype(dt),
                         params["wo"].astype(dt))
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + mlp(params["shared"], xf, dt)
    return out.reshape(b, s, d)

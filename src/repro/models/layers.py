"""Primitive layers (pure-functional, pytree params): norms, RoPE, MLP, embed.

Parameter handling is shapes-first: every module exposes ``*_shapes(cfg)``
returning a pytree of ``jax.ShapeDtypeStruct`` that mirrors its forward code.
``materialize`` turns a shape tree into real initialized params; the dry-run
passes the shape tree itself (no allocation), which is what lets us lower
314B-parameter models on a CPU host.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat

Params = Dict[str, Any]


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _draw(key, name: str, shape: Tuple[int, ...], dtype,
          fan_in: int) -> jax.Array:
    if name.startswith(("norm", "scale", "ln")):
        return jnp.ones(shape, dtype)
    if name.startswith(("bias", "dt_bias")):
        return jnp.zeros(shape, dtype)
    if name.startswith("a_log"):  # mamba A init: log of [1, 16)
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if name.startswith("decay"):  # rwkv decay speed init
        return jax.random.uniform(key, shape, jnp.float32, -8.0,
                                  -4.0).astype(dtype)
    if name.startswith("embed"):
        return (jax.random.normal(key, shape, jnp.float32)
                * 0.02).astype(dtype)
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# One compiled program per (leaf kind, shape, dtype, stacked).  The
# generator's float32 values exist only inside the program, and a stacked
# per-layer leaf is drawn one layer at a time, so they never span a whole
# stack: a danube MLP leaf would otherwise need 3.8 GB of float32 (and as
# much again in random bits) next to its 1.9 GB bf16 result.
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _init_leaf(key, name: str, shape: Tuple[int, ...], dtype, fan_in: int,
               stacked: bool) -> jax.Array:
    if not stacked:
        return _draw(key, name, shape, dtype, fan_in)
    return jax.lax.map(lambda k: _draw(k, name, shape[1:], dtype, fan_in),
                       jax.random.split(key, shape[0]))


def _fan_in(path: str, shape: Tuple[int, ...]) -> int:
    """How many inputs each output of a weight leaf sums over.  Attention
    projections keep heads as axes of their own — ``wq``/``wk``/``wv``
    are ``(…, d, H, hd)`` and sum over d, ``wo`` is ``(…, H, hd, d)`` and
    sums over H·hd; every other matrix is ``(…, d_in, d_out)``.  (Reading
    ``shape[-2]`` off an attention leaf gave q and k a std of ~1/sqrt(H)
    instead of 1/sqrt(d): saturated softmax, and random-weight logits that
    a bf16 rounding difference could flip.)"""
    *parents, name = path.split("/")
    if parents and parents[-1].endswith("attn") and len(shape) >= 3:
        if name in ("wq", "wk", "wv"):
            return shape[-3]
        if name == "wo":
            return shape[-3] * shape[-2]
    return shape[-2] if len(shape) >= 2 else max(shape[-1], 1)


def materialize(key: jax.Array, shape_tree: Params) -> Params:
    """Initialize a params pytree from its ShapeDtypeStruct tree."""
    leaves, treedef = jax.tree.flatten_with_path(shape_tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, s) in zip(keys, leaves):
        path = compat.path_str(path)
        shape = tuple(s.shape)
        # "layers", "enc_layers", "dec_layers": leading axis is the layer
        stacked = path.split("/")[0].endswith("layers") and len(shape) >= 2
        out.append(_init_leaf(k, path.split("/")[-1], shape,
                              jnp.dtype(s.dtype), _fan_in(path, shape),
                              stacked))
    return jax.tree.unflatten(treedef, out)


def param_count(tree: Params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def param_bytes(tree: Params) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def rms_norm_shapes(d: int, dtype) -> jax.ShapeDtypeStruct:
    return sds((d,), dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)                    # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs    # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                             # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def mlp_shapes(d_model: int, d_ff: int, dtype) -> Params:
    return {"wi_gate": sds((d_model, d_ff), dtype),
            "wi_up": sds((d_model, d_ff), dtype),
            "wo": sds((d_ff, d_model), dtype)}


def mlp(params: Params, x: jax.Array, compute_dtype=None) -> jax.Array:
    dt = compute_dtype or x.dtype
    g = jnp.einsum("...d,df->...f", x, params["wi_gate"].astype(dt))
    u = jnp.einsum("...d,df->...f", x, params["wi_up"].astype(dt))
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, params["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embed_shapes(vocab: int, d_model: int, dtype, tie: bool) -> Params:
    out = {"embed": sds((vocab, d_model), dtype)}
    if not tie:
        out["unembed"] = sds((d_model, vocab), dtype)
    return out


def embed(params: Params, tokens: jax.Array, compute_dtype) -> jax.Array:
    return params["embed"].astype(compute_dtype)[tokens]


def unembed(params: Params, x: jax.Array, compute_dtype) -> jax.Array:
    if "unembed" in params:
        w = params["unembed"].astype(compute_dtype)
    else:
        w = params["embed"].astype(compute_dtype).T
    return jnp.einsum("...d,dv->...v", x, w)

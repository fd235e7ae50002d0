"""Fused top-k softmax gating kernel (Pallas TPU).

One pass over a (bt, E) tile of router logits held in VMEM: k iterative
argmax sweeps (k ≤ 4 for every assigned arch — grok top-2, qwen2-moe top-4)
select the experts, then the selected gates are softmaxed in-register.  This
fuses what XLA otherwise lowers as top_k sort + gather + softmax — three
HBM round-trips over the (T, E) logits — into one.

E stays un-tiled (60 experts max — a single lane tile); T is the grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _gate_kernel(x_ref, w_ref, i_ref, *, k: int, bt: int, e: int):
    x = x_ref[...].astype(jnp.float32)                  # (bt, E)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, e), 1)
    gates = []
    idxs = []
    for _ in range(k):                                  # k argmax sweeps
        m = jnp.max(x, axis=-1, keepdims=True)          # (bt, 1)
        hit = x == m
        # tie-break to the lowest expert index, as lax.top_k does
        first = jnp.min(jnp.where(hit, cols, e), axis=-1, keepdims=True)
        gates.append(m)
        idxs.append(first)
        x = jnp.where(cols == first, NEG_INF, x)
    g = jnp.concatenate(gates, axis=-1)                 # (bt, k)
    ix = jnp.concatenate(idxs, axis=-1)
    p = jnp.exp(g - g[:, :1])                           # max is first
    w_ref[...] = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(w_ref.dtype)
    i_ref[...] = ix.astype(jnp.int32)


def topk_gating_fwd(logits: jax.Array, k: int, bt: int,
                    interpret: bool):
    t, e = logits.shape
    kernel = functools.partial(_gate_kernel, k=k, bt=bt, e=e)
    return pl.pallas_call(
        kernel,
        grid=(t // bt,),
        in_specs=[pl.BlockSpec((bt, e), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((bt, k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, k), jnp.float32),
                   jax.ShapeDtypeStruct((t, k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(logits)

"""Batched hashed-embedding featurization kernel (Pallas TPU) — the host
encoder of ``core/embedding.py`` as a fused device op.

The router's sentence encoder is a hashed bag-of-features map: blake2-hashed
token/trigram/bigram ids with tf weights, scatter-accumulated into a
``hash_dim`` count vector, sublinear-tf'd with log1p, projected through a
fixed Gaussian matrix, and L2-normalized.  The hashing itself is string work
and stays on host (one vectorized pass building padded ``(Q, L)`` id/weight
tensors); everything after the ids — the scatter, the tf transform, the
projection, the normalization — is dense arithmetic and runs here as one
VMEM pass per Q-block:

    counts[q, h] = Σ_l weights[q, l] · [ids[q, l] == h]      (scatter)
    emb[q]       = normalize(log1p(counts[q]) @ proj)        (tf + project)

The scatter is a one-hot compare-and-reduce, so it vectorizes on the VPU
instead of serializing into per-element stores.  Ids and weights arrive as
``(Q·L, 1)`` columns: a ``(lb, 1)`` slab of one query's features compares
against the lane iota of buckets as an ``(lb, hash_dim)`` tile (features on
sublanes, buckets on lanes), and a sublane sum folds it into that query's
``(1, hash_dim)`` count row in a VMEM scratch.  One MXU matmul then
projects the whole ``(bq, hash_dim)`` block.  Padding rows use id −1,
which matches no bucket.  ``hash_dim`` (2048) and ``dim`` (384) are
lane-aligned; ``proj`` (3 MB fp32) stays resident in VMEM across the grid.

log1p(0) = 0, so applying the tf transform unconditionally is exactly the
host encoder's "skip log1p when the text produced no features" branch — an
all-padding row yields the same all-zero embedding on both paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _featurize_kernel(ids_ref, w_ref, proj_ref, o_ref, counts_ref, *,
                      hash_dim: int, seq_l: int, lb: int):
    bq = o_ref.shape[0]
    buckets = jax.lax.broadcasted_iota(jnp.int32, (1, hash_dim), 1)
    for r in range(bq):                                  # static: block rows

        def chunk(c, counts, r=r):
            start = pl.multiple_of(r * seq_l + c * lb, lb)
            ids = ids_ref[pl.ds(start, lb), :]           # (lb, 1) int32
            w = w_ref[pl.ds(start, lb), :]               # (lb, 1) f32
            hit = jnp.where(ids == buckets, w, 0.0)      # (lb, hash_dim)
            return counts + jnp.sum(hit, axis=0, keepdims=True)

        counts_ref[pl.ds(r, 1), :] = jax.lax.fori_loop(
            0, seq_l // lb, chunk, jnp.zeros((1, hash_dim), jnp.float32))
    tf = jnp.log1p(counts_ref[...])
    v = jax.lax.dot(tf, proj_ref[...], precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)  # (bq, dim)
    norm = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
    o_ref[...] = jnp.where(norm > 0.0, v / jnp.maximum(norm, 1e-30),
                           v).astype(o_ref.dtype)


def hashed_embed_fwd(ids, weights, proj, bq: int, lb: int, interpret: bool):
    """ids/weights: (Q, L) with L a multiple of ``lb``; proj: (H, D)."""
    q, seq_l = ids.shape
    hash_dim, dim = proj.shape
    kernel = functools.partial(_featurize_kernel, hash_dim=hash_dim,
                               seq_l=seq_l, lb=lb)
    col = (bq * seq_l, 1)
    return pl.pallas_call(
        kernel,
        grid=(q // bq,),
        in_specs=[
            pl.BlockSpec(col, lambda i: (i, 0)),
            pl.BlockSpec(col, lambda i: (i, 0)),
            pl.BlockSpec((hash_dim, dim), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, dim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hash_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(ids.reshape(q * seq_l, 1), weights.reshape(q * seq_l, 1), proj)

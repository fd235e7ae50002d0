"""Seeded random weights, made on the device in one jitted call per model.

The benchmark makes the weights itself, in the type they are served in
(bf16; rwkv keeps its per-channel decay and bonus vectors in float32), and
hands them to the program's engine.  So the plain reference can run on the
same weights without taking anything the program made.  The tree below is
the parameter layout the program's engines take, written out from the two
architectures; the harness checks it against the program's own shapes
before serving.  A leaf stacked over layers is drawn one layer at a time
(``lax.map``), so the float32 draws of a whole stack never exist at once.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

# (path, shape, dtype, kind, a, b): "normal" std a, "uniform" on [a, b),
# "ones".  A leaf whose path starts with "layers" is stacked over layers.
Spec = Tuple[str, Tuple[int, ...], str, str, float, float]
RWKV_HEAD_DIM = 64
RWKV_LORA_RANK = 32


def _normal(path, shape, fan_in, dtype="bfloat16") -> Spec:
    return (path, tuple(shape), dtype, "normal", 1.0 / math.sqrt(fan_in), 0.0)


def leaf_specs(cfg: dict) -> List[Spec]:
    """Every leaf of one model's parameter tree, from its sizes."""
    L, d, f, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    specs: List[Spec] = [
        ("tok/embed", (V, d), "bfloat16", "normal", 0.02, 0.0),
        _normal("tok/unembed", (d, V), d),
        ("norm_f", (d,), "bfloat16", "ones", 0.0, 0.0),
    ]
    if cfg["layout"] == "dense":
        H, Hk, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
        specs += [
            ("layers/norm_attn", (L, d), "bfloat16", "ones", 0.0, 0.0),
            _normal("layers/attn/wq", (L, d, H, hd), d),
            _normal("layers/attn/wk", (L, d, Hk, hd), d),
            _normal("layers/attn/wv", (L, d, Hk, hd), d),
            _normal("layers/attn/wo", (L, H, hd, d), H * hd),
            ("layers/norm_mlp", (L, d), "bfloat16", "ones", 0.0, 0.0),
            _normal("layers/mlp/wi_gate", (L, d, f), d),
            _normal("layers/mlp/wi_up", (L, d, f), d),
            _normal("layers/mlp/wo", (L, f, d), f),
        ]
    elif cfg["layout"] == "rwkv":
        r = RWKV_LORA_RANK
        mix = [(f"layers/rwkv/mu_{n}", (L, d), "bfloat16", "uniform", 0.0, 1.0)
               for n in ("r", "k", "v", "w", "g", "ck", "cr")]
        specs += [
            ("layers/ln1", (L, d), "bfloat16", "ones", 0.0, 0.0),
            ("layers/ln2", (L, d), "bfloat16", "ones", 0.0, 0.0),
            *mix,
            *[_normal(f"layers/rwkv/{n}", (L, d, d), d)
              for n in ("wr", "wk", "wv", "wg", "wo_tm", "cr")],
            ("layers/rwkv/decay_base", (L, d), "float32", "uniform", -8.0, -4.0),
            _normal("layers/rwkv/lora_a_decay", (L, d, r), d),
            _normal("layers/rwkv/lora_b_decay", (L, r, d), r),
            ("layers/rwkv/bonus_u", (L, d), "float32", "normal", 0.1, 0.0),
            ("layers/rwkv/ln_x", (L, d), "bfloat16", "ones", 0.0, 0.0),
            _normal("layers/rwkv/ck", (L, d, f), d),
            _normal("layers/rwkv/cv", (L, f, d), f),
        ]
    else:
        raise ValueError(f"no weights for layout {cfg['layout']!r}")
    return specs


def _draw(key, shape, dtype, kind, a, b):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, a, b).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32) * a).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _generate(key, specs: Tuple[Spec, ...]) -> Dict:
    tree: Dict = {}
    for i, (path, shape, dtype, kind, a, b) in enumerate(specs):
        k = jax.random.fold_in(key, i)
        if path.startswith("layers/"):
            value = jax.lax.map(
                lambda kk, s=shape[1:], dt=dtype, kd=kind, lo=a, hi=b:
                _draw(kk, s, dt, kd, lo, hi),
                jax.random.split(k, shape[0]))
        else:
            value = _draw(k, shape, dtype, kind, a, b)
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = value
    return tree


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, stream):
        key = jax.random.fold_in(key, word)
    return key


def make(cfg: dict, seed: int, stream: int, device=None) -> Dict:
    """One model's weights on ``device`` (the default device if None)."""
    with jax.default_device(device):
        return _generate(seed_key(seed, stream), tuple(leaf_specs(cfg)))

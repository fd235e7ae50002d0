"""Seeded random weights, made on the device in one jitted call per model.

The benchmark makes the weights itself, in the type they are served in,
and hands them to the program's engine.  So the plain reference can run on
the same weights without taking anything the program made.  The tree is
the parameter layout the program's engines take, written out leaf by leaf
from each architecture in its ``arch/<layout>.py`` (``layouts.py``): a new
architecture adds its specs there and nothing here.  The harness checks
the tree against the program's own shapes before serving.  A leaf stacked
over layers is drawn one layer at a time (``lax.map``), so the float32
draws of a whole stack never exist at once.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

import layouts

# (path, shape, dtype, kind, a, b): "normal" std a, "uniform" on [a, b),
# "ones".  A leaf whose path starts with "layers" is stacked over layers.
Spec = Tuple[str, Tuple[int, ...], str, str, float, float]


def _normal(path, shape, fan_in, dtype="bfloat16") -> Spec:
    return (path, tuple(shape), dtype, "normal", 1.0 / math.sqrt(fan_in), 0.0)


def leaf_specs(cfg: dict) -> List[Spec]:
    """Every leaf of one model's parameter tree, from its sizes, in the
    order they are drawn: its layout's ``arch/<layout>.py``."""
    return layouts.of(cfg).leaf_specs(cfg)


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

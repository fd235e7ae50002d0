"""Small JAX helpers shared across the stack.

The repo runs on one JAX installation (see README.md "JAX version"); JAX
APIs are called directly at their call sites.  What lives here are the
few helpers that add behaviour on top of a JAX call:

  * ``path_str`` — render a pytree key path as ``outer/inner/leaf``;
  * ``make_mesh`` — ``jax.make_mesh`` with every axis ``AxisType.Auto``
    (JAX's default is ``Explicit``; the sharding rules in
    ``models/sharding.py`` are written for automatic propagation);
  * ``donation_kwargs`` — buffer donation only where the backend
    implements it.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax

__all__ = ["path_str", "make_mesh", "donation_kwargs"]


def path_str(path: Sequence[Any]) -> str:
    """Render a key path as 'outer/inner/leaf' (DictKey/SequenceKey/…)."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> "jax.sharding.Mesh":
    """jax.make_mesh with Auto axis types on every axis."""
    shape = tuple(axis_shapes)
    return jax.make_mesh(shape, tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
                         devices=devices)


def donation_kwargs(*argnums: int) -> dict:
    """``jax.jit`` donation kwargs for state-carrying jitted programs.

    Buffer donation (input aliased to output, no copy) is implemented on
    TPU/GPU but not on CPU, where XLA emits a warning per traced call —
    so on CPU this returns no kwargs and the jit simply copies.  Callers
    splat the result: ``jax.jit(f, **compat.donation_kwargs(0))``.  Only
    donate arguments the caller immediately replaces with the call's
    output (e.g. a ``BanditState`` threaded through update, the k-means
    device tuple) — a donated input buffer is dead after the call.
    """
    if jax.default_backend() in ("tpu", "gpu", "cuda", "rocm"):
        return {"donate_argnums": tuple(argnums)}
    return {}

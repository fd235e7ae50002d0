"""Production mesh construction (DESIGN §5).

Defined as FUNCTIONS so importing this module never touches jax device
state — smoke tests and benches must keep seeing 1 CPU device; only
``dryrun.py`` (which sets XLA_FLAGS before any jax import) sees 512.

All meshes are built through ``repro.compat.make_mesh``, which requests
Auto axis types (JAX's own default is Explicit).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import Mesh

from repro import compat


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips/pod; the multi-pod mesh adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh with Auto axis types (tests, degraded/elastic meshes)."""
    return compat.make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """The 1-device mesh every smoke test / bench runs under."""
    n = len(jax.devices())
    return compat.make_mesh((n, 1), ("data", "model"))


def degraded_mesh(lost_chips: int, *, multi_pod: bool = False) -> Mesh:
    """Elastic-scaling helper: the largest (data', model) mesh that fits the
    surviving device count — the 'model' extent is preserved (TP degree is
    fixed by weight shardings), data parallelism shrinks."""
    base = make_production_mesh(multi_pod=multi_pod)
    total = base.devices.size - lost_chips
    model = base.shape["model"]
    data = total // model
    if data < 1:
        raise ValueError(f"cannot remesh: {total} chips < model axis {model}")
    if multi_pod:
        return compat.make_mesh((1, data, model), ("pod", "data", "model"))
    return compat.make_mesh((data, model), ("data", "model"))

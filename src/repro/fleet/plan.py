"""Fleet planning: partition the device set into engine-pool shards.

``plan_fleet`` splits ``jax.devices()`` round-robin into ``n_shards``
disjoint groups and builds one ``(len(group), 1)`` ``("data", "model")``
mesh per group — the same axis convention as ``launch.mesh.make_host_mesh``
so ``models.sharding.ShardingPolicy`` specs apply unchanged on a shard
mesh (on a 1-device shard the policy's mesh-size fallback replicates).

Each shard runs a full replica of the model pool behind its own
``PoolServer`` + ``GreenServRouter`` (weak scaling: n shards absorb n×
the arrival rate at ~flat per-query latency).  The fleet-level mesh over
*all* devices exists only for degradation bookkeeping: when a shard
dies, ``FleetController`` records a ``distributed.elastic.plan_remesh``
plan over it (how the remaining chips would re-mesh), mirroring the
training-side elastic story.

Planning is pure bookkeeping — importing this module never touches jax
device state; meshes are built lazily from the recorded device ids.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np


def base_model_name(member: str) -> str:
    """Strip the ``@shard`` adoption suffix: arms a shard adopts during
    fail-over are named ``<base>@<dead-shard>`` so pool names stay unique
    while the all-reduce still merges their statistics per base model."""
    return member.split("@", 1)[0]


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """One engine-pool shard: a named, disjoint slice of the device set."""

    index: int
    name: str
    device_ids: Tuple[int, ...]
    models: Tuple[str, ...]

    @property
    def n_devices(self) -> int:
        return len(self.device_ids)


@dataclasses.dataclass(frozen=True)
class FleetPlan:
    """Shard layout + mesh builders (axes fixed to ("data", "model"))."""

    shards: Tuple[ShardSpec, ...]
    axes: Tuple[str, str] = ("data", "model")

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    def _devices_by_id(self):
        import jax
        return {d.id: d for d in jax.devices()}

    def shard_device(self, spec: ShardSpec):
        """The shard's first device — where a one-chip replica lives."""
        return self._devices_by_id()[spec.device_ids[0]]

    def shard_mesh(self, spec: ShardSpec):
        """Per-shard mesh: (n_dev, 1) over exactly the shard's devices."""
        from jax.sharding import Mesh
        by_id = self._devices_by_id()
        devs = [by_id[i] for i in spec.device_ids]
        return Mesh(np.array(devs).reshape(len(devs), 1), self.axes)

    def fleet_mesh(self):
        """Whole-fleet mesh (all shards' devices) — the frame of reference
        for ``plan_remesh`` degradation records on shard loss."""
        from jax.sharding import Mesh
        by_id = self._devices_by_id()
        ids = [i for spec in self.shards for i in spec.device_ids]
        devs = [by_id[i] for i in ids]
        return Mesh(np.array(devs).reshape(len(devs), 1), self.axes)


def plan_fleet(n_shards: int,
               pool_names: Sequence[str],
               devices: Optional[Sequence] = None) -> FleetPlan:
    """Partition devices round-robin (``devices[i::n_shards]``) into
    ``n_shards`` shard specs, each replicating the full ``pool_names``.

    With fewer devices than shards (the common CPU case: 1 device, many
    virtual shards), shards share devices one-to-one by index modulo the
    device count — the controller is a concurrency structure there, not a
    placement one, and the meshes degenerate to (1, 1).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if not pool_names:
        raise ValueError("pool_names must be non-empty")
    if devices is None:
        import jax
        devices = jax.devices()
    ids = [d.id for d in devices]
    shards: List[ShardSpec] = []
    models = tuple(pool_names)
    for i in range(n_shards):
        mine = tuple(ids[i::n_shards]) if len(ids) >= n_shards \
            else (ids[i % len(ids)],)
        shards.append(ShardSpec(index=i, name=f"shard{i}",
                                device_ids=mine, models=models))
    return FleetPlan(shards=tuple(shards))

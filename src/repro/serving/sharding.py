"""Batch-axis sharding + per-device fault domains for the vision mesh.

One :class:`~repro.serving.executors.ExecutorCache` entry normally jits
the whole bucket onto the default device.  With a device list configured
the cache instead lowers the Program at the *local* batch
(``bucket // n_devices``) and wraps ``execute`` in ``shard_map`` over a
1-D ``("batch",)`` mesh: params replicated, activations split along the
batch axis (``distributed.partition.data_parallel_specs``), so the same
cache entry drives every device at once.  ``check_vma=False`` is load-
bearing — Pallas calls have no shard_map replication rule, and the
per-batch-element int8 scales (``core.quantization.quantize_act``) make
the split bit-transparent anyway.

Each device is its own *fault domain*.  :class:`DeviceHealth` is the
registry: a ``DeviceLostError`` marks its device dead and bumps the
mesh ``epoch``; the cache then evicts every executor whose shard
included that device and rebuilds on the survivors — a smaller mesh,
or single-device when nothing divides.  When the last device dies,
``shard_for`` raises ``MeshExhausted`` and the scheduler fails requests
immediately instead of burning retries.  Tested on fake host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh

from repro.common.errors import MeshExhausted
from repro.core.program import execute
from repro.distributed.partition import data_parallel_specs

BATCH_AXIS = "batch"

__all__ = ["BATCH_AXIS", "ShardSpec", "DeviceHealth", "shard_width",
           "sharded_forward"]


@dataclass(frozen=True)
class ShardSpec:
    """The device slice one executor is built for.

    ``devices`` is the tuple of jax devices forming the 1-D batch mesh;
    ``local_batch`` is the per-device batch the Program was lowered at
    (``bucket == local_batch * n_devices``)."""
    devices: tuple
    local_batch: int

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def device_ids(self) -> tuple[int, ...]:
        return tuple(d.id for d in self.devices)


def shard_width(batch: int, n_alive: int) -> int:
    """Largest device count ``k <= n_alive`` with ``batch % k == 0``.

    The bucket ladder is powers of two but the mesh can shrink to any
    size (4 devices -> 3 after one loss), so pick the widest divisor
    rather than requiring the mesh to divide: batch 4 on 3 survivors
    runs 2-wide, batch 1 always runs 1-wide.
    """
    if batch <= 0 or n_alive <= 0:
        raise ValueError(f"shard_width({batch}, {n_alive})")
    for k in range(min(batch, n_alive), 0, -1):
        if batch % k == 0:
            return k
    return 1


@dataclass
class DeviceHealth:
    """Per-device fault-domain registry for one serving mesh.

    Tracks which devices are alive, attributes launch failures to their
    device, and hands out :class:`ShardSpec` slices over the survivors.
    ``epoch`` increments on every death so executors built against an
    older mesh can be recognised as stale.
    """
    devices: tuple
    _dead: set = field(default_factory=set)
    epoch: int = 0
    # optional obs.trace.Tracer: mesh deaths become zero-duration marks
    # on the "mesh" track (ExecutorCache threads it through)
    tracer: object = field(default=None, repr=False, compare=False)

    @classmethod
    def of(cls, devices=None) -> "DeviceHealth":
        return cls(devices=tuple(devices if devices is not None
                                 else jax.devices()))

    def alive(self) -> tuple:
        return tuple(d for d in self.devices if d.id not in self._dead)

    def dead_ids(self) -> tuple[int, ...]:
        return tuple(sorted(self._dead))

    @property
    def n_alive(self) -> int:
        return len(self.alive())

    @property
    def exhausted(self) -> bool:
        return self.n_alive == 0

    def mark_dead(self, device_id: int) -> bool:
        """Record a device loss; returns True if it was newly dead."""
        known = {d.id for d in self.devices}
        if device_id not in known or device_id in self._dead:
            return False
        self._dead.add(device_id)
        self.epoch += 1
        if self.tracer is not None:
            self.tracer.end(self.tracer.begin(
                "device.lost", track="mesh", device=device_id,
                alive=self.n_alive, epoch=self.epoch))
        return True

    def attribute(self, err, shard: ShardSpec | None) -> int | None:
        """Blame a launch failure on a device id, if one can be named.

        ``DeviceLostError`` carries its device; anything else blames the
        first device of the failing shard (the host-side launch runs
        through it first)."""
        dev = getattr(err, "device", None)
        if dev is not None:
            return dev
        if shard is not None and shard.devices:
            return shard.devices[0].id
        return None

    def shard_for(self, batch: int) -> ShardSpec:
        """Widest shard of ``batch`` over the surviving devices.

        Raises :class:`MeshExhausted` when no device is left."""
        alive = self.alive()
        if not alive:
            raise MeshExhausted(
                f"all {len(self.devices)} devices dead "
                f"(ids {self.dead_ids()})")
        k = shard_width(batch, len(alive))
        return ShardSpec(devices=alive[:k], local_batch=batch // k)


def sharded_forward(program, params, *, plan=None, shard: ShardSpec):
    """Jitted whole-mesh forward for one executor-cache entry.

    ``program``/``plan`` are lowered at ``shard.local_batch``; the
    returned callable takes the full bucket ``(B, H, W, C)`` and splits
    it row-wise across ``shard.devices`` via ``shard_map`` (params
    replicated, ``check_vma=False`` for the Pallas launches inside).
    """
    mesh = Mesh(np.array(shard.devices), (BATCH_AXIS,))
    param_specs, act_spec = data_parallel_specs(mesh, params,
                                                batch_axis=BATCH_AXIS)

    def local(p, v):
        return execute(program, p, v, plan=plan)

    f = jax.shard_map(local, mesh=mesh, in_specs=(param_specs, act_spec),
                      out_specs=act_spec, check_vma=False)
    return jax.jit(f)

"""Device mesh construction.

The mesh is the TPU-native replacement for the reference's
``torch.distributed`` process group (NCCL world created implicitly by
Lightning, reference ``perceiver/scripts/cli.py:33-34``): every collective —
gradient allreduce (DDP parity), parameter all-gather/reduce-scatter (FSDP
parity), metric reduction (``sync_dist`` parity) — is emitted by XLA from
sharding annotations over these named axes.

Axis semantics:

- ``data``: batch sharded, everything else replicated (DDP).
- ``fsdp``: batch *and* parameters/optimizer state sharded (ZeRO-3/FSDP).
  The ``data`` and ``fsdp`` axes jointly shard the batch.
- ``model``: tensor parallelism (heads / MLP hidden dim).
- ``seq``: sequence/context parallelism (ring attention over long inputs).

On multi-host pods the mesh should put ``data``/``fsdp`` on the outermost
(DCN) dimension and ``model``/``seq`` innermost so their heavier collectives
ride ICI — :func:`make_mesh` uses ``jax.experimental.mesh_utils`` device
assignment which handles this for TPU topologies.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"

AXIS_NAMES: Tuple[str, ...] = (AXIS_DATA, AXIS_FSDP, AXIS_MODEL, AXIS_SEQ)

#: Axes over which the *batch* dimension is sharded.
BATCH_AXES: Tuple[str, ...] = (AXIS_DATA, AXIS_FSDP)


@dataclasses.dataclass
class MeshConfig:
    """Parallelism degrees. ``-1`` for exactly one axis means "all remaining
    devices" (like the reference's ``--trainer.devices=-1``)."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    seq: int = 1

    def resolve(self, num_devices: int) -> "MeshConfig":
        sizes = dataclasses.asdict(self)
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if num_devices % fixed:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = num_devices // fixed
        elif fixed > num_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices but only {num_devices} are available"
            )
        return MeshConfig(**sizes)

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        return (self.data, self.fsdp, self.model, self.seq)


def make_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    **axis_sizes: int,
) -> Mesh:
    """Build a 4-axis ``Mesh`` (data, fsdp, model, seq) over ``devices``.

    ``make_mesh()`` → all devices on the data axis (DDP parity).
    ``make_mesh(fsdp=8, data=1)`` → fully-sharded over 8 devices (FSDP parity).
    """
    if config is None:
        config = MeshConfig(**axis_sizes)
    elif axis_sizes:
        raise ValueError("pass either a MeshConfig or axis sizes, not both")
    devices = list(devices) if devices is not None else jax.devices()
    config = config.resolve(len(devices))
    devices = devices[: math.prod(config.shape)]  # fully-specified smaller mesh
    # topology-aware on TPU devices, where a shape the topology cannot hold
    # raises; a plain reshape for virtual CPU devices
    device_array = mesh_utils.create_device_mesh(
        config.shape, devices=np.asarray(devices)
    )
    return Mesh(device_array, AXIS_NAMES)


def device_slice(
    count: int,
    *,
    offset: int = 0,
    devices: Optional[Sequence[jax.Device]] = None,
) -> list:
    """A contiguous device subset — the serving-fleet call shape: N replicas
    each own ``count`` devices at disjoint offsets (``serving/sharding.py``),
    so replicas=2 × mesh-of-4 coexist in one process instead of every mesh
    claiming ``jax.devices()`` whole. Validates the slice actually exists —
    an over-subscribed fleet must fail at construction, not alias devices
    silently."""
    devices = list(devices) if devices is not None else jax.devices()
    if count < 1:
        raise ValueError(f"device_slice count must be >= 1, got {count}")
    if offset < 0:
        raise ValueError(f"device_slice offset must be >= 0, got {offset}")
    if offset + count > len(devices):
        raise ValueError(
            f"device slice [{offset}, {offset + count}) overruns the "
            f"{len(devices)} available devices — shrink the mesh or the "
            "replica count (replicas x data x model devices must fit)"
        )
    return devices[offset:offset + count]


def single_device_mesh(device: Optional[jax.Device] = None,
                       *, index: int = 0) -> Mesh:
    """Degenerate 1-device mesh so the same sharded train step runs on one
    chip (all axes size 1 — every PartitionSpec collapses to replication).
    ``index`` picks the device when none is passed — the serving-replica
    form of "use this device subset" (:func:`device_slice`) at size 1."""
    device = device if device is not None else device_slice(1, offset=index)[0]
    return make_mesh(MeshConfig(data=1), devices=[device])

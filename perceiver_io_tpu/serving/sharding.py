"""Sharded serving runtime: run the slot engine over the parallelism mesh.

Training has had a 4-axis device mesh (``parallel/mesh.py``), declarative
``PartitionSpec`` rules (``parallel/partition.py``), and multihost wiring
since the first PRs — but every serving executor compiled single-device,
so a fleet could only scale by whole-chip replicas. This module is the
bridge (docs/serving.md "Sharded serving"): a :class:`ServingMeshSpec`
resolves to a 2-axis serving mesh (``data`` × ``model``; fsdp/seq pinned
at 1) over an explicit **device subset** (:func:`~perceiver_io_tpu.
parallel.mesh.device_slice` — N replicas × M-device replicas, the second
scaling axis), and a :class:`ServingSharding` places the slot engine's
whole working set onto it:

- **params** — the Megatron TP rules (``infer_param_specs``): q/k/v and
  MLP-up kernels column-parallel on ``model``, o/MLP-down row-parallel,
  everything replicated across ``data``.
- **slot state** — the serving rule set
  (:data:`~perceiver_io_tpu.parallel.partition.SERVING_STATE_RULES`):
  slots/batch along ``data``; attention heads — dense per-slot caches,
  the paged pool's flat ``pool_k``/``pool_v``, staging caches — along
  ``model``. The pool's token dimension stays UNsharded across ``data``:
  block tables address one shared pool, so every data shard must see
  every page (cross-slot sharing is the paged layout's point).

The executors themselves stay the slot engine's: they compile under
``jax.jit`` **over the mesh** — committed sharded inputs plus pinned
``out_shardings`` make XLA GSPMD partition the computation and emit the
collectives (head-parallel attends, the o-projection all-reduce — the
``sharded_flash_attention``/``sharded_paged_attention`` shapes from
SNIPPETS.md [1], derived instead of hand-written), and
:func:`~perceiver_io_tpu.ops.paged_attention.gather_constraint` keeps the
paged gather's dense view head-sharded so the attend computes shard-local.
GSPMD guarantees semantics for ANY sharding, so exactness degrades
gracefully: a degenerate 1-device mesh compiles the identical program
(byte-identical behavior, pinned), and a real multi-device mesh is greedy
token-identical to the unsharded engine (the o-projection partial-sum
order is the only float difference; pinned on an 8-virtual-device CPU
mesh by ``tests/test_sharding.py``).

Mesh geometry is part of executor identity: the spec's fingerprint folds
into every slot-engine cache key and the compile ledger's components
(``mesh``), so a mesh flip REBUILDS and attributes instead of
silently reusing a single-device trace (docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perceiver_io_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_MODEL,
    AXIS_SEQ,
    MeshConfig,
    device_slice,
    make_mesh,
)
from perceiver_io_tpu.parallel.partition import (
    infer_param_specs,
    serving_state_spec,
    serving_state_specs,
)


@dataclasses.dataclass(frozen=True)
class ServingMeshSpec:
    """Declarative serving-mesh geometry: ``data`` × ``model`` devices at
    ``device_offset`` into the process's device list. ``data`` shards the
    slot/batch dimension (slots must divide evenly), ``model`` the
    attention heads and KV caches (heads must divide evenly); fsdp/seq are
    pinned at 1 — serving holds no optimizer state and the slot engine's
    context fits one shard's HBM by construction (the paged pool is the
    context-scaling lever).

    ``device_offset`` is the fleet hook: replica i of an M-device fleet
    resolves at offset ``i*M`` so replicas own disjoint subsets
    (:func:`fleet_mesh_specs`)."""

    data: int = 1
    model: int = 1
    device_offset: int = 0

    def __post_init__(self):
        if self.data < 1 or self.model < 1:
            raise ValueError(
                f"mesh axis sizes must be >= 1, got data={self.data} "
                f"model={self.model}"
            )
        if self.device_offset < 0:
            raise ValueError(
                f"device_offset must be >= 0, got {self.device_offset}"
            )

    @property
    def num_devices(self) -> int:
        return self.data * self.model

    def resolve(self, devices: Optional[Sequence[jax.Device]] = None
                ) -> "ServingSharding":
        """Claim the device subset and build the resolved sharding layer."""
        subset = device_slice(
            self.num_devices, offset=self.device_offset, devices=devices
        )
        mesh = make_mesh(
            MeshConfig(data=self.data, fsdp=1, model=self.model, seq=1),
            devices=subset,
        )
        return ServingSharding(self, mesh)


class ServingSharding:
    """A resolved serving mesh: placement + out-sharding helpers for the
    slot engine's executors. Constructed via :meth:`ServingMeshSpec.resolve`
    (or :func:`as_serving_sharding` from an existing 4-axis ``Mesh`` whose
    fsdp/seq axes are 1)."""

    def __init__(self, spec: ServingMeshSpec, mesh: Mesh):
        self.spec = spec
        self.mesh = mesh
        self.data_size = int(mesh.shape.get(AXIS_DATA, 1))
        self.model_size = int(mesh.shape.get(AXIS_MODEL, 1))
        self.num_devices = int(np.prod(tuple(mesh.shape.values())))
        #: (allocator, group) when this sharding came from a
        #: :class:`MeshGroupAllocator` — see :meth:`release`
        self._allocator_claim = None

    def release(self) -> None:
        """Free this sharding's :class:`MeshGroupAllocator` group claim
        explicitly (idempotent; no-op for shardings resolved directly).
        ``Replica.restart`` calls it on the crashed engine's sharding
        before re-running the factory, so the rebuild reclaims the crashed
        group deterministically instead of waiting for the garbage
        collector to clear the weakref."""
        claim = self._allocator_claim
        if claim is None:
            return
        self._allocator_claim = None
        allocator, group = claim
        ref = allocator._claims.get(group)
        if ref is not None and ref() is self:
            del allocator._claims[group]

    # -- identity ------------------------------------------------------------
    def fingerprint(self) -> Tuple:
        """Executor-cache key component: axis sizes + the concrete device
        ids. Device ids matter — two fleet replicas with the SAME geometry
        on DISJOINT subsets must not share a compiled executor whose
        shardings bake in the other replica's devices."""
        return (
            "mesh", self.data_size, self.model_size,
            tuple(int(d.id) for d in self.mesh.devices.flat),
        )

    def describe(self) -> str:
        """Ledger-component / stats rendering: ``data x model @ devices``."""
        first = int(self.mesh.devices.flat[0].id)
        return (
            f"{self.data_size}x{self.model_size}"
            f"@{self.num_devices}dev+{first}"
        )

    # -- shardings -----------------------------------------------------------
    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def state_shardings(self, state):
        """NamedSharding pytree for a slot-state dict (the serving rules)."""
        specs = serving_state_specs(state, self.mesh)
        return jax.tree_util.tree_map(self.named, specs)

    def leaf_sharding(self, name: str, shape: Tuple[int, ...]) -> NamedSharding:
        return self.named(serving_state_spec(name, tuple(shape), self.mesh))

    def table_sharding(self, slots: int, pages: int) -> NamedSharding:
        return self.leaf_sharding("table", (slots, pages))

    def tokens_sharding(self, slots: int) -> NamedSharding:
        return self.leaf_sharding("tokens", (slots,))

    def gathered_kv_spec(self) -> P:
        """Spec for the paged attend's transient dense (slots, heads, n, d)
        gather — slots along data, heads along model — applied inside
        :func:`~perceiver_io_tpu.ops.paged_attention.gather_kv` itself via
        :func:`~perceiver_io_tpu.ops.paged_attention.gather_constraint`
        (non-divisible dims dropped per shape, e.g. a batch-1 prefill
        gather) so NO gathered view materializes replicated — the decode
        step, the boundary step, and the prefill finalize alike."""
        return P(AXIS_DATA, AXIS_MODEL, None, None)

    # -- placement -----------------------------------------------------------
    def put_params(self, params):
        """Tensor-parallel param placement (``infer_param_specs``: Megatron
        TP rules on ``model``; fsdp is 1 so everything else replicates)."""
        specs = infer_param_specs(params, self.mesh)
        return jax.device_put(
            params, jax.tree_util.tree_map(self.named, specs)
        )

    def put_state(self, state):
        return jax.device_put(state, self.state_shardings(state))

    def put_leaf(self, name: str, value):
        return jax.device_put(
            value, self.leaf_sharding(name, np.shape(value))
        )


def as_serving_sharding(
    mesh: Union[None, ServingMeshSpec, ServingSharding, Mesh],
) -> Optional[ServingSharding]:
    """Coerce the slot engine's ``mesh=`` argument: None passes through
    (unsharded — today's exact code path), a spec resolves against the
    process's devices, an existing 4-axis ``Mesh`` is accepted when its
    fsdp/seq axes are 1 (the training-mesh reuse case)."""
    if mesh is None or isinstance(mesh, ServingSharding):
        return mesh
    if isinstance(mesh, ServingMeshSpec):
        return mesh.resolve()
    if isinstance(mesh, Mesh):
        shape = dict(mesh.shape)
        extra = {
            a: s for a, s in shape.items()
            if a not in (AXIS_DATA, AXIS_MODEL) and s > 1
        }
        if extra:
            raise ValueError(
                f"serving meshes use only ({AXIS_DATA!r}, {AXIS_MODEL!r}); "
                f"got extra axes {extra} — serving holds no optimizer state "
                f"to {AXIS_FSDP}-shard and no {AXIS_SEQ} ring"
            )
        spec = ServingMeshSpec(
            data=int(shape.get(AXIS_DATA, 1)),
            model=int(shape.get(AXIS_MODEL, 1)),
        )
        return ServingSharding(spec, mesh)
    raise TypeError(
        "mesh must be None, a ServingMeshSpec, a ServingSharding, or a "
        f"jax.sharding.Mesh, got {type(mesh).__name__}"
    )


def fleet_mesh_specs(
    spec: ServingMeshSpec,
    replicas: int,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Tuple[ServingMeshSpec, ...]:
    """Disjoint per-replica mesh specs: replica i at device offset
    ``spec.device_offset + i * spec.num_devices``. Validates the whole
    fleet fits the device budget up front (an over-subscribed fleet must
    fail at launch, not alias devices silently mid-scale-up)."""
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    per = spec.num_devices
    # validate the LAST slice; earlier ones are subsets of the budget
    device_slice(
        per, offset=spec.device_offset + (replicas - 1) * per, devices=devices
    )
    return tuple(
        dataclasses.replace(spec, device_offset=spec.device_offset + i * per)
        for i in range(replicas)
    )


class MeshGroupAllocator:
    """Hands each engine spawn a disjoint device group — the
    engine-factory form the serve CLI uses: every factory call (initial
    spawn, crash rebuild, autoscaler scale-up) ``acquire()``s the first
    FREE group of ``spec.num_devices`` devices.

    A group is busy while an engine built on it is alive: claims are
    weakrefs to the resolved :class:`ServingSharding` the engine holds for
    its lifetime, plus an explicit :meth:`ServingSharding.release` —
    ``Replica.restart`` releases the crashed engine's claim *before*
    re-running its factory, so the rebuild reclaims the crashed group
    deterministically instead of aliasing a live replica's devices (and a
    retired engine whose claim was never released explicitly frees it
    through the weakref when it is collected). Only when every group is
    claimed does the allocator wrap round-robin (documented, not an
    error: CPU-virtual devices alias harmlessly; size real pods so
    ``max_replicas x num_devices <= len(jax.devices())``)."""

    def __init__(self, spec: ServingMeshSpec, *,
                 devices: Optional[Sequence[jax.Device]] = None):
        all_devices = list(devices) if devices is not None else jax.devices()
        self.spec = spec
        self.groups = max(
            1, (len(all_devices) - spec.device_offset) // spec.num_devices
        )
        self._devices = devices
        self._claims: dict = {}  # group index -> weakref to its ServingSharding
        self._wrap = 0

    def acquire(self) -> "ServingSharding":
        """Resolve the first free group (round-robin wrap when none is)."""
        free = [
            i for i in range(self.groups)
            if (ref := self._claims.get(i)) is None or ref() is None
        ]
        if free:
            group = free[0]
        else:
            group = self._wrap % self.groups
            self._wrap += 1
        spec = dataclasses.replace(
            self.spec,
            device_offset=self.spec.device_offset
            + group * self.spec.num_devices,
        )
        sharding = spec.resolve(self._devices)
        self._claims[group] = weakref.ref(sharding)
        sharding._allocator_claim = (self, group)
        return sharding


# ---------------------------------------------------------------- probe main
def _probe_main(argv: Optional[list] = None) -> int:
    """Self-contained sharded-serving probe (``python -m
    perceiver_io_tpu.serving.sharding``): build a tiny CLM, serve ragged
    greedy prompts through a slot engine on the requested mesh, print ONE
    JSON line — tokens/s, per-shard resident bytes, the emitted tokens
    (what two runs are compared by), compile count. Run it twice (one
    device, then a virtual CPU mesh with the device count injected via
    ``XLA_FLAGS``) to compare the records."""
    import argparse
    import json
    import time

    parser = argparse.ArgumentParser(description=_probe_main.__doc__)
    parser.add_argument("--data", type=int, default=1)
    parser.add_argument("--model", type=int, default=1)
    parser.add_argument("--device-offset", type=int, default=0)
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--requests", type=int, default=8)
    parser.add_argument("--new-tokens", type=int, default=8)
    parser.add_argument("--kv-layout", default="dense",
                        choices=("dense", "paged", "paged_int8"))
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax.numpy as jnp

    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.inference.samplers import SamplingConfig
    from perceiver_io_tpu.models.text.clm import (
        CausalLanguageModel,
        CausalLanguageModelConfig,
    )
    # the canonical class, NOT this file's local binding: under
    # ``python -m perceiver_io_tpu.serving.sharding`` this module runs as
    # ``__main__`` while the engine isinstance-checks against the import
    # system's copy
    from perceiver_io_tpu.serving import (
        BucketTable,
        ServingMeshSpec as _CanonicalSpec,
        SlotServingEngine,
    )

    cfg = CausalLanguageModelConfig(
        vocab_size=93, max_seq_len=64, max_latents=16, num_channels=32,
        num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32), 16
    )["params"]
    gen = GenerationConfig(
        max_new_tokens=args.new_tokens, num_latents=4,
        sampling=SamplingConfig(temperature=0.0),
    )
    spec = _CanonicalSpec(
        data=args.data, model=args.model, device_offset=args.device_offset
    )
    engine = SlotServingEngine(
        model, params, gen, BucketTable(prompt_lens=(16, 32), batch_sizes=(1,)),
        slots=args.slots, mesh=spec, kv_layout=args.kv_layout,
    )
    compiles = engine.warmup()
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(1, 93, size=int(n)).astype(np.int32)
        for n in rng.integers(8, 28, size=args.requests)
    ]
    t0 = time.monotonic()
    outs = engine.serve(prompts)
    wall = time.monotonic() - t0
    stats = engine.stats()
    resident = int(stats.get("kv_pool", {}).get("resident_bytes", 0)) or int(
        engine.registry.gauge("kv_cache_resident_bytes") or 0
    )
    record = {
        "devices": len(jax.devices()),
        "mesh": {"data": args.data, "model": args.model},
        "kv_layout": engine.kv_layout,
        "compile_count": compiles,
        "tokens_generated": int(stats["tokens_generated"]),
        "tokens_per_s": round(stats["tokens_generated"] / max(wall, 1e-9), 2),
        "wall_s": round(wall, 3),
        "resident_bytes": resident,
        "per_shard_resident_bytes": resident // max(1, args.model),
        "tokens": [np.asarray(o).tolist() for o in outs],
    }
    print(json.dumps(record))
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    raise SystemExit(_probe_main())

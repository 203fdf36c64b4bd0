"""Parameter & batch partitioning rules.

Replaces the reference's FSDP wrap policy (``transformer_auto_wrap_policy``
over attention layers, reference ``perceiver/scripts/text/clm_fsdp.py:24-37``)
with declarative ``PartitionSpec`` rules — XLA GSPMD then emits the
all-gathers and reduce-scatters torch FSDP performs imperatively.

Two composable rule sets:

- **Tensor parallelism** (``model`` axis): attention head projections are
  sharded on the head dimension (q/k/v output, o input), the MLP on its
  hidden dimension. These are the canonical Megatron shardings, which make
  the two collectives per layer an all-reduce of activations.
- **FSDP** (``fsdp`` axis): every parameter's largest still-unsharded,
  evenly-divisible dimension is sharded. Parameters too small to split
  stay replicated (same effect as torch FSDP leaving small leaves in the
  root wrap unit).

The rules operate on flax param-path strings, so they apply uniformly to
every model family in :mod:`perceiver_io_tpu.models`.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perceiver_io_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_MODEL,
    AXIS_SEQ,
    BATCH_AXES,
)

# (path regex, dim) — dim of the kernel to shard over the `model` axis.
# Column-parallel (output dim): q/k/v projections, MLP up-projection.
# Row-parallel (input dim): attention output projection, MLP down-projection.
_TP_KERNEL_RULES: Tuple[Tuple[str, int], ...] = (
    # by head, whatever a head's width (the lm family's ``head_dim`` makes the
    # heads' channels another number than the inputs': q 2560 x 3584, o 3584 x
    # 2560) and whatever the layer's mask (full or a sliding window)
    (r"(q_proj|k_proj|v_proj)/kernel$", 1),
    (r"o_proj/kernel$", 0),
    (r"mlp/hidden/kernel$", 1),
    (r"mlp/out/kernel$", 0),
    # gated MLP (models/core/hybrid.py): gate and up by column, down by row;
    # the shared expert beside the routed ones is one
    (r"(mlp|shared_expert)/(gate|up)/kernel$", 1),
    (r"(mlp|shared_expert)/down/kernel$", 0),
    # latent attention (models/core/modules.py): the up-projections' columns
    # are by head; the low-rank down-projections and their norms are not split
    (r"(q_b_proj|kv_b_proj)/kernel$", 1),
    # stacked expert weights (experts, in, out): the experts' hidden width,
    # never the expert dimension
    (r"moe/(gate|up)$", 2),
    (r"moe/down$", 1),
    # no rule names a sparse layer's indexer (``indexer/wq``, ``wk``,
    # ``weights_proj``): its scores sum over all of its heads, so it stays
    # whole across ``model`` and only FSDP splits it
)

# Stacked expert weights: dim 0 counts experts, and a shard of it would be
# another placement of experts than the layer was told it holds
# (SparseExperts.expert_offset): neither ``model`` nor ``fsdp`` splits it.
_STACKED_EXPERTS = r"moe/(gate|up|down)$"

# Biases of column-parallel layers follow their kernel's output sharding;
# row-parallel biases stay replicated (added after the allreduce).
_TP_BIAS_RULES: Tuple[str, ...] = (
    r"(q_proj|k_proj|v_proj)/bias$",
    r"mlp/hidden/bias$",
)


def _tp_spec(path: str, shape: Tuple[int, ...], model_size: int) -> list:
    spec: list = [None] * len(shape)
    if model_size <= 1:
        return spec
    for pattern, dim in _TP_KERNEL_RULES:
        if re.search(pattern, path) and shape[dim] % model_size == 0:
            spec[dim] = AXIS_MODEL
            return spec
    for pattern in _TP_BIAS_RULES:
        if re.search(pattern, path) and shape[-1] % model_size == 0:
            spec[-1] = AXIS_MODEL
            return spec
    return spec


def infer_param_spec(
    path: str,
    value: Any,
    mesh: Mesh,
    *,
    min_fsdp_size: int = 2**14,
) -> P:
    """PartitionSpec for one parameter: TP rules first, then FSDP shards the
    largest remaining dimension. ``min_fsdp_size`` keeps tiny leaves (norms,
    biases) replicated — gathering them costs more than storing them."""
    shape = tuple(np.shape(value))
    spec = _tp_spec(path, shape, mesh.shape.get(AXIS_MODEL, 1))

    fsdp_size = mesh.shape.get(AXIS_FSDP, 1)
    if fsdp_size > 1 and np.size(value) >= min_fsdp_size:
        dims = sorted(range(len(shape)), key=lambda d: shape[d], reverse=True)
        if re.search(_STACKED_EXPERTS, path):
            dims = [d for d in dims if d != 0]
        for d in dims:
            if spec[d] is None and shape[d] % fsdp_size == 0:
                spec[d] = AXIS_FSDP
                break
    return P(*spec)


def _flatten_path(key_path) -> str:
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in key_path
    )


def infer_param_specs(params, mesh: Mesh, *, min_fsdp_size: int = 2**14):
    """Pytree of PartitionSpecs matching ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, v: infer_param_spec(
            _flatten_path(kp), v, mesh, min_fsdp_size=min_fsdp_size
        ),
        params,
    )


def param_shardings(params_or_specs, mesh: Mesh):
    """NamedShardings for a param pytree (or a pytree of PartitionSpecs)."""
    def to_sharding(leaf):
        spec = leaf if isinstance(leaf, P) else None
        if spec is None:
            raise TypeError("expected a pytree of PartitionSpec")
        return NamedSharding(mesh, spec)

    if all(isinstance(l, P) for l in jax.tree_util.tree_leaves(params_or_specs)):
        return jax.tree_util.tree_map(to_sharding, params_or_specs)
    specs = infer_param_specs(params_or_specs, mesh)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)


def shard_params(params, mesh: Mesh):
    """Place a (host or single-device) param pytree onto the mesh according
    to the inferred specs — the moment FSDP materializes its shards."""
    return jax.device_put(params, param_shardings(params, mesh))


# -- serving KV / slot-state rules (docs/serving.md "Sharded serving") ------
#
# The slot engine's persistent decode state (``serving/slots.py``) is the
# serving-side analogue of the param tree: named leaves with fixed layouts.
# The rules mirror the Megatron TP discipline above — attention heads (and
# everything keyed by them: dense per-slot caches, the paged pool's flat
# ``pool_k``/``pool_v``, the chunked-prefill staging caches) shard along
# ``model``; the slot/batch dimension shards along ``data``. Pool arrays are
# deliberately NOT data-sharded: block tables address one shared pool, so
# every data shard must see every page (sharing the pool across slots is the
# paged layout's whole point). A dimension that does not divide its axis
# falls back to replication on that dimension — same stance as the FSDP
# rule's small-leaf fallback.
#
# (name regex, per-dim axis template). Longest/most-specific first; matched
# against the leaf's path ("stack_k/0" for tuple entries).
SERVING_STATE_RULES: Tuple[Tuple[str, Tuple], ...] = (
    # (pool_tokens, heads, head_dim): shared across slots, heads sharded
    (r"^(pool_k|pool_v)$", (None, AXIS_MODEL, None)),
    # (pool_tokens, heads, 1) int8-layout dequant scales: they address by
    # the same (position, head) coordinates as the pool, so they shard
    # WITH their blocks along model (the trailing size-1 dim replicates)
    (r"^(scale_k|scale_v)$", (None, AXIS_MODEL, None)),
    # (1, heads, n, head_dim) batch-1 staging caches (chunked prefill)
    (r"^(stage_k|stage_v)$", (None, AXIS_MODEL, None, None)),
    # (slots, heads, n, head_dim) dense per-slot caches
    (r"^(cross_k|cross_v|stack_k|stack_v)(/\d+)?$",
     (AXIS_DATA, AXIS_MODEL, None, None)),
    # (slots, n) / (slots, vocab) / (slots, pages)
    (r"^(window|logits|table)$", (AXIS_DATA, None)),
    # (slots,) per-row vectors (and the decode step's token output)
    (r"^(pad|length|m|steps|tokens)$", (AXIS_DATA,)),
)


def serving_state_spec(name: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for one slot-state leaf by name. Unknown names and
    non-divisible dimensions replicate — the safe default; the sharded
    serving layer validates the load-bearing divisibilities (slots % data,
    heads % model) loudly at engine construction instead."""
    for pattern, template in SERVING_STATE_RULES:
        if re.search(pattern, name):
            spec: list = [None] * len(shape)
            for dim, axis in enumerate(template[: len(shape)]):
                if axis is None:
                    continue
                size = mesh.shape.get(axis, 1)
                if size > 1 and shape[dim] % size == 0:
                    spec[dim] = axis
            return P(*spec)
    return P()


def serving_state_specs(state, mesh: Mesh):
    """Pytree of PartitionSpecs matching a slot-engine state dict."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, v: serving_state_spec(
            _flatten_path(kp), tuple(np.shape(v)), mesh
        ),
        state,
    )


def batch_spec(
    mesh: Mesh, *, ndim: int = 2, shard_seq: bool = False, stacked_steps: bool = False
) -> P:
    """PartitionSpec for a batch array: leading dim over (data, fsdp), and
    optionally the sequence dim over ``seq`` (context parallelism).
    ``stacked_steps`` marks arrays with an extra leading steps dim — shape
    ``(n_steps, batch, ...)`` for the multi-step-in-jit train loop — which is
    scanned over, never sharded."""
    spec: list = [BATCH_AXES] + [None] * (ndim - 1)
    if stacked_steps:
        spec = [None, BATCH_AXES] + [None] * (ndim - 2)
    seq_dim = 2 if stacked_steps else 1
    if shard_seq and ndim > seq_dim and mesh.shape.get(AXIS_SEQ, 1) > 1:
        spec[seq_dim] = AXIS_SEQ
    return P(*spec)


def batch_sharding(
    mesh: Mesh, *, ndim: int = 2, shard_seq: bool = False, stacked_steps: bool = False
) -> NamedSharding:
    return NamedSharding(
        mesh, batch_spec(mesh, ndim=ndim, shard_seq=shard_seq, stacked_steps=stacked_steps)
    )


def shard_batch(batch, mesh: Mesh, *, shard_seq: bool = False, stacked_steps: bool = False):
    """Device-put a pytree of host batch arrays with batch-dim sharding.

    On multi-host pods, per-host arrays should instead be assembled with
    ``jax.make_array_from_process_local_data`` — see
    :mod:`perceiver_io_tpu.parallel.multihost`.
    """
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x,
            batch_sharding(
                mesh, ndim=np.ndim(x), shard_seq=shard_seq, stacked_steps=stacked_steps
            ),
        ),
        batch,
    )

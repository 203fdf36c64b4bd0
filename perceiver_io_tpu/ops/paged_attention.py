"""Paged KV gather/scatter helpers + the paged cross-attention dispatchers.

The slot engine's paged layout (``serving/kv_pool.py``, docs/serving.md)
keeps every resident's cross-attention k/v in ONE flat device pool of
shape ``(pool_tokens, heads, head_dim)``, addressed through per-slot
block tables. This module is the device-side address arithmetic, the
optional int8 quantization of pool rows, and the attention dispatchers
over that layout:

- **Gather reference (every backend).** Flatten the block table into
  per-position pool indices, ``jnp.take`` the pages back into a dense
  ``(b, h, n, d)`` view, and run the caller's attend. Because masking in
  :func:`~perceiver_io_tpu.ops.attention.dot_product_attention` is a
  ``where`` select on the fp32 logits, positions whose pages are
  unmapped (they gather null-block trash) contribute exactly what the
  dense layout's masked garbage contributes — nothing — so greedy output
  is **bitwise identical** to the dense layout (pinned by
  ``tests/test_paged_kv.py``). The gathered view is a transient XLA
  temp, not resident HBM; the persistent footprint is the pool.
  Sharing-transparent by construction: the gather addresses pages purely
  through the table, so two slots whose tables alias the SAME physical
  blocks (cross-request prefix sharing, docs/serving.md "Prefix
  sharing") read bitwise-identical values — no read-path change was
  needed for copy-on-write sharing, and the aliased-table parity is
  pinned by ``tests/test_prefix_cache.py``.
- **Ragged kernel (opt-in).** ``PERCEIVER_RAGGED_KERNEL=1`` dispatches
  :mod:`perceiver_io_tpu.ops.ragged_attention` — one Pallas kernel that
  consumes the block table and per-row lengths directly and reads only
  the live pages, for chunked-prefill rows (multi-query) and decode rows
  (single query) alike. Pallas-compiled on TPU, ``interpret=True``
  elsewhere so the tier-1 CPU suite exercises the same kernel body. The
  kernel's blockwise online softmax is exact but not bit-identical to
  the XLA einsum, so the gather path stays the bitwise oracle; the flag
  is folded into ``ragged_attention.trace_env`` so a mid-process
  toggle rebuilds the decode executors instead of silently reusing the
  other trace.

**Quantized pools** (``kv_layout="paged_int8"``, docs/serving.md
"Quantized KV"): pool rows are stored int8 with per-(position, head)
symmetric f32 scales carried in twin ``(pool_tokens, heads, 1)`` arrays
addressed by the SAME flat indices as the pool. :func:`scatter_kv`
quantizes at every append site (decode scatter, chunked-prefill stage,
prefix-share COW copy) and :func:`gather_kv` dequantizes into the
transient dense view, so the attend math itself stays full precision.
A never-written row has scale 0 and dequantizes to exactly 0.0 — never
NaN — which keeps null-block reads as harmless as the exact layout's
(pinned by ``tests/test_quant_kv.py``).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
import jax.numpy as jnp

#: trace-time sharding hint for the gather path's transient dense view
#: (docs/serving.md "Sharded serving"): the sharded slot engine sets it
#: around its decode executors' trace so the gathered (b, h, n, d) k/v
#: stay slot-sharded along ``data`` and head-sharded along ``model`` —
#: the attend computes shard-local and only the o-projection all-reduces
#: (the ``sharded_paged_attention`` shape, derived by GSPMD instead of a
#: hand-written shard_map). The ragged kernel reads the same hint to
#: derive its shard_map specs, so both read paths honor one constraint.
#: None (the default) changes nothing.
_GATHER_SHARDING: contextvars.ContextVar = contextvars.ContextVar(
    "paged_gather_sharding", default=None
)


@contextlib.contextmanager
def gather_constraint(sharding):
    """Install a ``NamedSharding`` constraint for the paged gather's dense
    view during an executor trace (no-op for ``None``). Trace-time only:
    the constraint is baked into the jitted program, so the context needs
    to be live when the executor's Python body runs, not per dispatch."""
    if sharding is None:
        yield
        return
    token = _GATHER_SHARDING.set(sharding)
    try:
        yield
    finally:
        _GATHER_SHARDING.reset(token)


def flat_position_indices(table: jnp.ndarray, block_size: int, n: int) -> jnp.ndarray:
    """Pool indices for token positions ``0..n-1`` through a block table.

    :param table: ``(..., pages)`` int32 block ids (0 = null block).
    :param block_size: token positions per block.
    :param n: positions to address (``<= pages * block_size``).
    :return: ``(..., n)`` int32 indices into the flat token-major pool.
    """
    pos = jnp.arange(n, dtype=jnp.int32)
    return table[..., pos // block_size] * block_size + pos % block_size


def flat_write_indices(table: jnp.ndarray, positions: jnp.ndarray,
                       block_size: int) -> jnp.ndarray:
    """Pool indices for per-row write ``positions``.

    :param table: ``(b, pages)`` int32 block table.
    :param positions: ``(b, ...)`` int32 token positions (each row indexes
        its own table row).
    :return: same shape as ``positions``, indices into the flat pool.
    """
    b = table.shape[0]
    rows = jnp.arange(b).reshape((b,) + (1,) * (positions.ndim - 1))
    return table[rows, positions // block_size] * block_size + positions % block_size


def quantize_kv(x: jnp.ndarray):
    """Per-(position, head) symmetric int8 quantization over head_dim.

    The scale is the row's absmax over the head_dim axis divided by 127,
    so dequantization is a single fused multiply and the worst-case
    relative error is bounded by the 8-bit grid. An all-zero row (a
    never-written pool position, or genuinely zero k/v) yields scale 0
    AND quantized 0 — the ``maximum(scale, eps)`` guard keeps the
    quantizing divide finite without shifting any nonzero row's grid.

    :param x: ``(..., d)`` values, any float dtype.
    :return: ``(q, scale)`` — int8 same shape as ``x``, f32 scale of
        shape ``x.shape[:-1] + (1,)``.
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.maximum(scale, 1e-30)), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def scatter_kv(pool: jnp.ndarray, scale: Optional[jnp.ndarray],
               flat_idx: jnp.ndarray, values: jnp.ndarray):
    """Append ``values`` into the pool at ``flat_idx``, quantizing when the
    layout carries scales — the ONE write primitive every paged append
    site flows through (decode scatter, boundary migrate+append, prefill
    finalize latent scatter, chunked-prefill stage), so the int8 layout
    cannot drift between sites.

    :param pool: ``(pool_tokens, h, d)`` flat pool (int8 or float).
    :param scale: ``(pool_tokens, h, 1)`` f32 scales, or None for the
        exact layout (then values are cast to the pool dtype, the
        pre-quantization behavior, bitwise unchanged).
    :param flat_idx: ``(...,)`` int32 flat pool indices.
    :param values: ``flat_idx.shape + (h, d)`` new k or v rows.
    :return: ``(pool, scale)`` with the rows written (scale None in the
        exact layout).
    """
    if scale is None:
        return pool.at[flat_idx].set(values.astype(pool.dtype)), None
    q, s = quantize_kv(values)
    return pool.at[flat_idx].set(q), scale.at[flat_idx].set(s.astype(scale.dtype))


def _constrain_gather(x: jnp.ndarray) -> jnp.ndarray:
    """Apply the installed :func:`gather_constraint` to one gathered dense
    view, dropping any dim the constraint cannot shard (a batch-1 prefill
    gather keeps its heads sharded while its slot dim replicates)."""
    constraint = _GATHER_SHARDING.get()
    if constraint is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    mesh, spec = constraint.mesh, constraint.spec
    dims = []
    for i in range(x.ndim):
        axis = spec[i] if i < len(spec) else None
        size = int(mesh.shape.get(axis, 1)) if axis is not None else 1
        dims.append(axis if size > 1 and x.shape[i] % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*dims))
    )


def gather_kv(
    pool: jnp.ndarray,
    flat_idx: jnp.ndarray,
    scale: Optional[jnp.ndarray] = None,
    out_dtype=None,
) -> jnp.ndarray:
    """Gather pool rows into a dense per-slot view, dequantizing when the
    layout carries scales.

    Every gather-path caller — the decode step, the boundary-phase step
    and the prefill finalize in ``inference/generate.py`` — flows through
    here, so the :func:`gather_constraint` sharding hint covers ALL paged
    gathers: on a serving mesh the transient view stays slot/head-sharded
    instead of all-gathering the model-sharded pool.

    :param pool: ``(pool_tokens, h, d)`` flat token-major pool.
    :param flat_idx: ``(b, n)`` indices from :func:`flat_position_indices`.
    :param scale: ``(pool_tokens, h, 1)`` f32 scales for the int8 layout
        (gathered by the same indices; ``int8 * f32`` in f32 — a zero
        scale dequantizes to exactly 0.0, never a 0/0 NaN).
    :param out_dtype: cast the dequantized view to this dtype (the attend
        compute dtype); ignored for the exact layout.
    :return: ``(b, h, n, d)`` dense view (transient).
    """
    g = jnp.take(pool, flat_idx, axis=0)
    if scale is not None:
        s = jnp.take(scale, flat_idx, axis=0)
        g = g.astype(jnp.float32) * s.astype(jnp.float32)
        if out_dtype is not None:
            g = g.astype(out_dtype)
    return _constrain_gather(g.transpose(0, 2, 1, 3))


def _ragged_kernel_attention(
    q, pool_k, pool_v, table, lengths, *, block_size, scale_k, scale_v, project_out
):
    """Dispatch the ragged kernel + output projection, or None when the
    kernel is not enabled (caller degrades to the gather reference)."""
    from perceiver_io_tpu.ops import ragged_attention as ragged

    if not ragged.kernel_requested():
        return None
    o = ragged.ragged_paged_attention(
        q, pool_k, pool_v, table, lengths,
        block_size=block_size, scale_k=scale_k, scale_v=scale_v,
    )
    return project_out(o.astype(q.dtype))


def paged_decode_attention(
    attend,
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    table: jnp.ndarray,
    *,
    block_size: int,
    n: int,
    pad_mask: jnp.ndarray,
    lengths: Optional[jnp.ndarray] = None,
    scale_k: Optional[jnp.ndarray] = None,
    scale_v: Optional[jnp.ndarray] = None,
    project_out=None,
) -> jnp.ndarray:
    """One decode step's cross attention over the paged pool.

    :param attend: the caller's attend (``mha.attend`` — the SAME callable
        the dense layout runs, for bitwise parity on the gather path; it
        includes the output projection).
    :param q: ``(b, h, 1, d)`` pre-scaled, pre-rotated query.
    :param pool_k/pool_v: ``(pool_tokens, h, d)`` flat pools.
    :param table: ``(b, pages)`` block table rows for these b slots.
    :param block_size: pool block size in token positions.
    :param n: dense context length being addressed.
    :param pad_mask: ``(b, n)`` True = masked (the future/pad mask the
        dense attend uses).
    :param lengths: ``(b,)`` valid-token counts INCLUDING the position
        written this step — only the kernel path consumes it (the gather
        path's masking comes entirely from ``pad_mask``).
    :param scale_k/scale_v: int8-layout dequant scales, or None.
    :param project_out: ``mha.project_out`` — applies the output
        projection to the kernel's raw ``(b, h, q, d)`` attention (the
        gather path's ``attend`` already includes it). Required for the
        kernel path.
    :return: ``(b, h_out)``-projected attention output, same as attend's.
    """
    if lengths is not None and project_out is not None:
        out = _ragged_kernel_attention(
            q, pool_k, pool_v, table, lengths.astype(jnp.int32),
            block_size=block_size, scale_k=scale_k, scale_v=scale_v,
            project_out=project_out,
        )
        if out is not None:
            return out
    flat = flat_position_indices(table, block_size, n)
    out_dtype = q.dtype if scale_k is not None else None
    k = gather_kv(pool_k, flat, scale_k, out_dtype)  # gather_constraint applies inside
    v = gather_kv(pool_v, flat, scale_v, out_dtype)
    return attend(q, k, v, pad_mask=pad_mask, deterministic=True)


def paged_window_attention(
    attend,
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    table: jnp.ndarray,
    *,
    block_size: int,
    n: int,
    pad_count: jnp.ndarray,
    scale_k: Optional[jnp.ndarray] = None,
    scale_v: Optional[jnp.ndarray] = None,
    project_out=None,
) -> jnp.ndarray:
    """Window-aligned cross attention for the multi-query paged phases
    (prefill finalize, boundary step): the latent queries attend the whole
    ``n``-slot window, front-padded by ``pad_count`` garbage slots the pad
    mask removes.

    Gather path: position ``i`` reads pool position ``max(i - pad, 0)``
    (pads re-read position 0 and are masked) — bitwise identical to the
    dense layout's aligned gather, with ``attend`` applying the
    right-aligned causal mask ``j <= i + (j_len - i_len)`` in slot space.
    Kernel path: dropping the pad slots shifts both keys and queries left
    by ``pad``, so the slot-space causal mask becomes the kernel's
    position-space bound (query ``i`` sees positions
    ``<= lengths[r] - q_len + i``) over the CONTIGUOUS live span
    ``[0, n - pad_count)`` — exactly the block-table + lengths contract
    the decode rows use, which is what lets ONE kernel serve both row
    shapes (q length 1 or ``max_latents``) with no per-phase variant.

    :param pad_count: ``(b,)`` leading pad slots per row.
    :return: projected attention output (same contract as ``attend``'s).
    """
    lengths = (n - pad_count).astype(jnp.int32)
    if project_out is not None:
        out = _ragged_kernel_attention(
            q, pool_k, pool_v, table, lengths,
            block_size=block_size, scale_k=scale_k, scale_v=scale_v,
            project_out=project_out,
        )
        if out is not None:
            return out
    slot_abs = jnp.maximum(jnp.arange(n)[None, :] - pad_count[:, None], 0)
    flat_g = flat_write_indices(table, slot_abs, block_size)
    out_dtype = q.dtype if scale_k is not None else None
    k_slots = gather_kv(pool_k, flat_g, scale_k, out_dtype)
    v_slots = gather_kv(pool_v, flat_g, scale_v, out_dtype)
    pad_mask = jnp.arange(n)[None, :] < pad_count[:, None]
    return attend(q, k_slots, v_slots, pad_mask=pad_mask, deterministic=True)

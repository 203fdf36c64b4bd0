"""Scaled dot-product attention — the single attention primitive shared by all
Perceiver cross-/self-attention modules.

Capability parity with reference ``perceiver/model/core/modules.py:84-154``:
optional causal masking of right-aligned q/kv of unequal length, boolean key
pad masking, attention-matrix dropout, and a ``max_heads_parallel`` knob that
bounds peak memory by serializing over head groups.

TPU-first design notes:
- logits/softmax always computed in float32 regardless of input dtype
  (bf16 q/k/v stay bf16 for the matmuls feeding the MXU; the softmax runs on
  the VPU in fp32 for numerical parity with the reference).
- masks are applied as ``where(mask, -inf_min, logits)`` selects on the fp32
  logits; XLA fuses them into the softmax.
- ``impl='flash'`` dispatches to the Pallas flash kernel
  (:mod:`perceiver_io_tpu.ops.flash_attention`) when shapes permit;
  ``impl='xla'`` is the reference-semantics einsum path. ``'auto'`` picks
  flash on TPU. A shape with at least one block of query rows that the
  kernel refuses is counted (``attention_einsum_fallback_total`` on the
  default registry) and warned about once, so the einsum path never stands
  in for the kernel in silence; shorter queries (single-token decode steps)
  are the einsum path's by design.
- Mosaic kernels are not partitioned automatically, so under a mesh with
  more than one device the flash call runs inside ``jax.shard_map``: batch
  over ``data``/``fsdp``, heads over ``model``.
- ``impl='ring'`` dispatches to ring attention
  (:mod:`perceiver_io_tpu.parallel.ring`): q and k/v sequence dims are
  sharded over the ambient mesh's ``seq`` axis and k/v chunks rotate via
  ``ppermute`` — context parallelism for sequences one device cannot hold.

The ambient mesh is the one ``jax.sharding.get_abstract_mesh()`` reports.
``make_train_step``/``make_eval_step`` and the sharded slot engine publish
theirs (``jax.sharding.use_abstract_mesh``) while they trace; other callers
wrap the call in ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def _mask_value() -> float:
    return float(jnp.finfo(jnp.float32).min)


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    pad_mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    max_heads_parallel: Optional[int] = None,
    impl: str = "auto",
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Attention over pre-projected (and pre-scaled, pre-rotated) heads.

    :param q: ``(b, h, i, ck)`` queries — already multiplied by ``ck**-0.5``
        and rotary-rotated by the caller (mirroring the reference's order of
        operations, ``modules.py:104-115``): ``MultiHeadAttention._finish_q``
        scales and rotates the projection's ``(b, i, h * ck)`` output before
        it splits the heads.
    :param k: ``(b, hk, j, ck)`` keys (rotary-rotated by the caller,
        ``MultiHeadAttention._finish_k``, likewise before the head split).
        ``hk`` may divide ``h`` (grouped-query attention): query head ``n``
        reads key-value head ``n // (h // hk)``; neither path repeats k or v.
    :param v: ``(b, hk, j, cv)`` values.
    :param pad_mask: optional boolean ``(b, j)``; **True marks padding** (the
        reference's convention, ``modules.py:97``).
    :param causal: apply right-aligned causal masking.
    :param dropout_rate: dropout on the post-softmax attention matrix.
    :param max_heads_parallel: process at most this many heads at once
        (memory bound); ``None`` = all heads.
    :param impl: ``'auto' | 'xla' | 'flash'``.
    :param window: with ``causal``, a sliding window: query ``t`` sees key
        ``s`` iff ``0 <= t + (j - i) - s < window`` (its own position counts).
        Both paths apply it; the kernels skip the block pairs outside the band.
    :return: ``(b, h, i, cv)``.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window} needs causal=True and at least one key")
    _declare_selection_counter()
    if impl == "ring":
        if dropout_rate > 0.0:
            raise ValueError("ring attention does not support attention dropout")
        if window is not None:
            raise ValueError("ring attention does not support a sliding window")
        mesh = _ambient_mesh()
        if mesh is None or "seq" not in mesh.axis_names or mesh.shape["seq"] == 1:
            # No seq-sharded mesh in scope (e.g. model.init outside the mesh
            # context): ring is numerically identical to the einsum path, so
            # degrade gracefully instead of failing.
            import warnings

            warnings.warn(
                "impl='ring' without an ambient mesh with a 'seq' axis of "
                "size > 1 — falling back to the XLA einsum path; wrap the "
                "call in `with jax.set_mesh(make_mesh(MeshConfig(seq=...))):` "
                "for sequence-parallel execution",
                UserWarning,
                stacklevel=2,
            )
        else:
            from perceiver_io_tpu.parallel.ring import ring_attention_sharded

            return ring_attention_sharded(
                q, k, v, mesh, axis_name="seq", pad_mask=pad_mask, causal=causal
            )

    if impl == "flash" or (impl == "auto" and _flash_eligible(dropout_rate)):
        from perceiver_io_tpu.ops import flash_attention

        if impl == "flash" and dropout_rate > 0.0:
            raise ValueError("flash attention does not support attention dropout")
        if flash_attention.supported(q, k, v, causal=causal, window=window):
            return _flash_over_mesh(q, k, v, pad_mask, causal, window)
        if impl == "flash":
            raise ValueError(
                f"flash attention requested but unsupported for shapes q={q.shape} k={k.shape}"
            )
        if q.shape[2] >= flash_attention.LANES:
            _count_einsum_fallback(q, k, v, causal)

    num_heads = q.shape[1]
    if k.shape[1] != num_heads:
        return _attention_xla_grouped(q, k, v, pad_mask, causal, dropout_rate, dropout_rng, window)
    if max_heads_parallel is None or max_heads_parallel >= num_heads:
        return _attention_xla(q, k, v, pad_mask, causal, dropout_rate, dropout_rng, window=window)

    chunks = []
    for h0 in range(0, num_heads, max_heads_parallel):
        h1 = min(h0 + max_heads_parallel, num_heads)
        rng = None
        if dropout_rng is not None:
            dropout_rng, rng = jax.random.split(dropout_rng)
        chunks.append(
            _attention_xla(
                q[:, h0:h1], k[:, h0:h1], v[:, h0:h1], pad_mask, causal, dropout_rate, rng,
                window=window,
            )
        )
    return jnp.concatenate(chunks, axis=1)


def selected_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bits: jnp.ndarray,
    *,
    pad_mask: Optional[jnp.ndarray] = None,
    impl: str = "auto",
):
    """Causal attention of each query over the keys a learned selection
    keeps (``ops/sparse_attention.py``), and its log-sum-exp: ``(o, lse)``,
    ``lse`` ``(b, h, i)`` float32 without a gradient. ``bits`` is the packed
    ``(b, i / 32, j)`` selection. The flash kernels take it where they take
    the shapes (``impl`` as in :func:`dot_product_attention`); the einsum
    path holds the ``(b, h, i, j)`` scores whole. Counted at trace time in
    ``sparse_attention_call_total``."""
    from perceiver_io_tpu.observability import default_registry
    from perceiver_io_tpu.ops import sparse_attention

    _declare_selection_counter()
    default_registry().inc(sparse_attention.SELECTION_COUNTER)
    if impl == "flash" or (impl == "auto" and _flash_eligible(0.0)):
        from perceiver_io_tpu.ops import flash_attention

        if (flash_attention.supported(q, k, v, causal=True)
                and flash_attention.selection_blocks_fit(q.shape[2])):
            return _flash_over_mesh(q, k, v, pad_mask, True, selection=bits)
        if impl == "flash":
            raise ValueError(
                f"flash attention with a selection is unsupported for shapes q={q.shape} k={k.shape}")
        if q.shape[2] >= flash_attention.LANES:
            _count_einsum_fallback(q, k, v, True)
    return sparse_attention.attention_xla(q, k, v, bits, pad_mask)


def _declare_selection_counter() -> None:
    """Trace time: every attention call declares the selection counter, so a
    program whose layers all ran dense exports it at 0."""
    from perceiver_io_tpu.observability import default_registry
    from perceiver_io_tpu.ops.sparse_attention import SELECTION_COUNTER

    default_registry().declare_counters(SELECTION_COUNTER)


def _ambient_mesh():
    """The mesh of the enclosing ``jax.set_mesh`` /
    ``jax.sharding.use_abstract_mesh`` context, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _flash_over_mesh(q, k, v, pad_mask, causal, window=None, selection=None):
    """The flash kernel, inside ``shard_map`` when the ambient mesh has more
    than one device: batch over the ``data``/``fsdp`` axes, heads over
    ``model``. A dim its axes do not divide stays replicated (a batch-1
    prefill on a data-sharded serving mesh). With ``selection`` (packed bits,
    by batch) the selected kernels and ``(o, lse)``."""
    from jax.sharding import PartitionSpec as P

    from perceiver_io_tpu.ops.flash_attention import flash_attention, flash_attention_selected
    from perceiver_io_tpu.parallel.mesh import AXIS_MODEL, AXIS_SEQ, BATCH_AXES

    mesh = _ambient_mesh()
    if mesh is None or mesh.size == 1:
        if selection is not None:
            return flash_attention_selected(q, k, v, selection, pad_mask=pad_mask)
        return flash_attention(q, k, v, pad_mask=pad_mask, causal=causal, window=window)
    if mesh.shape.get(AXIS_SEQ, 1) > 1:
        raise ValueError(
            "flash attention cannot run on a mesh whose 'seq' axis is sharded "
            f"({dict(mesh.shape)}); use attention_impl='ring' for sequence "
            "parallelism"
        )

    def dividing(axes, size):
        axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
        shards = math.prod(mesh.shape[a] for a in axes)
        return axes if axes and size % shards == 0 else None

    batch_ax = dividing(BATCH_AXES, q.shape[0])
    # grouped heads: a shard of query heads needs its own key-value heads
    head_ax = dividing((AXIS_MODEL,), k.shape[1])
    qkv_spec = P(batch_ax, head_ax, None, None)
    args, in_specs = (q, k, v), (qkv_spec,) * 3
    if pad_mask is not None:
        args, in_specs = args + (pad_mask,), in_specs + (P(batch_ax, None),)
    if selection is not None:
        args, in_specs = args + (selection,), in_specs + (P(batch_ax, None, None),)

        def body(q_, k_, v_, *rest):
            pad_ = rest[0] if pad_mask is not None else None
            return flash_attention_selected(q_, k_, v_, rest[-1], pad_mask=pad_)

        out_specs = (qkv_spec, P(batch_ax, head_ax, None))
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                             check_vma=False)(*args)

    def body(q_, k_, v_, pad_=None):
        return flash_attention(q_, k_, v_, pad_mask=pad_, causal=causal, window=window)

    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec, check_vma=False
    )(*args)


def _count_einsum_fallback(q, k, v, causal) -> None:
    """Record that ``impl='auto'`` on a TPU left a shape to the einsum path
    because the flash kernel does not support it. Runs at trace time, so
    once per traced shape."""
    import warnings

    from perceiver_io_tpu.observability import default_registry

    default_registry().inc("attention_einsum_fallback_total")
    warnings.warn(
        f"flash attention does not support q={q.shape} k={k.shape} v={v.shape} "
        f"dtype={q.dtype} causal={causal}; this call runs on the einsum path",
        RuntimeWarning,
        stacklevel=3,
    )


def _flash_eligible(dropout_rate: float) -> bool:
    """What ``impl='auto'`` asks before the kernel's own ``supported()``:
    flash on a TPU and without attention dropout (the reference's default is
    0.0 everywhere; a training config that enables it takes the einsum path)."""
    return dropout_rate == 0.0 and jax.default_backend() == "tpu"


def _attention_xla(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    pad_mask: Optional[jnp.ndarray],
    causal: bool,
    dropout_rate: float,
    dropout_rng: Optional[jax.Array],
    causal_rows: Optional[int] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    i, j = q.shape[-2], k.shape[-2]
    logits = jnp.einsum("bhic,bhjc->bhij", q, k, preferred_element_type=jnp.float32)
    logits = logits.astype(jnp.float32)

    if pad_mask is not None:
        logits = jnp.where(pad_mask[:, None, None, :], _mask_value(), logits)
    if causal:
        allowed = _causal_allowed(jnp.arange(i), j, j - i, window)
        logits = jnp.where(allowed[None, None], logits, _mask_value())
    elif causal_rows is not None:
        # grouped heads folded into the rows: row r is position r % causal_rows
        allowed = _causal_allowed(jnp.arange(i) % causal_rows, j, j - causal_rows, window)
        logits = jnp.where(allowed[None, None], logits, _mask_value())

    attn = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, attn.shape)
        attn = jnp.where(keep, attn / (1.0 - dropout_rate), 0.0)
    attn = attn.astype(v.dtype)
    return jnp.einsum("bhij,bhjc->bhic", attn, v)


def _causal_allowed(pos, j: int, offset: int, window: Optional[int]):
    """``(rows, j)``: key ``s`` is allowed for the query at ``pos`` iff ``s <=
    pos + offset`` and, under a window, ``s > pos + offset - window``."""
    cols, last = jnp.arange(j)[None, :], pos[:, None] + offset
    allowed = cols <= last
    return allowed if window is None else allowed & (cols > last - window)


def _attention_xla_grouped(q, k, v, pad_mask, causal, dropout_rate, dropout_rng, window=None):
    """The einsum path with fewer key-value heads than query heads: the
    query heads are viewed ``(b, hk, h // hk, i, c)`` and each group
    contracts with its one key-value head, so k and v keep ``hk`` heads."""
    b, h, i, c = q.shape
    hk = k.shape[1]
    if h % hk:
        raise ValueError(f"{h} query heads are not a multiple of {hk} key-value heads")
    # fold the group into the query rows: (b, hk, g * i, c) against (b, hk, j, c)
    o = _attention_xla(
        q.reshape(b, hk, (h // hk) * i, c), k, v, pad_mask, False, dropout_rate, dropout_rng,
        causal_rows=i if causal else None, window=window,
    )
    return o.reshape(b, h, i, v.shape[-1])

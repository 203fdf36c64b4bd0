"""One ragged paged-attention kernel for mixed prefill/decode rows.

The gather reference in :mod:`perceiver_io_tpu.ops.paged_attention`
materializes a dense ``(b, h, n, d)`` view of every row's FULL window —
``n`` positions of HBM traffic per step regardless of how few tokens the
row actually holds. This module is the ragged alternative (the "Ragged
Paged Attention" TPU kernel design, PAPERS.md): ONE Pallas kernel that
consumes the block table and per-row lengths directly, reads only the
mapped pages, and computes a blockwise online softmax over the live span
``[0, lengths[r])`` under the Perceiver-AR right-aligned causal
contract: query ``i`` of a ``q_len``-query row sits at position
``lengths[r] - q_len + i`` and sees only positions up to its own. Rows
are ragged in two senses and the kernel handles both in one launch:

- **decode rows**: a single query token (``q_len = 1``) over however
  many positions the row has accumulated;
- **chunked-prefill / boundary rows**: the full latent segment
  (``q_len = max_latents``) over the row's prompt span.

Both phases call the SAME kernel body — only the ``q_len`` of the
launch's q block differs — so there are no per-phase kernel variants and
the engine's compile bound is unchanged (pinned by
``tests/test_ragged_attention.py``).

Backend policy: compiled by Mosaic when the program is lowered for a TPU,
run by the Pallas interpreter on any other platform
(:func:`~perceiver_io_tpu.ops.flash_attention.pallas_call_on_lowering_platform`), so
the tier-1 CPU suite executes the same kernel body. The kernel's
online softmax is exact but not bitwise-equal to the XLA einsum, so the
gather reference remains the bitwise oracle and the kernel is opt-in via
``PERCEIVER_RAGGED_KERNEL=1`` (folded into :func:`trace_env`, which keys
every executor cache, + the CompileLedger ``kv_layout`` component, so flips
rebuild and attribute instead of silently reusing a stale trace).

Quantized pools: optional per-(position, head) f32 scales ride along as
two more page-blocked inputs and the dequantize multiply happens inside
the kernel, on the one page actually being processed — int8 HBM traffic,
f32 math (docs/serving.md "Quantized KV").

Sharding: the kernel honors the SAME
:func:`~perceiver_io_tpu.ops.paged_attention.gather_constraint` hint the
gather path uses — rows shard along the constraint's first (data) axis,
heads along its second (model) axis, pages replicated — via an explicit
``shard_map``, so the sharded slot engine (docs/serving.md "Sharded
serving") can flip the kernel on without touching its mesh plumbing.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops.flash_attention import pallas_call_on_lowering_platform

#: trace-time env flag enabling the ragged kernel on every paged read
#: path (see module docstring; folded into :func:`trace_env`)
ENV_KERNEL = "PERCEIVER_RAGGED_KERNEL"

#: number of times a kernel launch was TRACED this process — a retrace
#: probe for tests (steady-state decode must not grow it), not a metric;
#: the serving engine's dispatch counter is ``kv_ragged_kernel_steps_total``
TRACE_COUNT = 0


def kernel_requested() -> bool:
    """Normalized read of :data:`ENV_KERNEL` (read while a program is
    traced; unset, ``"0"`` and anything but ``"1"`` are one setting). Not
    TPU-gated: other backends run the same kernel body under the Pallas
    interpreter, so the flag in the CPU test suite exercises the real path."""
    return os.environ.get(ENV_KERNEL, "0") == "1"


def trace_env() -> tuple:
    """What of the environment a traced program depends on, as one hashable
    tuple: the generation, beam and slot executor caches and the decode
    strategy registry key on it, and the compile ledger attributes a rebuild
    it caused as ``trace_env``. A mid-process flip of :data:`ENV_KERNEL`
    rebuilds those executors; flipping back hits the first ones."""
    return (kernel_requested(),)


def _make_kernel(block_size: int, pages: int, quantized: bool):
    """Build the kernel body for one (block_size, pages-per-row, layout)
    geometry. ``pages`` is baked in so the final-page epilogue is a
    trace-time predicate; the grid iterates pages minor, so the scratch
    accumulators carry one row's running softmax across its pages."""

    def kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest):
        if quantized:
            sk_ref, sv_ref, o_ref, m_ref, l_ref, acc_ref = rest
        else:
            o_ref, m_ref, l_ref, acc_ref = rest
        r = pl.program_id(0)
        p = pl.program_id(1)

        @pl.when(p == 0)
        def _init():
            # finite sentinel, not -inf: exp(m_prev - m_new) must stay
            # well-defined for rows whose every position is masked
            m_ref[...] = jnp.full(m_ref.shape, -1e30, m_ref.dtype)
            l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

        q = q_ref[0].astype(jnp.float32)            # (h, q_len, d)
        k = k_ref[0].astype(jnp.float32)            # (block_size, h, d)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # dequant on the page in registers: int8 HBM reads, f32 math;
            # zero scale (never-written row) multiplies to exactly 0.0
            k = k * sk_ref[0].astype(jnp.float32)
            v = v * sv_ref[0].astype(jnp.float32)
        k = k.transpose(1, 0, 2)                    # (h, block_size, d)
        v = v.transpose(1, 0, 2)

        # q arrives pre-scaled by ck**-0.5 (the projection applies it);
        # the kernel adds no scale of its own — same as the einsum path
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                           # (h, q_len, block_size)
        pos = p * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # right-aligned causal bound, matching the dense attend's
        # `j <= i + (j_len - i_len)` (ops/attention.py): query qi of a
        # window row sits at position lengths[r] - q_len + qi and may not
        # see the later latents' entries; q_len = 1 decode rows reduce to
        # the plain live-span mask pos < lengths[r]
        q_len = s.shape[1]
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (pos + (q_len - 1) - qi) < len_ref[r]
        s = jnp.where(valid, s, -1e30)

        m_prev = m_ref[...]                         # (h, q_len)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zeroing, not exp(-1e30 - m): a fully-masked page must
        # contribute exactly nothing to l and acc
        probs = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(probs, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
            probs, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

        @pl.when(p == pages - 1)
        def _emit():
            # l == 0 (an idle row with length <= 0) divides the zero acc
            # by the epsilon: finite zeros, discarded by write routing
            o_ref[0] = (
                acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
            ).astype(o_ref.dtype)

    return kernel


def _launch(q, k_pages, v_pages, table, lengths, scales, *, block_size):
    """One pallas_call over grid (rows, pages-per-row). Scalar-prefetched
    table/lengths drive the page index maps, so each step fetches exactly
    the row's mapped page — the ragged read the gather path lacks."""
    b, h, q_len, d = q.shape
    pages = table.shape[1]
    quantized = scales is not None

    row_map = lambda r, p, tbl, lens: (r, 0, 0, 0)
    page_map = lambda r, p, tbl, lens: (tbl[r, p], 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, h, q_len, d), row_map),
        pl.BlockSpec((1, block_size, h, d), page_map),
        pl.BlockSpec((1, block_size, h, d), page_map),
    ]
    inputs = [q, k_pages, v_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, block_size, h, 1), page_map),
            pl.BlockSpec((1, block_size, h, 1), page_map),
        ]
        inputs += list(scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, q_len, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((h, q_len), jnp.float32),      # running max
            pltpu.VMEM((h, q_len), jnp.float32),      # running denominator
            pltpu.VMEM((h, q_len, d), jnp.float32),   # running numerator
        ],
    )

    return pallas_call_on_lowering_platform(
        _make_kernel(block_size, pages, quantized),
        table, lengths, *inputs,
        name="ragged_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, q_len, d), q.dtype),
    )


def ragged_paged_attention(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    block_size: int,
    scale_k: Optional[jnp.ndarray] = None,
    scale_v: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Ragged paged attention over the flat pool.

    :param q: ``(b, h, q_len, d)`` pre-scaled, pre-rotated queries —
        ``q_len`` is 1 for decode rows, ``max_latents`` for prefill
        finalize / boundary rows; both shapes run this same kernel.
    :param pool_k/pool_v: ``(pool_tokens, h, d)`` flat token-major pools
        (int8 when scales are given; ``pool_tokens`` must be a multiple
        of ``block_size`` — the pool is allocated in whole blocks).
    :param table: ``(b, pages)`` int32 block ids (0 = null block; rows
        attend only ``[0, lengths[r])`` — right-aligned causally for
        multi-query rows, matching the dense attend's
        ``j <= i + (j_len - i_len)`` mask — so unmapped tail pages read
        the null block and are masked by the length predicate).
    :param lengths: ``(b,)`` int32 live-span lengths; ``<= 0`` rows
        produce all-zero output (idle slots, discarded by the engine's
        write routing).
    :param scale_k/scale_v: optional ``(pool_tokens, h, 1)`` f32 dequant
        scales (the int8 layout).
    :return: ``(b, h, q_len, d)`` raw attention (NO output projection —
        the caller applies ``mha.project_out``; the gather reference's
        ``attend`` includes it).
    """
    global TRACE_COUNT
    TRACE_COUNT += 1
    tokens, h, d = pool_k.shape
    if tokens % block_size:
        raise ValueError(
            f"pool_tokens={tokens} not a multiple of block_size={block_size}"
        )
    pages_total = tokens // block_size
    k_pages = pool_k.reshape(pages_total, block_size, h, d)
    v_pages = pool_v.reshape(pages_total, block_size, h, d)
    scales = None
    if scale_k is not None:
        scales = (
            scale_k.astype(jnp.float32).reshape(pages_total, block_size, h, 1),
            scale_v.astype(jnp.float32).reshape(pages_total, block_size, h, 1),
        )
    table = table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    launch = functools.partial(_launch, block_size=block_size)

    from perceiver_io_tpu.ops import paged_attention as paged  # cycle-free: lazy

    constraint = paged._GATHER_SHARDING.get()
    if constraint is None:
        return launch(q, k_pages, v_pages, table, lengths, scales)

    # Same placement the gather constraint encodes for its (b, h, n, d)
    # view: rows along the data axis, heads along the model axis, pool
    # pages replicated... but shard_map needs exact divisibility, so any
    # non-divisible dim degrades to replicated (the _constrain_gather
    # discipline).
    mesh, spec = constraint.mesh, constraint.spec

    def _axis(i, size):
        ax = spec[i] if i < len(spec) else None
        if ax is None or int(mesh.shape.get(ax, 1)) <= 1 or size % int(mesh.shape[ax]):
            return None
        return ax

    row_ax, head_ax = _axis(0, q.shape[0]), _axis(1, h)
    if row_ax is None and head_ax is None:
        return launch(q, k_pages, v_pages, table, lengths, scales)

    from jax.sharding import PartitionSpec as P

    page_spec = P(None, None, head_ax, None)
    in_specs = [
        P(row_ax, head_ax, None, None),  # q
        page_spec, page_spec,            # k/v pages
        P(row_ax, None),                 # table
        P(row_ax,),                      # lengths
    ]
    if scales is not None:
        in_specs += [page_spec, page_spec]

    def body(q_, k_, v_, tbl_, lens_, *maybe_scales):
        return launch(q_, k_, v_, tbl_, lens_, maybe_scales or None)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P(row_ax, head_ax, None, None), check_vma=False,
    )
    args = (q, k_pages, v_pages, table, lengths) + (scales if scales else ())
    return fn(*args)

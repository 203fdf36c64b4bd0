"""Grouped matrix product: rows sorted by group, one weight matrix a group.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies rows
``[offset_g, offset_g + group_sizes[g])`` of ``lhs`` ``(m, k)`` with
``rhs[g]`` ``(k, n)``, the offsets being the running sum of the sizes. Sizes
are data, not shapes: a group may be empty or hold every row, so a buffer
sized for the worst case never drops a row. Rows past the last group
(``sum(group_sizes) < m``) belong to no product: the CPU gives zeros there,
the TPU's kernel leaves them unwritten, forward and backward, and the caller
must not read them (``models/core/hybrid.py`` selects them away).

This is ``jax.lax.ragged_dot``, which XLA compiles for the TPU by itself and
partitions under a mesh like any other operation. PERF.md (PR 28) has what
it and the Pallas ``megablox`` kernel read on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``(m, k) x (g, k, n) -> (m, n)`` in ``lhs``'s dtype, accumulated in
    float32."""
    return jax.lax.ragged_dot(
        lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
        preferred_element_type=lhs.dtype,
    )

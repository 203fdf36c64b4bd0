"""Position encodings: shifted absolute positions, rotary embeddings,
inverse-frequency encodings and N-D Fourier features.

Capability parity with reference ``perceiver/model/core/position.py:9-138``;
implemented as pure functions / pytree dataclasses so everything is traceable
and shardable under ``jit``.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def positions(b: int, n: int, shift: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Absolute positions ``0..n-1`` per batch row, optionally shifted left by a
    per-row pad count (for left-padded batches) and clamped at 0.

    Mirrors reference ``position.py:9-17``.

    :param shift: optional ``(b, 1)`` int array — number of left-pad tokens.
    :return: ``(b, n)`` int32 positions.
    """
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    if shift is not None:
        if shift.shape != (b, 1):
            raise ValueError(f"shift must have shape {(b, 1)} but has shape {shift.shape}")
        pos = pos - shift.astype(jnp.int32)
    return jnp.maximum(pos, 0)


def frequency_position_encoding(
    abs_pos: jnp.ndarray, dim: int, theta: float = 10000.0
) -> jnp.ndarray:
    """Inverse-frequency encoding of absolute positions (rotary frequencies).

    ``inv_freq_i = theta ** (-2i/dim)``; each frequency is repeated twice along
    the channel axis so that consecutive channel pairs share a frequency (the
    pair layout :class:`RotaryEmbedding` rotates). Mirrors reference
    ``position.py:53-71``.

    :param abs_pos: ``(..., n)`` integer positions.
    :param dim: number of rotated channels (even).
    :return: ``(..., n, dim)`` float32 angles ``pos * inv_freq``.
    """
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    pos_enc = abs_pos.astype(jnp.float32)[..., None] * inv_freq
    # [f0, f0, f1, f1, ...] pairing, matching the reference's (pf r) repeat.
    return jnp.repeat(pos_enc, 2, axis=-1)


def swap_pairs(x: jnp.ndarray) -> jnp.ndarray:
    """Exchange channels ``2i`` and ``2i+1`` of the last axis:
    ``[x0, x1, x2, x3, ...] -> [x1, x0, x3, x2, ...]``. Two shifts by one
    channel and a select on the channel's parity: no ``(..., c/2, 2)`` view,
    which on the TPU is a relayout of the whole array."""
    axis = x.ndim - 1
    keep = [(0, 0, 0)] * axis
    zero = jnp.zeros((), x.dtype)
    nxt = lax.pad(x, zero, keep + [(-1, 1, 0)])  # nxt[l] = x[l + 1]
    prv = lax.pad(x, zero, keep + [(1, -1, 0)])  # prv[l] = x[l - 1]
    return jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, axis) % 2 == 0, nxt, prv)


@jax.custom_vjp
def _rotate(t: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """``t * cos + swap_pairs(t) * sin`` with float32 tables of ``t``'s shape:
    products and sum in float32, one rounding to ``t``'s dtype. The swap moves
    ``t`` as it is stored and the conversion follows it, so that the pass
    reads its input once, in its own dtype.

    The backward pass is written out because it is the same pass, ``g``
    rotated by ``cos`` and the swapped ``sin``: autodiff's transpose of the
    shifts and the select comes out of XLA as two passes with a float32
    array of ``t``'s size between them (1.8 of the 8k training step's ms on
    the cross-attention's keys alone, PERF.md PR 27)."""
    y = t.astype(jnp.float32) * cos + swap_pairs(t).astype(jnp.float32) * sin
    return y.astype(t.dtype)


def _rotate_fwd(t, cos, sin):
    return _rotate(t, cos, sin), (t, cos, sin)


def _rotate_bwd(residuals, g):
    t, cos, sin = residuals
    g32 = g.astype(jnp.float32)
    return (
        _rotate(g, cos, swap_pairs(sin)),
        g32 * t.astype(jnp.float32),
        g32 * swap_pairs(t).astype(jnp.float32),
    )


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


@jax.tree_util.register_pytree_node_class
class RotaryEmbedding:
    """Rotary position embedding (RoFormer) applied to the leading
    ``rotate_dim`` channels of q/k heads; remaining channels pass through.

    Built from ``frq_pos_enc`` of shape ``(b, n, rotate_dim)``, the angles of
    :func:`frequency_position_encoding` (consecutive channel pairs share one).
    Rotating the pair ``(x1, x2)`` by its angle ``a`` to
    ``(x1 cos a - x2 sin a, x2 cos a + x1 sin a)`` is, over a whole head,
    ``x * cos + swap_pairs(x) * sin`` with ``sin`` signed ``-`` on even and
    ``+`` on odd channels: the embedding holds these two float32 tables,
    computed once here for every module that rotates with it. When
    ``right_align`` is set, a shorter input of length ``m < n`` is aligned to
    the *last* ``m`` positions — used by Perceiver AR where latents sit at the
    sequence tail. Mirrors reference ``position.py:20-50``.
    """

    def __init__(self, frq_pos_enc: jnp.ndarray, right_align: bool = False):
        angles = jnp.asarray(frq_pos_enc, jnp.float32)
        self.cos = jnp.cos(angles)
        self.sin = jnp.sin(angles) * np.resize(np.float32([-1.0, 1.0]), angles.shape[-1])
        self.right_align = right_align

    def tree_flatten(self):
        return (self.cos, self.sin), self.right_align

    @classmethod
    def tree_unflatten(cls, right_align, tables):
        self = object.__new__(cls)
        self.cos, self.sin = tables
        self.right_align = right_align
        return self

    @property
    def rotate_dim(self) -> int:
        return self.cos.shape[-1]

    def _tables(self, seq_len: int, num_channels: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``(b, seq_len, num_channels)`` tables of one head: the positions
        this input sits at, 1 and 0 on the channels that pass through."""
        n = self.cos.shape[-2]
        at = slice(n - seq_len, n) if self.right_align else slice(0, seq_len)
        rest = ((0, 0), (0, 0), (0, num_channels - self.rotate_dim))
        cos = jnp.pad(self.cos[:, at], rest, constant_values=1.0)
        return cos, jnp.pad(self.sin[:, at], rest)

    def rotate(self, t: jnp.ndarray, num_heads: Optional[int] = None) -> jnp.ndarray:
        """Rotate heads of ``c >= rotate_dim`` channels: ``t`` is
        ``(b, h, m, c)`` or, with ``num_heads``, a projection's
        ``(b, m, h * c)`` output, rotated as it stands (every head by the
        same tables)."""
        if num_heads is None:
            cos, sin = (jnp.broadcast_to(x[:, None], t.shape) for x in self._tables(*t.shape[-2:]))
        else:
            tables = self._tables(t.shape[-2], t.shape[-1] // num_heads)
            cos, sin = (jnp.tile(x, (1, 1, num_heads)) for x in tables)
        return _rotate(t, cos, sin)


import functools


@functools.lru_cache(maxsize=32)
def _fourier_table(input_shape: Tuple[int, ...], num_frequency_bands: int) -> np.ndarray:
    coords = [np.linspace(-1.0, 1.0, num=s, dtype=np.float32) for s in input_shape]
    pos = np.stack(np.meshgrid(*coords, indexing="ij"), axis=-1)  # (*shape, d)
    encodings = [pos]
    grids = []
    for i, max_freq in enumerate(input_shape):
        freqs = np.linspace(1.0, max_freq / 2.0, num=num_frequency_bands, dtype=np.float32)
        grids.append(pos[..., i : i + 1] * freqs)
    encodings.extend([np.sin(math.pi * g) for g in grids])
    encodings.extend([np.cos(math.pi * g) for g in grids])
    enc = np.concatenate(encodings, axis=-1)
    return enc.reshape(-1, enc.shape[-1])


class FourierPositionEncoding:
    """N-D Fourier feature position encoding for grid-shaped inputs (images).

    Positions are evenly spaced in ``[-1, 1]`` per spatial dim (``ij`` indexed
    meshgrid, matching reference ``position.py:91-99``); each coordinate is
    expanded with ``num_frequency_bands`` sin/cos features with frequencies
    linearly spaced in ``[1, max_freq/2]`` plus the raw coordinate.

    The encoding is input-independent; the table is built once per
    (shape, bands) pair via an lru_cache (adapters and model ``setup`` may
    construct this object many times per trace) and becomes an XLA constant
    under ``jit``.
    """

    def __init__(self, input_shape: Sequence[int], num_frequency_bands: int):
        self.input_shape = tuple(input_shape)
        self.num_frequency_bands = num_frequency_bands
        self._encoding = _fourier_table(self.input_shape, num_frequency_bands)

    @property
    def num_channels(self) -> int:
        return len(self.input_shape) * (2 * self.num_frequency_bands + 1)

    def __call__(self, b: int) -> jnp.ndarray:
        """Return ``(b, prod(input_shape), num_channels)`` encodings."""
        enc = jnp.asarray(self._encoding)
        return jnp.broadcast_to(enc, (b, *enc.shape))

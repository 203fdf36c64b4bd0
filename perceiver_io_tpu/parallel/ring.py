"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

The reference has **no** sequence parallelism: it reaches long context purely
architecturally (Perceiver AR latent bottleneck, SURVEY.md §5.7). Going
beyond parity, this module shards the *sequence* dimension of attention over
the mesh: each device holds one contiguous chunk of q and of k/v, and k/v
chunks rotate around the ring via ``jax.lax.ppermute`` (one ICI hop per
step) while each device folds every visiting chunk into an online-softmax
accumulator (running max / running sum — the same math as the Pallas flash
kernel, at ring-block granularity). Peak memory per device is
O(local_q × local_kv) instead of O(n²), and the ppermute of the next chunk
overlaps with compute on the current one under XLA's async collectives.

Masking matches :func:`perceiver_io_tpu.ops.attention.dot_product_attention`:
right-aligned causal of unequal global q/kv lengths (offset ``j - i``,
reference ``modules.py:120-125``) and boolean key pad masks (True = pad).
Chunks are contiguous: global q row ``s·i_loc + r``, global kv col
``src·j_loc + c`` for the chunk originating on device ``src``.

Two entry points:

- :func:`ring_attention` — per-device body, for call sites already inside
  ``shard_map`` (e.g. a fully sequence-parallel train step);
- :func:`ring_attention_sharded` — standalone: takes mesh-sharded global
  arrays, applies ``shard_map`` over the given axis itself.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    axis_size: int,
    pad_mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
) -> jnp.ndarray:
    """Per-device ring attention body (call inside ``shard_map``).

    :param q: local ``(b, h, i_loc, d)`` pre-scaled queries — the chunk of
        the global query this device owns.
    :param k: local ``(b, h, j_loc, d)`` keys.
    :param v: local ``(b, h, j_loc, dv)`` values.
    :param pad_mask: local boolean ``(b, j_loc)``, True marks padding.
    :param axis_name: mesh axis the sequence is sharded over.
    :param axis_size: static size of that axis (= number of ring steps).
    :param causal: right-aligned causal over the *global* lengths.
    :return: local ``(b, h, i_loc, dv)`` output chunk.
    """
    s = jax.lax.axis_index(axis_name)
    b, h, i_loc, _ = q.shape
    j_loc, dv = k.shape[2], v.shape[3]
    # Offset of the shifted causal diagonal, from the static global lengths.
    offset = (j_loc - i_loc) * axis_size if causal else None

    qf = q
    m = jnp.full((b, h, i_loc, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, i_loc, 1), jnp.float32)
    acc = jnp.zeros((b, h, i_loc, dv), jnp.float32)

    perm = [(d, (d + 1) % axis_size) for d in range(axis_size)]
    k_t, v_t, pad_t = k, v, pad_mask
    for t in range(axis_size):
        src = (s - t) % axis_size  # device the visiting chunk originated on

        logits = jnp.einsum("bhic,bhjc->bhij", qf, k_t, preferred_element_type=jnp.float32)
        logits = logits.astype(jnp.float32)
        allowed = None
        if pad_t is not None:
            allowed = ~pad_t[:, None, None, :]
        if causal:
            rows = s * i_loc + jnp.arange(i_loc)[:, None]
            cols = src * j_loc + jnp.arange(j_loc)[None, :]
            cm = (cols <= rows + offset)[None, None]
            allowed = cm if allowed is None else jnp.logical_and(allowed, cm)
        if allowed is not None:
            logits = jnp.where(allowed, logits, _MASK)

        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhij,bhjc->bhic", p.astype(v_t.dtype), v_t, preferred_element_type=jnp.float32
        ).astype(jnp.float32)
        m = m_new

        if t + 1 < axis_size:
            k_t = jax.lax.ppermute(k_t, axis_name, perm)
            v_t = jax.lax.ppermute(v_t, axis_name, perm)
            if pad_t is not None:
                pad_t = jax.lax.ppermute(pad_t, axis_name, perm)

    safe_l = jnp.where(l > 0.0, l, 1.0)
    return (acc / safe_l).astype(q.dtype)


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    axis_name: str = "seq",
    pad_mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
) -> jnp.ndarray:
    """Ring attention over *global* arrays sharded on ``axis_name``.

    Applies ``shard_map`` itself: q/k/v are (re)sharded so their sequence
    dimension is split over the axis, every other mesh axis replicated.
    """
    if causal and k.shape[2] < q.shape[2]:
        raise ValueError("causal ring attention requires kv_len >= q_len")
    n_seq = mesh.shape[axis_name]
    if q.shape[2] % n_seq or k.shape[2] % n_seq:
        raise ValueError(
            f"q_len={q.shape[2]} and kv_len={k.shape[2]} must divide the "
            f"'{axis_name}' axis size {n_seq}"
        )

    seq_spec = P(None, None, axis_name, None)
    pad_spec = P(None, axis_name)
    in_specs = (seq_spec, seq_spec, seq_spec) + ((pad_spec,) if pad_mask is not None else ())
    body = functools.partial(
        _ring_body, axis_name=axis_name, axis_size=n_seq, causal=causal
    )
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=seq_spec, check_vma=False
    )
    args = (q, k, v) + ((pad_mask,) if pad_mask is not None else ())
    return fn(*args)


def _ring_body(q, k, v, pad_mask=None, *, axis_name, axis_size, causal):
    return ring_attention(
        q, k, v, axis_name=axis_name, axis_size=axis_size,
        pad_mask=pad_mask, causal=causal,
    )

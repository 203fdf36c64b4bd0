"""Blocks of a hybrid decoder whose layers are declared one by one: a gated
MLP, a gated short convolution and a layer of sparse experts. With
:class:`~perceiver_io_tpu.models.core.modules.RMSNorm` and
``MultiHeadAttention(num_kv_heads=..., qk_norm=True)`` they make the layers of
:mod:`perceiver_io_tpu.models.text.lm` (docs/lm.md has the equations).

The phases inside the modules carry ``jax.named_scope``s (``short_conv``;
``router``, ``dispatch``, ``experts``, ``combine``): Flax names the modules,
these name what a module does, so that ``observability.ledger.op_scopes``
places every device operation (docs/observability.md).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.ops.grouped_matmul import grouped_matmul

_HIGHEST = jax.lax.Precision.HIGHEST


def _dense(features: int, init_scale: float, dtype, name: str) -> nn.Dense:
    return nn.Dense(
        features, use_bias=False, kernel_init=nn.initializers.normal(stddev=init_scale),
        dtype=dtype, name=name,
    )


class GatedMLP(nn.Module):
    """``down(silu(gate(x)) * up(x))``, no bias."""

    num_channels: int
    hidden_channels: int
    init_scale: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        gate = _dense(self.hidden_channels, self.init_scale, self.dtype, "gate")(x)
        up = _dense(self.hidden_channels, self.init_scale, self.dtype, "up")(x)
        return _dense(self.num_channels, self.init_scale, self.dtype, "down")(nn.silu(gate) * up)


def causal_depthwise_conv(x: jnp.ndarray, filt: jnp.ndarray) -> jnp.ndarray:
    """``y[t] = sum_l filt[l] * x[t - (L - 1 - l)]`` by channel over ``x``
    ``(b, n, c)`` with ``filt`` ``(L, c)``: the ``L`` latest positions, the
    last tap on the current one, zeros before the start. ``L`` shifted
    multiply-adds; no convolution operator."""
    n, taps = x.shape[1], filt.shape[0]
    y = x * filt[taps - 1]
    for back in range(1, taps):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :n]
        y = y + shifted * filt[taps - 1 - back]
    return y


class ShortConv(nn.Module):
    """Gated short convolution: ``B, C, x = split3(in_proj(u))``;
    ``y = out_proj(C * causal_depthwise_conv(B * x))``, one filter of
    ``kernel_size`` taps a channel, no bias. ``pad_mask`` (True at padding)
    zeroes ``B * x`` there, so left padding never reaches a real position."""

    num_channels: int
    kernel_size: int = 3
    init_scale: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: jnp.ndarray, pad_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        bcx = _dense(3 * self.num_channels, self.init_scale, self.dtype, "in_proj")(u)
        bound = 1.0 / math.sqrt(self.kernel_size)  # a depthwise filter's fan-in is its taps
        filt = self.param(
            "filter",
            lambda key, shape: jax.random.uniform(key, shape, jnp.float32, -bound, bound),
            (self.kernel_size, self.num_channels),
        )
        with jax.named_scope("short_conv"):
            b_gate, c_gate, x = jnp.split(bcx, 3, axis=-1)
            bx = b_gate * x
            if pad_mask is not None:
                bx = jnp.where(pad_mask[..., None], 0, bx)
            y = c_gate * causal_depthwise_conv(bx, filt.astype(self.dtype))
        return _dense(self.num_channels, self.init_scale, self.dtype, "out_proj")(y)


@jax.custom_vjp
def _take_tokens(tokens, order, inverse, valid):
    """``tokens[order // k]`` for ``order`` a permutation of the ``t * k``
    token-expert pairs (pair ``p`` is token ``p // k``). The backward pass is
    the inverse permutation's gather and a sum over each token's ``k`` pairs:
    no scatter either way. ``valid`` marks the sorted rows that a held expert
    computes; the gradient of the others is dropped, whatever it holds: a
    grouped product's kernel leaves the rows past its last group unwritten."""
    return tokens[order // (order.shape[0] // tokens.shape[0])]


def _take_tokens_fwd(tokens, order, inverse, valid):
    return _take_tokens(tokens, order, inverse, valid), (inverse, valid, tokens.shape[0])


def _take_tokens_bwd(res, g):
    inverse, valid, t = res
    g = jnp.where(valid[:, None], g, jnp.zeros((), g.dtype))
    by_token = g[inverse].reshape(t, -1, g.shape[-1])
    return by_token.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None, None


_take_tokens.defvjp(_take_tokens_fwd, _take_tokens_bwd)


@jax.custom_vjp
def _permute_rows(x: jnp.ndarray, perm: jnp.ndarray, inverse: jnp.ndarray) -> jnp.ndarray:
    """``x[perm]`` for a permutation and its inverse; the backward pass is
    ``g[inverse]``, a gather like the forward."""
    return x[perm]


def _permute_rows_fwd(x, perm, inverse):
    return x[perm], (inverse,)


def _permute_rows_bwd(res, g):
    return g[res[0]], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def route(tokens, router, bias, top_k: int, normalise: bool, scaling: float):
    """``(indices, weights)``, both ``(t, top_k)``: scores are
    ``sigmoid(tokens @ router)`` in float32 (six-pass products: a choice
    that flips with the rounding of a bfloat16 product is a different
    model), the ``top_k`` highest of ``scores + bias`` are chosen, and the
    weights are those scores themselves, over their sum + 1e-6 if
    ``normalise``, times ``scaling``. ``bias`` only chooses: it takes no
    gradient."""
    logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32), precision=_HIGHEST)
    scores = jax.nn.sigmoid(logits)
    chosen = scores if bias is None else scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, indices = jax.lax.top_k(chosen, top_k)
    weights = jnp.take_along_axis(scores, indices, axis=-1)
    if normalise:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return indices, weights * scaling


def held_experts_output(
    tokens, indices, weights, gate, up, down, expert_offset: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the layer's output for ``tokens`` ``(t, c)``
    and the pairs each held expert was given ``(held,)``.

    The ``t * top_k`` token-expert pairs are sorted by held expert, the pairs
    of experts not held last; each projection is one grouped product over the
    held experts' rows. The buffers hold every pair, so none is dropped
    whatever the routing. What a grouped product leaves in the rows past the
    held pairs is never read: the output's are selected away before the
    combine, and their gradient is dropped where the rows were gathered."""
    t, top_k = indices.shape
    held = gate.shape[0]
    with jax.named_scope("dispatch"):
        local = indices - expert_offset
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = (key[:, None] == jnp.arange(held)[None, :]).sum(axis=0, dtype=jnp.int32)
        valid = jnp.arange(t * top_k) < group_sizes.sum()
        rows = _take_tokens(tokens, order, inverse, valid)
        weights = jnp.where(here, weights, 0.0)
    with jax.named_scope("experts"):
        hidden = nn.silu(grouped_matmul(rows, gate, group_sizes)) * grouped_matmul(rows, up, group_sizes)
        out_rows = grouped_matmul(hidden, down, group_sizes)
    with jax.named_scope("combine"):
        out_rows = jnp.where(valid[:, None], out_rows, jnp.zeros((), out_rows.dtype))
        by_token = _permute_rows(out_rows, inverse, order).reshape(t, top_k, -1)
        out = (by_token.astype(jnp.float32) * weights[..., None]).sum(axis=1)
    return out.astype(tokens.dtype), group_sizes


class SparseExperts(nn.Module):
    """A mixture-of-experts layer that is told which experts it holds.

    The router has ``router_width`` outputs, the published number of experts,
    and every token chooses ``top_k`` of them (:func:`route`). This module
    holds experts ``expert_offset .. expert_offset + num_experts - 1`` as
    stacked weights ``gate``/``up`` ``(num_experts, c, hidden_channels)`` and
    ``down`` ``(num_experts, hidden_channels, c)``, and returns the weighted
    sum of *their* outputs for the tokens routed to them, nothing for the
    others: one chip's part of an expert-parallel layer, without its
    exchange. With ``num_experts == router_width`` it is the whole layer. No
    pair is ever dropped (:func:`held_experts_output`).

    Returns ``(output, stats)``; ``stats`` is ``[pairs computed here, fullest
    held expert over the mean held expert]`` of this call, float32.

    Under a mesh with more than one device the tokens' part runs inside
    ``jax.shard_map`` over the batch axes, every shard sorting its own
    tokens, with the weights replicated; the expert dimension is never
    split.
    """

    num_channels: int
    hidden_channels: int
    router_width: int
    num_experts: int
    expert_offset: int = 0
    top_k: int = 4
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    init_scale: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if not 0 <= self.expert_offset <= self.router_width - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.num_experts - 1} "
                f"are not among the router's {self.router_width}"
            )
        c, f, e = self.num_channels, self.hidden_channels, self.num_experts
        init = nn.initializers.normal(stddev=self.init_scale)
        router = self.param("router", init, (c, self.router_width))
        bias = (
            self.param("expert_bias", nn.initializers.zeros, (self.router_width,))
            if self.use_expert_bias else None
        )
        gate = self.param("gate", init, (e, c, f))
        up = self.param("up", init, (e, c, f))
        down = self.param("down", init, (e, f, c))

        def tokens_part(x, router, bias, gate, up, down, axes=()):
            tokens = x.reshape(-1, c)
            with jax.named_scope("router"):
                indices, weights = route(
                    tokens, router, bias, self.top_k, self.norm_topk_prob,
                    self.routed_scaling_factor,
                )
            out, sizes = held_experts_output(
                tokens, indices, weights, gate, up, down, self.expert_offset)
            if axes:
                sizes = jax.lax.psum(sizes, axes)
            sizes = sizes.astype(jnp.float32)
            stats = jnp.stack([sizes.sum(), sizes.max() / jnp.maximum(sizes.mean(), 1.0)])
            return out.reshape(x.shape), stats

        args = (u.astype(self.dtype), router, bias, gate, up, down)
        axes = _batch_axes_dividing(u.shape[0])
        if not axes:
            return tokens_part(*args)
        from jax.sharding import PartitionSpec as P

        specs = (P(axes),) + tuple(None if a is None else P() for a in args[1:])
        return jax.shard_map(
            lambda *a: tokens_part(*a, axes=axes), mesh=jax.sharding.get_abstract_mesh(),
            in_specs=specs, out_specs=(P(axes), P()), check_vma=False,
        )(*args)


def _batch_axes_dividing(batch: int) -> tuple:
    """The ambient mesh's batch axes of more than one device, if they
    divide ``batch``; ``()`` outside a mesh or on one device."""
    from perceiver_io_tpu.ops.attention import _ambient_mesh
    from perceiver_io_tpu.parallel.mesh import BATCH_AXES

    mesh = _ambient_mesh()
    if mesh is None or mesh.size == 1:
        return ()
    axes = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    shards = math.prod(mesh.shape[a] for a in axes)
    return axes if axes and batch % shards == 0 else ()

"""Blocks of a hybrid decoder whose layers are declared one by one: a gated
MLP, a gated short convolution and a layer of sparse experts. With
:class:`~perceiver_io_tpu.models.core.modules.RMSNorm` and
``MultiHeadAttention(num_kv_heads=..., qk_norm=True)`` they make the layers of
:mod:`perceiver_io_tpu.models.text.lm` (docs/lm.md has the equations).

The phases inside the modules carry ``jax.named_scope``s (``short_conv``;
``router``, ``dispatch``, ``experts``, ``combine``): Flax names the modules,
these name what a module does, so that ``observability.ledger.op_scopes``
places every device operation (docs/observability.md). The expert layer's
``lax.cond`` stands outside them, so that no ``conditional`` carries a phase.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.ops.grouped_matmul import grouped_matmul

_HIGHEST = jax.lax.Precision.HIGHEST

#: The expert layer's row bound over what uniform routing sends to the held
#: experts, ``m = tokens * top_k * num_experts / router_width`` pairs. A
#: layer's held pairs are a sum of ``tokens * top_k`` choices: under uniform
#: routing their spread is under ``sqrt(m)`` (91 at the 8,192 of 16,384
#: tokens, 4 of 64 experts a token, 8 held), so chance alone needs a per cent.
#: What needs room is routing that is not uniform: a selection bias seeded at
#: 0.01 puts the fullest held expert at 1.5 times the mean one and a layer's
#: held pairs within a few per cent of ``m`` (33.4 k a step over four layers
#: for 32.8 k), a bias of 0.1 puts an expert at 3.7 times (PERF.md, PR 28).
#: Twice ``m`` holds a layer all of whose held experts run half again over
#: their share and then some, and the rows' cost is linear in the bound: at 8
#: of 64 it is a quarter of the worst case, where ``1.25 m`` would be a
#: sixth. Past it nothing is wrong: the layer pays the worst case that step.
_ROWS_OVER_UNIFORM = 2
#: rows of a tile of the grouped product's kernel on the TPU (``ragged-dot-none``)
_ROW_TILE = 512
#: what stands between an expert's gate and up projections
_ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu}
#: how the router's logits become choices and weights (:func:`route`)
ROUTER_SCORES = ("sigmoid", "softmax_topk")


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
def _take_tokens(tokens, ids, valid, inverse):
    """``tokens[ids]`` for ``ids`` ``(rows,)``, the tokens of the first
    ``rows`` sorted pairs; ``inverse`` ``(t * k,)`` is every pair's place in
    the sorted order (pair ``p`` is token ``p // k``). The backward pass is a
    gather too: a token takes the gradient rows of its ``k`` pairs, choice by
    choice (``(k, t, c)``, summed over the leading dimension in float32: no
    ``(t, k, c)`` copy padded to the tiling), and a pair whose place is past
    ``rows`` takes zeros. ``valid`` marks the sorted rows that a held expert
    computes; the gradient of the others is dropped, whatever it holds: a
    grouped product's kernel leaves the rows past its last group unwritten.
    (The other form, a float32 scatter-add of the rows by token id, put a
    layer's ``dispatch`` at 2.97 ms on the chip against 1.50: PERF.md, PR 31.)"""
    return tokens[ids]


def _take_tokens_fwd(tokens, ids, valid, inverse):
    return tokens[ids], (valid, inverse, tokens.shape[0])


def _take_tokens_bwd(res, g):
    valid, inverse, t = res
    g = jnp.where(valid[:, None], g, jnp.zeros((), g.dtype))
    by_choice = jnp.take(g, inverse.reshape(t, -1).T, axis=0, mode="fill", fill_value=0)
    return by_choice.astype(jnp.float32).sum(axis=0).astype(g.dtype), None, None, None


_take_tokens.defvjp(_take_tokens_fwd, _take_tokens_bwd)


@jax.custom_vjp
def _combine(out_rows, weights, head):
    """``(t, c)``: every token's sum of its sorted rows under their weights.
    ``head`` ``(rows,)`` are the pairs of ``out_rows`` ``(rows, c)`` (pair
    ``p`` is token ``p // k``, choice ``p % k`` of ``weights`` ``(t, k)``).
    From the sorted side both ways: the rows, weighted in float32, are added
    into the tokens' float32 sums by token id, and rounded once; the backward
    pass gathers the tokens' gradient rows back, ``rows`` of them. A row that
    no held expert computes must arrive zeroed and under a zero weight. (The
    other form, a gather of all ``t * k`` places from the token side, read
    1.65 ms a step more in ``lfm2moe-train-8k``: PERF.md, PR 31.)"""
    return _combine_fwd(out_rows, weights, head)[0]


def _combine_fwd(out_rows, weights, head):
    t, top_k = weights.shape
    w = weights.reshape(-1)[head]
    weighted = out_rows.astype(jnp.float32) * w[:, None]
    out = jnp.zeros((t, out_rows.shape[-1]), jnp.float32).at[head // top_k].add(weighted)
    return out.astype(out_rows.dtype), (out_rows, w, head, weights)


def _combine_bwd(res, g):
    out_rows, w, head, weights = res
    g_rows = g[head // weights.shape[1]].astype(jnp.float32)
    d_rows = (g_rows * w[:, None]).astype(out_rows.dtype)
    d_w = (g_rows * out_rows.astype(jnp.float32)).sum(axis=-1)
    d_weights = jnp.zeros((weights.size,), jnp.float32).at[head].set(d_w)
    return d_rows, d_weights.reshape(weights.shape).astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def route(tokens, router, bias, top_k: int, normalise: bool, scaling: float,
          score: str = "sigmoid"):
    """``(indices, weights)``, both ``(t, top_k)``, from the logits ``tokens @
    router`` in float32 (six-pass products: a choice that flips with the
    rounding of a bfloat16 product is a different model).

    ``score="sigmoid"``: scores are ``sigmoid(logits)``, the ``top_k`` highest
    of ``scores + bias`` are chosen, and the weights are those scores
    themselves, over their sum + 1e-6 if ``normalise``.
    ``score="softmax_topk"``: the ``top_k`` highest of ``logits + bias`` are
    chosen and the weights are a softmax over the chosen logits alone, which
    sum to one (``normalise`` has nothing left to do). Either way times
    ``scaling``; ``bias`` only chooses: it takes no gradient."""
    if score not in ROUTER_SCORES:
        raise ValueError(f"router score {score!r}; known: {ROUTER_SCORES}")
    logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32), precision=_HIGHEST)
    if score == "softmax_topk":
        chosen = logits if bias is None else logits + jax.lax.stop_gradient(bias.astype(jnp.float32))
        _, indices = jax.lax.top_k(chosen, top_k)
        weights = jax.nn.softmax(jnp.take_along_axis(logits, indices, axis=-1), axis=-1)
        return indices, weights * scaling
    scores = jax.nn.sigmoid(logits)
    chosen = scores if bias is None else scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, indices = jax.lax.top_k(chosen, top_k)
    weights = jnp.take_along_axis(scores, indices, axis=-1)
    if normalise:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return indices, weights * scaling


def expected_rows(tokens: int, top_k: int, num_experts: int, router_width: int) -> int:
    """The sorted rows an expert layer runs on while its held pairs fit them:
    ``_ROWS_OVER_UNIFORM`` times the held experts' uniform share of ``tokens
    * top_k`` pairs, in whole tiles of the grouped product."""
    uniform = tokens * top_k * num_experts / router_width
    return _ROW_TILE * math.ceil(_ROWS_OVER_UNIFORM * uniform / _ROW_TILE)


def sort_pairs(indices, weights, expert_offset: int, held: int):
    """``(order, inverse, group_sizes, weights)`` of the ``t * top_k``
    token-expert pairs: ``order`` sorts them by held expert, the pairs of
    experts not held last (stable: within an expert by token); ``inverse`` is
    each pair's place in that order; ``group_sizes`` ``(held,)`` the pairs of
    each held expert; ``weights`` with those of the other pairs zeroed."""
    with jax.named_scope("dispatch"):
        local = indices - expert_offset
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = (key[:, None] == jnp.arange(held)[None, :]).sum(axis=0, dtype=jnp.int32)
        weights = jnp.where(here, weights, 0.0)
    return order, inverse, group_sizes, weights


def sorted_rows_output(tokens, weights, gate, up, down, order, inverse, group_sizes, rows: int,
                       activation: str = "silu"):
    """The held experts' part of the output for ``tokens`` ``(t, c)``, from
    the first ``rows`` pairs of :func:`sort_pairs`' order. ``rows`` is static
    and must hold every held pair (``group_sizes.sum() <= rows``), which sort
    first; ``order.shape[0]`` always does.

    The ``rows`` token rows are gathered, each projection is one grouped
    product over the held experts' rows (``down(activation(gate x) * up x)``,
    ``activation`` ``silu`` or ``relu``), and the rows go back to their tokens
    under their weights. What a grouped product leaves in the rows past the
    held pairs is never read: the output's are selected away before the
    combine, and their gradient is dropped where the rows were gathered."""
    with jax.named_scope("dispatch"):
        head = order[:rows]
        valid = jnp.arange(rows) < group_sizes.sum()
        x = _take_tokens(tokens, head // weights.shape[1], valid, inverse)
    with jax.named_scope("experts"):
        act = _ACTIVATIONS[activation]
        hidden = act(grouped_matmul(x, gate, group_sizes)) * grouped_matmul(x, up, group_sizes)
        out_rows = grouped_matmul(hidden, down, group_sizes)
    with jax.named_scope("combine"):
        out_rows = jnp.where(valid[:, None], out_rows, jnp.zeros((), out_rows.dtype))
        return _combine(out_rows, weights, head)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bounded_or_full(rows, activation, fits, tokens, weights, gate, up, down, order, inverse,
                     group_sizes):
    """:func:`sorted_rows_output` on ``rows`` rows if ``fits`` (they hold the
    held pairs), else on every pair's: the same code at two sizes, chosen by
    ``lax.cond`` on a count of this call's own routing. Forward and backward
    are each a ``cond`` on ``fits``, and the backward's branches recompute
    from the operands: reverse mode through one ``cond`` would have each
    branch write the other's residuals as zeros, worst-case-sized ones on the
    bounded branch. (Inside a rematerialised layer the forward's ``cond``
    leaves the recomputation as dead code, its residuals being its operands:
    three passes over the rows a step, as without the ``cond``.) The ``cond``s
    stand outside the phase scopes, the scopes inside the branches."""
    return jax.lax.cond(
        fits,
        functools.partial(sorted_rows_output, rows=rows, activation=activation),
        functools.partial(sorted_rows_output, rows=order.shape[0], activation=activation),
        tokens, weights, gate, up, down, order, inverse, group_sizes,
    )


def _bounded_or_full_fwd(rows, activation, fits, *operands):
    return _bounded_or_full(rows, activation, fits, *operands), (fits, operands)


def _bounded_or_full_bwd(rows, activation, res, g):
    fits, (*inputs, order, inverse, group_sizes) = res

    def pull_back(on_rows):
        def branch(g, *inputs):
            run = functools.partial(
                sorted_rows_output, order=order, inverse=inverse, group_sizes=group_sizes,
                rows=on_rows, activation=activation)
            return jax.vjp(run, *inputs)[1](g)
        return branch

    grads = jax.lax.cond(fits, pull_back(rows), pull_back(order.shape[0]), g, *inputs)
    return (None, *grads, None, None, None)


_bounded_or_full.defvjp(_bounded_or_full_fwd, _bounded_or_full_bwd)


def held_experts_output(
    tokens, indices, weights, gate, up, down, expert_offset: int, rows: Optional[int] = None,
    activation: str = "silu",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the layer's output for ``tokens`` ``(t, c)``
    and the pairs each held expert was given ``(held,)``, computed on the
    first ``rows`` sorted pairs (:func:`sorted_rows_output`): every pair,
    ``t * top_k``, unless told a smaller number that holds the held pairs.
    On every pair none is dropped whatever the routing."""
    order, inverse, group_sizes, weights = sort_pairs(indices, weights, expert_offset, gate.shape[0])
    out = sorted_rows_output(
        tokens, weights, gate, up, down, order, inverse, group_sizes, rows=rows or order.shape[0],
        activation=activation)
    return out, group_sizes


def bounded_experts_output(
    tokens, indices, weights, gate, up, down, expert_offset: int, rows_expected: int,
    activation: str = "silu",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`held_experts_output` on ``rows_expected`` rows when this call's
    held pairs fit them and on every pair when they do not
    (:func:`_bounded_or_full`): no pair is dropped either way. Also returns
    1.0 if they fitted, else 0.0. With ``rows_expected`` no smaller than
    ``t * top_k`` there is nothing to choose: one path, and 1.0."""
    order, inverse, group_sizes, weights = sort_pairs(indices, weights, expert_offset, gate.shape[0])
    operands = (tokens, weights, gate, up, down, order, inverse, group_sizes)
    if rows_expected >= order.shape[0]:
        out = sorted_rows_output(*operands, rows=order.shape[0], activation=activation)
        return out, group_sizes, jnp.ones((), jnp.float32)
    fits = group_sizes.sum() <= rows_expected
    out = _bounded_or_full(rows_expected, activation, fits, *operands)
    return out, group_sizes, fits.astype(jnp.float32)


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
    pair is ever dropped: the held pairs sort first, and dispatch, experts
    and combine run on :func:`expected_rows` sorted rows while the held pairs
    fit them and on every pair's when they do not
    (:func:`bounded_experts_output`: the same code at two sizes, chosen each
    call from the routing it finds; no option).

    ``router_score`` and ``activation`` say how the logits become weights
    (:func:`route`) and what gates an expert (``silu``, ``relu``). The router
    reads the experts' own input unless the call hands it another
    (``router_input``: a model whose router stands before the attention gives
    the attention's normed input); the experts always compute on ``u``.

    Returns ``(output, stats)``; ``stats`` is ``[pairs computed here, fullest
    held expert over the mean held expert, 1.0 if the held pairs fitted the
    row bound]`` of this call, float32.

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
    router_score: str = "sigmoid"
    activation: str = "silu"

    @nn.compact
    def __call__(
        self, u: jnp.ndarray, router_input: Optional[jnp.ndarray] = None
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if self.activation not in _ACTIVATIONS:
            raise ValueError(f"activation {self.activation!r}; known: {sorted(_ACTIVATIONS)}")
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

        def tokens_part(x, seen, router, bias, gate, up, down, axes=()):
            tokens = x.reshape(-1, c)
            with jax.named_scope("router"):
                indices, weights = route(
                    tokens if seen is None else seen.reshape(-1, c), router, bias, self.top_k,
                    self.norm_topk_prob, self.routed_scaling_factor, self.router_score,
                )
            out, sizes, fitted = bounded_experts_output(
                tokens, indices, weights, gate, up, down, self.expert_offset,
                expected_rows(tokens.shape[0], self.top_k, e, self.router_width), self.activation)
            if axes:  # every shard chose for its own tokens: bounded if all were
                sizes, fitted = jax.lax.psum(sizes, axes), jax.lax.pmin(fitted, axes)
            sizes = sizes.astype(jnp.float32)
            stats = jnp.stack([sizes.sum(), sizes.max() / jnp.maximum(sizes.mean(), 1.0), fitted])
            return out.reshape(x.shape), stats

        seen = None if router_input is None else router_input.astype(self.dtype)
        args = (u.astype(self.dtype), seen, router, bias, gate, up, down)
        axes = _batch_axes_dividing(u.shape[0])
        if not axes:
            return tokens_part(*args)
        from jax.sharding import PartitionSpec as P

        specs = (P(axes), None if seen is None else P(axes)) + tuple(
            None if a is None else P() for a in args[2:])
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

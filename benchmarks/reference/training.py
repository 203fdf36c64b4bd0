"""The reference's side of a training step: loss and gradients in blocks of
rows (so that the full batch fits beside nothing else), and a plain AdamW.
Imports nothing of the program.

The device holds the parameters, their gradient and the two moments once
each: every function here that makes a new tree of the parameters' size
from an old one is one jitted call that donates the old one, so the update
is made in place."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seeded_params(ref, cfg: dict, seed: int) -> dict:
    """The reference's parameters from ``seed``, made in one jitted call."""
    return jax.jit(lambda key: ref.init_params(key, cfg))(jax.random.PRNGKey(seed % (2**31)))


def make_block(ref, cfg: dict):
    """Jitted summed loss, label count and gradient of one block of rows."""

    @jax.jit
    def block(p, blk, blk_aux):
        (total, count), grads = jax.value_and_grad(
            lambda q: ref.train_nll(q, cfg, blk, blk_aux), has_aux=True
        )(p)
        return total, count, grads

    return block


@functools.partial(jax.jit, donate_argnums=0)
def _added(total, more):
    return jax.tree_util.tree_map(jnp.add, total, more)


@functools.partial(jax.jit, donate_argnums=0)
def _divided(tree, denom):
    return jax.tree_util.tree_map(lambda g: g / denom, tree)


def loss_and_grads(block, params: dict, batch: dict, aux, rows: int):
    """Mean loss over the batch's labels and its gradient, accumulated over
    blocks of ``rows`` rows: the mean's denominator is the whole batch's
    label count, as in one pass. A further block's gradient is added into
    the sum's own buffers."""
    n = batch["input_ids"].shape[0]
    total, count, grads = 0.0, 0, None
    for lo in range(0, n, rows):
        blk = {k: v[lo:lo + rows] for k, v in batch.items()}
        blk_aux = None if aux is None else aux[lo:lo + rows]
        t, c, g = block(params, blk, blk_aux)
        total, count = total + t, count + c
        grads = g if grads is None else _added(grads, g)
        del g  # or the next block's gradient would be made beside this one
    denom = jnp.maximum(count, 1)
    return total / denom, _divided(grads, denom)


def lr_at(opt: dict, count: int) -> float:
    """The learning rate of the update that follows ``count`` updates:
    linear warm-up, then a cosine to ``min_fraction`` of the base rate."""
    base, warm, total = opt["lr"], opt["warmup_steps"], opt["training_steps"]
    if opt["schedule"] == "constant":
        return base
    if count < warm:
        return base * count / max(1.0, warm)
    progress = min(max((count - warm) / max(1.0, total - warm), 0.0), 1.0)
    mf = opt["min_fraction"]
    return base * (mf + (1.0 - mf) * 0.5 * (1.0 + math.cos(math.pi * progress)))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7), donate_argnums=(0, 2, 3))
def _adamw(params, grads, mu, nu, b1, b2, eps, wd, lr, count):
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p), params, mu, nu
    )
    return new, mu, nu


def _zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def adamw_step(opt: dict, params, grads, state):
    """One AdamW update (Loshchilov & Hutter, decoupled decay), in place:
    ``params`` and the moments are donated to the update and ``grads`` are
    deleted after it, so none of the four can be read again. ``state`` is
    ``(mu, nu, count)`` or None before the first."""
    if state is None:  # a tree each: two donated arguments cannot share buffers
        state = (_zeros_like(params), _zeros_like(params), 0)
    mu, nu, count = state
    lr = lr_at(opt, count)
    params, mu, nu = _adamw(
        params, grads, mu, nu, opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
        jnp.float32(lr), jnp.float32(count + 1),
    )
    for g in jax.tree_util.tree_leaves(grads):
        g.delete()
    return params, (mu, nu, count + 1)


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))) for k, v in tree.items()}

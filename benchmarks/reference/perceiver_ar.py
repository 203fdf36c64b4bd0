"""Plain reference of Perceiver AR (arXiv:2202.07765) as the causal language
model the program trains and serves: tied byte embedding, learned absolute
positions plus rotary on half of each head, one causal cross-attention of the
latents (the tail of the sequence) over [prefix, latents], a causal
self-attention stack with rotary in its first layer only, logits against the
embedding table.

Imports nothing of the program. Parameters are a flat dict of float32 arrays
made from a key by :func:`init_params`.
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

from . import blocks
from .blocks import attention, layer_norm, mlp


def param_shapes(cfg: dict) -> dict:
    c, v, n = cfg["num_channels"], cfg["vocab_size"], cfg["max_seq_len"]
    shapes = {"emb.tok": (v, c), "emb.pos": (n, c), "head.bias": (v,)}

    def layer(name, widening, *, kv_norm, out_bias):
        norms = ["q_norm", "kv_norm"] if kv_norm else ["norm"]
        for nm in norms + ["mlp.norm"]:
            shapes[f"{name}.{nm}.g"] = (c,)
            shapes[f"{name}.{nm}.b"] = (c,)
        for proj in "qkvo":
            shapes[f"{name}.attn.{proj}.w"] = (c, c)
        if out_bias:
            shapes[f"{name}.attn.o.bias"] = (c,)
        shapes[f"{name}.mlp.hidden.w"] = (c, widening * c)
        shapes[f"{name}.mlp.out.w"] = (widening * c, c)

    layer("cross", cfg["cross_attention_widening_factor"], kv_norm=True, out_bias=True)
    for i in range(cfg["num_self_attention_layers"]):
        layer(f"self.{i}", cfg["self_attention_widening_factor"], kv_norm=False, out_bias=False)
    return shapes


def init_params(key, cfg: dict) -> dict:
    return blocks.normal_params(key, param_shapes(cfg), cfg.get("init_scale", 0.02))


def _flax_rng(key, *path):
    """The key flax hands a module at ``path`` for its n-th draw: the static
    path and count are folded in through a SHA-1 (flax.core.scope)."""
    from flax import config as flax_config

    m = hashlib.sha1()
    for x in path:
        if flax_config.flax_fix_rng_separator:
            m.update(b"\00")
        m.update(x.encode() if isinstance(x, str) else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def prefix_keep_indices(seed: int, step: int, batch: int, prefix_len: int, rate: float):
    """Which prefix positions training step ``step`` keeps, in order: the
    step's key is ``fold_in(PRNGKey(seed), step)``, split into dropout and
    prefix keys; the model draws one uniform score per prefix position and
    keeps the ``prefix_len - int(prefix_len * rate)`` highest of each row."""
    step_key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    _, prefix_key = jax.random.split(step_key)
    scores = jax.random.uniform(_flax_rng(prefix_key, "perceiver_ar", 1), (batch, prefix_len))
    keep = prefix_len - int(prefix_len * rate)
    return jnp.sort(jax.lax.top_k(scores, keep)[1], axis=-1)


def hidden(p, cfg, input_ids, prefix_len: int, pad_mask=None, keep=None):
    """Latent states ``(b, n - prefix_len, c)`` after the stack."""
    b, n = input_ids.shape
    heads = cfg["num_heads"]
    pos = jnp.broadcast_to(jnp.arange(n), (b, n))
    if pad_mask is not None:
        pos = jnp.maximum(pos - pad_mask.sum(1, keepdims=True), 0)
    x = p["emb.tok"][input_ids] + p["emb.pos"][pos]
    ang = blocks.rotary_angles(pos, cfg["num_channels"] // heads // 2)
    x_lat, x_pre = x[:, prefix_len:], x[:, :prefix_len]
    ang_lat, ang_pre = ang[:, prefix_len:], ang[:, :prefix_len]
    pad_pre = None if pad_mask is None else pad_mask[:, :prefix_len]
    if keep is not None:
        x_pre = jnp.take_along_axis(x_pre, keep[..., None], axis=1)
        ang_pre = jnp.take_along_axis(ang_pre, keep[..., None], axis=1)
        if pad_pre is not None:
            pad_pre = jnp.take_along_axis(pad_pre, keep, axis=1)
    key_pad = None if pad_mask is None else jnp.concatenate([pad_pre, pad_mask[:, prefix_len:]], 1)

    q_in = layer_norm(x_lat, p, "cross.q_norm")
    kv_in = jnp.concatenate([layer_norm(x_pre, p, "cross.kv_norm"), q_in], axis=1)
    x = x_lat + attention(
        q_in, kv_in, p, "cross.attn", heads, causal=True, key_pad=key_pad,
        rot_q=ang_lat, rot_k=jnp.concatenate([ang_pre, ang_lat], axis=1),
    )
    x = x + mlp(x, p, "cross.mlp")
    # rotary reaches the first layer of the stack only, as in the published code
    x = blocks.self_attention_layer(x, blocks.layer_params(p, "self.0"), heads, causal=True, rot=ang_lat)
    return blocks.self_attention_stack(
        x, p, "self", range(1, cfg["num_self_attention_layers"]), heads, causal=True)


def logits(p, cfg, input_ids, prefix_len: int, pad_mask=None, keep=None):
    x = hidden(p, cfg, input_ids, prefix_len, pad_mask, keep)
    return blocks.mm("bnc,vc->bnv", x, p["emb.tok"]) + p["head.bias"]


def train_nll(p, cfg, batch, keep):
    """Summed loss and label count of one block of rows of a training
    batch: loss on the latent positions, padded labels ignored."""
    prefix_len = batch["input_ids"].shape[1] - cfg["max_latents"]
    labels = jnp.where(batch["pad_mask"], -100, batch["labels"])[:, prefix_len:]
    out = logits(p, cfg, batch["input_ids"], prefix_len, batch["pad_mask"], keep)
    return blocks.token_nll(out, labels)


def train_aux(cfg: dict, trainer_seed: int, step: int, batch: dict):
    """Per-row extras of training step ``step``: the kept prefix positions."""
    b, n = batch["input_ids"].shape
    rate = cfg["cross_attention_dropout"]
    prefix_len = n - cfg["max_latents"]
    if rate <= 0.0 or prefix_len == 0:
        return None
    return prefix_keep_indices(trainer_seed, step, b, prefix_len, rate)

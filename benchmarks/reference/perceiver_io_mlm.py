"""Plain reference of the Perceiver IO masked language model
(arXiv:2107.14795, deepmind/language-perceiver): byte embedding plus learned
positions, one cross-attention of a learned latent array over the input, a
stack of latent self-attention layers, a decoder cross-attention of learned
output queries over the latents (no attention residual), logits against the
embedding table.

Imports nothing of the program. Parameters are a flat dict of float32 arrays
made from a key by :func:`init_params`.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import blocks
from .blocks import attention, layer_norm, mlp


def _attn_shapes(shapes, name, q_in, kv_in, qk, v, out):
    for proj, (i, o) in {"q": (q_in, qk), "k": (kv_in, qk), "v": (kv_in, v), "o": (v, out)}.items():
        shapes[f"{name}.attn.{proj}.w"] = (i, o)
        shapes[f"{name}.attn.{proj}.bias"] = (o,)


def _norm_shapes(shapes, name, c):
    shapes[name + ".g"] = (c,)
    shapes[name + ".b"] = (c,)


def _mlp_shapes(shapes, name, c, widening):
    _norm_shapes(shapes, name + ".mlp.norm", c)
    shapes[name + ".mlp.hidden.w"] = (c, widening * c)
    shapes[name + ".mlp.hidden.bias"] = (widening * c,)
    shapes[name + ".mlp.out.w"] = (widening * c, c)
    shapes[name + ".mlp.out.bias"] = (c,)


def param_shapes(cfg: dict) -> dict:
    d, dl, n = cfg["d_model"], cfg["d_latents"], cfg["max_position_embeddings"]
    qk, v = cfg["qk_channels"], cfg["v_channels"]
    shapes = {
        "emb.tok": (cfg["vocab_size"], d), "emb.pos": (n, d),
        "latents": (cfg["num_latents"], dl), "dec.query": (n, d),
        "head.bias": (cfg["vocab_size"],),
    }
    _norm_shapes(shapes, "cross.q_norm", dl)
    _norm_shapes(shapes, "cross.kv_norm", d)
    _attn_shapes(shapes, "cross", dl, d, qk, v, dl)
    _mlp_shapes(shapes, "cross", dl, cfg["cross_attention_widening_factor"])
    for i in range(cfg["num_self_attends_per_block"]):
        _norm_shapes(shapes, f"self.{i}.norm", dl)
        _attn_shapes(shapes, f"self.{i}", dl, dl, qk, v, dl)
        _mlp_shapes(shapes, f"self.{i}", dl, cfg["self_attention_widening_factor"])
    _norm_shapes(shapes, "dec.q_norm", d)
    _norm_shapes(shapes, "dec.kv_norm", dl)
    _attn_shapes(shapes, "dec", d, dl, qk, d, d)
    _mlp_shapes(shapes, "dec", d, cfg["cross_attention_widening_factor"])
    return shapes


def init_params(key, cfg: dict) -> dict:
    return blocks.normal_params(key, param_shapes(cfg), cfg.get("initializer_range", 0.02))


def logits(p, cfg, input_ids, pad_mask=None):
    b, n = input_ids.shape
    hc, hs = cfg["num_cross_attention_heads"], cfg["num_self_attention_heads"]
    x = p["emb.tok"][input_ids] + p["emb.pos"][:n]
    lat = jnp.broadcast_to(p["latents"], (b, *p["latents"].shape))
    lat = lat + attention(
        layer_norm(lat, p, "cross.q_norm"), layer_norm(x, p, "cross.kv_norm"),
        p, "cross.attn", hc, key_pad=pad_mask,
    )
    lat = lat + mlp(lat, p, "cross.mlp")
    lat = blocks.self_attention_stack(lat, p, "self", range(cfg["num_self_attends_per_block"]), hs)
    query = jnp.broadcast_to(p["dec.query"], (b, *p["dec.query"].shape))
    out = attention(
        layer_norm(query, p, "dec.q_norm"), layer_norm(lat, p, "dec.kv_norm"),
        p, "dec.attn", hc,
    )
    out = out + mlp(out, p, "dec.mlp")
    return (blocks.mm("bnc,vc->bnv", out, p["emb.tok"]) + p["head.bias"])[:, :n]


def train_nll(p, cfg, batch, aux=None):
    """Summed loss and label count of one block of rows of a training
    batch: loss where the label is not -100."""
    return blocks.token_nll(logits(p, cfg, batch["input_ids"], batch["pad_mask"]), batch["labels"])


def train_aux(cfg: dict, trainer_seed: int, step: int, batch: dict):
    """No per-step randomness: every dropout of the configuration is 0."""
    return None

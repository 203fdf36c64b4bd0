"""Plain reference of LFM2's mixture-of-experts decoder (``model_type``
``lfm2_moe``; huggingface.co/LiquidAI/LFM2-24B-A2B ``config.json``), forward,
loss and gradients in float32 ``jax.numpy``, for the layers and the share of
each layer that a configuration file says one chip holds.

The equations, as published:

- layer: ``h = h + operator(rms(h))``, then ``h = h + ffn(rms(h))``; after
  the last layer one more RMS norm, then logits against the embedding table
  (tied head).
- short convolution (``conv``): ``B, C, x = split3(in_proj(u))``;
  ``y = out_proj(C * causal_depthwise_conv(B * x))``, the convolution over
  the ``conv_L_cache`` latest positions, one filter a channel, no bias.
- attention (``full_attention``): q, k, v projections without bias to
  ``num_attention_heads`` / ``num_key_value_heads`` / ``num_key_value_heads``
  heads; RMS norm over each head's channels of q and of k; rotary over the
  whole head, channel ``i`` paired with ``i + head / 2``; causal softmax
  attention in which ``heads / kv heads`` query heads share a key-value head;
  o projection.
- experts: ``s = sigmoid(router(u))`` over ``router_width`` experts; the
  ``num_experts_per_tok`` highest of ``s + bias`` are chosen; their weights are
  those values of ``s`` over (their sum + 1e-6) if ``norm_topk_prob``, times
  ``routed_scaling_factor``; a chosen expert is ``down(silu(gate(u)) * up(u))``.
  The first ``num_dense_layers`` layers use the same SwiGLU at
  ``intermediate_size`` instead. The bias only chooses and takes no gradient.

The share: the configuration holds experts ``expert_offset ..
expert_offset + num_experts - 1`` of ``router_width`` and layers
``first_layer .. first_layer + num_layers - 1`` of the published
``layer_types``. The router keeps its width; the layer's output is the held
experts' weighted outputs for the tokens that chose them and nothing for the
other choices. Every held expert is computed for every token and weighted by
0 where the token did not choose it: the plainest form, and one that cannot
drop a token.

Imports nothing of the program. Every matrix product goes through
``blocks.mm`` (six-pass ``highest`` unless a control lowers it); the router's
too. Attention is computed in blocks of queries, each recomputed in the
backward pass, and every layer is recomputed likewise, so that the real size
fits one chip beside AdamW's state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import blocks
from .blocks import mm

QUERY_BLOCK = 512
#: recompute every layer and every block of queries in the backward pass, so
#: that the real size fits; off, the same arithmetic once (XLA's cost
#: analysis would count the recomputation)
RECOMPUTE = True
#: ``init_params``' scale of the selection bias: small beside the scores'
#: spread (0.2), as a bias that balances load is. At 0.1 a few experts take
#: several times their share (fullest over mean 3.7 against 1.4 at 0.01; my
#: chip run, PR 28)
BIAS_SCALE = 0.01


def held_layers(cfg: dict) -> list:
    """``(operator, dense)`` of each layer held, in order."""
    first = cfg.get("first_layer", 0)
    kinds = cfg["layer_types"][first:first + cfg["num_layers"]]
    return [(kind, i < cfg["num_dense_layers"]) for i, kind in enumerate(kinds)]


def _head(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_shapes(cfg: dict) -> dict:
    c, d = cfg["hidden_size"], _head(cfg)
    kv = cfg["num_key_value_heads"] * d
    e, r, f = cfg["num_experts"], cfg["router_width"], cfg["moe_intermediate_size"]
    shapes = {"emb.tok": (cfg["vocab_size"], c), "out_norm.g": (c,)}
    for i, (kind, dense) in enumerate(held_layers(cfg)):
        p = f"layer.{i}"
        shapes[f"{p}.op_norm.g"] = shapes[f"{p}.ffn_norm.g"] = (c,)
        if kind == "conv":
            shapes[f"{p}.conv.in.w"] = (c, 3 * c)
            shapes[f"{p}.conv.filter"] = (cfg["conv_L_cache"], c)
            shapes[f"{p}.conv.out.w"] = (c, c)
        else:
            shapes[f"{p}.attn.q.w"], shapes[f"{p}.attn.o.w"] = (c, c), (c, c)
            shapes[f"{p}.attn.k.w"] = shapes[f"{p}.attn.v.w"] = (c, kv)
            shapes[f"{p}.attn.q_norm.g"] = shapes[f"{p}.attn.k_norm.g"] = (d,)
        if dense:
            m = cfg["intermediate_size"]
            shapes[f"{p}.mlp.gate.w"] = shapes[f"{p}.mlp.up.w"] = (c, m)
            shapes[f"{p}.mlp.down.w"] = (m, c)
        else:
            shapes[f"{p}.moe.router.w"] = (c, r)
            if cfg["use_expert_bias"]:
                shapes[f"{p}.moe.bias"] = (r,)
            shapes[f"{p}.moe.gate"] = shapes[f"{p}.moe.up"] = (e, c, f)
            shapes[f"{p}.moe.down"] = (e, f, c)
    return shapes


def init_params(key, cfg: dict) -> dict:
    """One array a name from ``key``: normal at ``init_scale`` (0.02), gains
    around one, the filters at ``L ** -0.5``, the selection bias at 0.01."""
    scale = cfg.get("init_scale", 0.02)
    out = {}
    for idx, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        s = scale
        if name.endswith("conv.filter"):
            s = cfg["conv_L_cache"] ** -0.5  # a filter of L taps has fan-in L
        elif name.endswith("moe.bias"):
            s = BIAS_SCALE
        x = s * jax.random.normal(jax.random.fold_in(key, idx), shape, jnp.float32)
        out[name] = 1.0 + x if name.endswith(".g") else x
    return out


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def swiglu(u, gate, up, down):
    return mm("...f,fc->...c", jax.nn.silu(mm("...c,cf->...f", u, gate)) * mm("...c,cf->...f", u, up), down)


def short_conv(u, p, name):
    b_gate, c_gate, x = jnp.split(mm("bnc,cd->bnd", u, p[name + ".in.w"]), 3, axis=-1)
    bx, filt = b_gate * x, p[name + ".filter"]
    n, taps = bx.shape[1], filt.shape[0]
    y = sum(
        jnp.pad(bx, ((0, 0), (taps - 1 - l, 0), (0, 0)))[:, :n] * filt[l] for l in range(taps)
    )
    return mm("bnc,cd->bnd", c_gate * y, p[name + ".out.w"])


def rotary(x, theta: float):
    """``x`` ``(b, h, n, d)`` rotated by position: channel ``i`` with
    ``i + d / 2``, frequency ``theta ** (-2 i / d)``."""
    n, d = x.shape[-2:]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def _attend_block(q, k, v, first_row: int):
    """Causal attention of the query rows ``first_row ..`` of grouped heads:
    ``q`` ``(b, hk, g, m, d)`` over the keys up to its last row."""
    m, j = q.shape[3], k.shape[2]
    logits = mm("bkgid,bkjd->bkgij", q, k) * (q.shape[-1] ** -0.5)
    allowed = jnp.arange(j)[None, :] <= first_row + jnp.arange(m)[:, None]
    logits = jnp.where(allowed, logits, jnp.finfo(jnp.float32).min)
    return mm("bkgij,bkjd->bkgid", jax.nn.softmax(logits, axis=-1), v)


def attention(u, p, name, cfg):
    b, n, c = u.shape
    h, hk, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head(cfg)
    theta, eps = cfg["rope_parameters"]["rope_theta"], cfg["norm_eps"]

    def heads(x, count):
        return x.reshape(b, n, count, d).transpose(0, 2, 1, 3)

    q = heads(mm("bnc,cd->bnd", u, p[name + ".q.w"]), h)
    k = heads(mm("bnc,cd->bnd", u, p[name + ".k.w"]), hk)
    v = heads(mm("bnc,cd->bnd", u, p[name + ".v.w"]), hk)
    q = rotary(rms_norm(q, p[name + ".q_norm.g"], eps), theta).reshape(b, hk, h // hk, n, d)
    k = rotary(rms_norm(k, p[name + ".k_norm.g"], eps), theta)
    step = min(QUERY_BLOCK, n)
    outs = []
    for lo in range(0, n, step):
        hi = min(lo + step, n)
        block = jax.checkpoint(_attend_block, static_argnums=(3,)) if RECOMPUTE else _attend_block
        outs.append(block(q[:, :, :, lo:hi], k[:, :, :hi], v[:, :, :hi], lo))
    o = jnp.concatenate(outs, axis=3).reshape(b, h, n, d)
    return mm("bnc,cd->bnd", o.transpose(0, 2, 1, 3).reshape(b, n, c), p[name + ".o.w"])


def route(u, p, name, cfg):
    """``(indices, weights)`` ``(..., k)`` over the router's full width."""
    scores = jax.nn.sigmoid(mm("...c,cr->...r", u, p[name + ".router.w"]))
    chosen = scores
    if cfg["use_expert_bias"]:
        chosen = scores + jax.lax.stop_gradient(p[name + ".bias"])
    _, idx = jax.lax.top_k(chosen, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return idx, w * cfg["routed_scaling_factor"]


def experts(u, p, name, cfg, skip=()):
    """The held experts' part of the layer's output. ``skip`` leaves held
    experts out (the planted fault of the calibration)."""
    idx, w = route(u, p, name, cfg)
    out = jnp.zeros_like(u)
    for e in range(cfg["num_experts"]):
        if e in skip:
            continue
        w_e = jnp.where(idx == e + cfg.get("expert_offset", 0), w, 0.0).sum(axis=-1)
        y = swiglu(u, p[name + ".gate"][e], p[name + ".up"][e], p[name + ".down"][e])
        out = out + w_e[..., None] * y
    return out


def layer(h, lp, cfg, kind: str, dense: bool):
    u = rms_norm(h, lp["op_norm.g"], cfg["norm_eps"])
    h = h + (short_conv(u, lp, "conv") if kind == "conv" else attention(u, lp, "attn", cfg))
    u = rms_norm(h, lp["ffn_norm.g"], cfg["norm_eps"])
    if dense:
        return h + swiglu(u, lp["mlp.gate.w"], lp["mlp.up.w"], lp["mlp.down.w"])
    return h + experts(u, lp, "moe", cfg, skip=cfg.get("_skip_experts", ()))


def logits(p, cfg, input_ids):
    h = p["emb.tok"][input_ids]
    for i, (kind, dense) in enumerate(held_layers(cfg)):
        lp = blocks.layer_params(p, f"layer.{i}")
        run = jax.checkpoint(layer, static_argnums=(2, 3, 4)) if RECOMPUTE else layer
        h = run(h, lp, _Static(cfg), kind, dense)
    h = rms_norm(h, p["out_norm.g"], cfg["norm_eps"])
    return mm("bnc,vc->bnv", h, p["emb.tok"])


class _Static(dict):
    """A configuration as a hashable static argument of ``jax.checkpoint``."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def train_nll(p, cfg, batch, aux=None):
    """Summed next-token loss and label count of one block of rows: every
    position has a label, padded labels are ignored."""
    labels = jnp.where(batch["pad_mask"], -100, batch["labels"])
    return blocks.token_nll(logits(p, cfg, batch["input_ids"]), labels)


def train_aux(cfg: dict, trainer_seed: int, step: int, batch: dict):
    """The step draws nothing at random."""
    return None

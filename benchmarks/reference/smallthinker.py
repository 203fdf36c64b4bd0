"""Plain reference of SmallThinker's decoder (``model_name``
``smallthinker_21b_instruct``; huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
``config.json``), forward, loss and gradients in float32 ``jax.numpy``, for the
layers and the share of each layer that a configuration file says one chip
holds.

The equations, ``rms(x) = x * rsqrt(mean(x^2) + eps) * gain``, ``c =
hidden_size``, ``d = head_dim``, published layer ``l``:

- ``u = rms(h)``. ``q = u Wq`` to ``num_attention_heads`` heads of ``d``, ``k =
  u Wk`` and ``v = u Wv`` to ``num_key_value_heads`` heads of ``d``; query head
  ``n`` reads key-value head ``n // (heads / kv heads)``. No bias, no norm on q
  or k.
- ``rope_layout[l] == 1``: q and k are rotated over the whole head, channel
  ``i`` paired with ``i + d / 2`` (rotate-half), frequency ``rope_theta ** (-2
  i / d)``; ``== 0``: no position signal at all.
- ``sliding_window_layout[l] == 1``: query ``t`` sees keys ``s`` with ``0 <= t -
  s < sliding_window_size`` (its own position counts); ``== 0``: every ``s <=
  t``. The mask is built from the positions.
- ``h = h + softmax(q k^T / sqrt(d) + mask) v Wo``.
- the router reads ``u``, the attention's input (the router stands before the
  attention): ``r = u Wr`` over ``router_width`` experts; the
  ``moe_num_active_primary_experts`` largest of ``r`` are chosen and weighted
  by a softmax over those chosen logits (``moe_primary_router_apply_softmax``;
  they sum to one, so ``norm_topk_prob`` has nothing left to do).
- ``x = rms(h)``; ``h = h + sum_{e chosen} w_e down_e(relu(gate_e x) * up_e x)``,
  experts ``moe_ffn_hidden_size`` wide, no shared expert, in every layer.
- after the last layer one more RMS norm, then logits against a head of its
  own (``tie_word_embeddings`` false).

The share: the configuration holds experts ``expert_offset .. expert_offset +
moe_num_primary_experts - 1`` of ``router_width``, layers ``first_layer ..
first_layer + num_layers - 1`` of the two published layouts and ``vocab_size``
rows of the embedding and of the head. The router keeps its width; an expert
layer's output is the held experts' weighted outputs for the tokens that chose
them and nothing for the other choices. Every held expert is computed for
every token and weighted by 0 where the token did not choose it: the plainest
form, and one that cannot drop a token.

Imports nothing of the program. Every matrix product goes through
``blocks.mm`` (six-pass ``highest`` unless a control lowers it); the router's
too. Attention is computed one key-value head at a time and, in it, in blocks
of queries against every key under the mask, the held experts one after
another and the loss in chunks of positions, each recomputed in the backward
pass, and every layer and each of its halves likewise, so that a 16,384-token
row fits one chip beside AdamW's state. The heads, the blocks and the experts
are loops of one compiled body (``lax.map``, ``lax.scan``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import blocks
from .blocks import mm
from .lfm2_moe import _Static, rms_norm, rotary  # the same plain functions

QUERY_BLOCK = 512
#: positions whose logits exist at a time in the loss
LOSS_CHUNK = 2048
#: recompute every layer, each of its halves, every block of queries and every
#: chunk of logits in the backward pass, so that the real size fits; off, the
#: same arithmetic once
RECOMPUTE = True
IGNORE = -100


def held_layers(cfg: dict) -> list:
    """``(rotated, windowed)`` of each layer held, in order."""
    first = cfg.get("first_layer", 0)
    held = range(first, first + cfg["num_layers"])
    return [(bool(cfg["rope_layout"][l]), bool(cfg["sliding_window_layout"][l])) for l in held]


def param_shapes(cfg: dict) -> dict:
    c, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    e, r, f = cfg["moe_num_primary_experts"], cfg["router_width"], cfg["moe_ffn_hidden_size"]
    shapes = {"emb.tok": (cfg["vocab_size"], c), "head.w": (c, cfg["vocab_size"]), "out_norm.g": (c,)}
    for i in range(cfg["num_layers"]):
        p = f"layer.{i}"
        shapes.update({
            f"{p}.op_norm.g": (c,), f"{p}.ffn_norm.g": (c,),
            f"{p}.attn.q.w": (c, q), f"{p}.attn.k.w": (c, kv), f"{p}.attn.v.w": (c, kv),
            f"{p}.attn.o.w": (q, c), f"{p}.moe.router.w": (c, r),
            f"{p}.moe.gate": (e, c, f), f"{p}.moe.up": (e, c, f), f"{p}.moe.down": (e, f, c),
        })
    return shapes


def init_params(key, cfg: dict) -> dict:
    """One array a name from ``key``: normal at ``init_scale`` (0.02), gains
    around one; the embedding at ``embed_init_scale`` and the two projections
    that write into the residual stream (the attention's ``o``, an expert's
    ``down``) at ``residual_init_scale`` (each ``init_scale`` unless the file
    says otherwise; the configuration's ``assumed.init`` says why it does)."""
    scale = cfg.get("init_scale", 0.02)
    scales = {"emb.tok": cfg.get("embed_init_scale", scale),
              "attn.o.w": cfg.get("residual_init_scale", scale),
              "moe.down": cfg.get("residual_init_scale", scale)}
    out = {}
    for idx, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        s = next((v for k, v in scales.items() if name.endswith(k)), scale)
        x = s * jax.random.normal(jax.random.fold_in(key, idx), shape, jnp.float32)
        out[name] = 1.0 + x if name.endswith(".g") else x
    return out


def allowed_keys(rows, n: int, window):
    """``(len(rows), n)``: whether the query at position ``rows[t]`` sees key
    ``s``: ``s <= rows[t]`` and, under a window, ``rows[t] - s < window``."""
    back = rows[:, None] - jnp.arange(n)[None, :]
    return (back >= 0) if window is None else (back >= 0) & (back < window)


def grouped_attention(q, k, v, window):
    """One key-value head and the query heads that read it: ``q`` ``(b, g, n,
    d)``, ``k`` and ``v`` ``(b, n, d)``; the queries in blocks of
    ``QUERY_BLOCK`` one after another, each over every key under the mask."""
    b, g, n, d = q.shape
    step = min(QUERY_BLOCK, n)
    if n % step:
        raise ValueError(f"{n} positions are no whole number of blocks of {step} queries")

    def block(at):
        q_blk, first_row = at
        logits = mm("bgid,bjd->bgij", q_blk, k) * (d ** -0.5)
        allowed = allowed_keys(first_row + jnp.arange(step), n, window)
        logits = jnp.where(allowed, logits, jnp.finfo(jnp.float32).min)
        return mm("bgij,bjd->bgid", jax.nn.softmax(logits, axis=-1), v)

    block = jax.checkpoint(block) if RECOMPUTE else block
    blocks_of_q = q.reshape(b, g, n // step, step, d).transpose(2, 0, 1, 3, 4)
    o = jax.lax.map(block, (blocks_of_q, jnp.arange(0, n, step)))
    return o.transpose(1, 2, 0, 3, 4).reshape(b, g, n, d)


def attention(u, p, name, cfg, rotated: bool, windowed: bool):
    b, n, _ = u.shape
    h, hk, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window_size"] if windowed else None
    if cfg.get("_window_ignored"):  # a planted fault: the window layers see every earlier key
        window = None

    def heads(x, count):
        return x.reshape(b, n, count, d).transpose(0, 2, 1, 3)

    q = heads(mm("bnc,cd->bnd", u, p[name + ".q.w"]), h)
    k = heads(mm("bnc,cd->bnd", u, p[name + ".k.w"]), hk)
    v = heads(mm("bnc,cd->bnd", u, p[name + ".v.w"]), hk)
    if rotated or cfg.get("_global_rotated"):  # the second: a planted fault
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])

    def group(at):
        return grouped_attention(*at, window)

    group = jax.checkpoint(group) if RECOMPUTE else group
    by_kv_head = lambda x: jnp.moveaxis(x, 1, 0)
    o = jax.lax.map(group, (by_kv_head(q.reshape(b, hk, h // hk, n, d)), by_kv_head(k), by_kv_head(v)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, h, n, d)  # (hk, b, g, n, d) -> (b, h, n, d)
    return mm("bnd,dc->bnc", o.transpose(0, 2, 1, 3).reshape(b, n, h * d), p[name + ".o.w"])


def route(seen, p, name, cfg):
    """``(indices, weights)`` ``(..., k)`` over the router's full width, from
    what the router reads."""
    logits = mm("...c,cr->...r", seen, p[name + ".router.w"])
    top, idx = jax.lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    if cfg["moe_primary_router_apply_softmax"]:
        return idx, jax.nn.softmax(top, axis=-1)
    w = jax.nn.sigmoid(top)
    return idx, w / w.sum(axis=-1, keepdims=True) if cfg["norm_topk_prob"] else w


def reglu(x, gate, up, down, act=jax.nn.relu):
    return mm("...f,fc->...c", act(mm("...c,cf->...f", x, gate)) * mm("...c,cf->...f", x, up), down)


def experts(x, seen, p, name, cfg, skip=()):
    """The held experts' part of the layer's output for ``x``, routed on
    ``seen``; the experts one after another (one expert's program, one
    expert's activations alive). ``skip`` leaves held experts out and
    ``_silu`` gates with ``silu`` (planted faults of the calibration)."""
    idx, w = route(seen, p, name, cfg)
    held = cfg["moe_num_primary_experts"]
    keep = jnp.array([e not in skip for e in range(held)])
    act = jax.nn.silu if cfg.get("_silu") else jax.nn.relu

    def add_expert(out, at):
        e, kept, gate, up, down = at
        w_e = jnp.where(idx == e + cfg.get("expert_offset", 0), w, 0.0).sum(axis=-1) * kept
        return out + w_e[..., None] * reglu(x, gate, up, down, act), None

    add_expert = jax.checkpoint(add_expert) if RECOMPUTE else add_expert
    each = (jnp.arange(held), keep, p[name + ".gate"], p[name + ".up"], p[name + ".down"])
    return jax.lax.scan(add_expert, jnp.zeros_like(x), each)[0]


def _attention_half(h, lp, cfg, rotated: bool, windowed: bool):
    u = rms_norm(h, lp["op_norm.g"], cfg["rms_norm_eps"])
    return h + attention(u, lp, "attn", cfg, rotated, windowed), u


def _expert_half(h, u, lp, cfg):
    x = rms_norm(h, lp["ffn_norm.g"], cfg["rms_norm_eps"])
    seen = x if cfg.get("_router_reads_ffn_input") else u  # the first: a planted fault
    return h + experts(x, seen, lp, "moe", cfg, skip=cfg.get("_skip_experts", ()))


def layer(h, lp, cfg, rotated: bool, windowed: bool):
    """One layer. Its two halves are recomputed each on its own in the
    backward pass (inside the layer's own recomputation), so that the
    attention's activations and the experts' are never alive together."""
    attend, ffn = _attention_half, _expert_half
    if RECOMPUTE:
        attend = jax.checkpoint(attend, static_argnums=(2, 3, 4))
        ffn = jax.checkpoint(ffn, static_argnums=(3,))
    h, u = attend(h, lp, cfg, rotated, windowed)
    return ffn(h, u, lp, cfg)


def hidden(p, cfg, input_ids):
    """The last held layer's output, before the output norm."""
    h = p["emb.tok"][input_ids]
    for i, (rotated, windowed) in enumerate(held_layers(cfg)):
        run = jax.checkpoint(layer, static_argnums=(2, 3, 4)) if RECOMPUTE else layer
        h = run(h, blocks.layer_params(p, f"layer.{i}"), _Static(cfg), rotated, windowed)
    return h


def _head_logits(x, gain, head, eps):
    return mm("bnc,cv->bnv", rms_norm(x, gain, eps), head)


def logits(p, cfg, input_ids):
    return _head_logits(hidden(p, cfg, input_ids), p["out_norm.g"], p["head.w"], cfg["rms_norm_eps"])


def train_nll(p, cfg, batch, aux=None):
    """Summed next-token loss and label count of one block of rows,
    ``LOSS_CHUNK`` positions' logits at a time: every position has a label,
    padded labels are ignored."""
    labels = jnp.where(batch["pad_mask"], IGNORE, batch["labels"])
    x, eps = hidden(p, cfg, batch["input_ids"]), cfg["rms_norm_eps"]

    def chunk(x_c, gain, head, labels_c):
        return blocks.token_nll(_head_logits(x_c, gain, head, eps), labels_c, IGNORE)

    run = jax.checkpoint(chunk) if RECOMPUTE else chunk
    total, count = 0.0, 0
    for lo in range(0, x.shape[1], LOSS_CHUNK):
        t, c = run(x[:, lo:lo + LOSS_CHUNK], p["out_norm.g"], p["head.w"], labels[:, lo:lo + LOSS_CHUNK])
        total, count = total + t, count + c
    return total, count


def train_aux(cfg: dict, trainer_seed: int, step: int, batch: dict):
    """The step draws nothing at random."""
    return None

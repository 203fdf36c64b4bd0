"""Plain reference of Keye-VL-2.0-30B-A3B's language model (``model_type``
``KeyeVL2``; huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``),
forward, loss and gradients in float32 ``jax.numpy``, for the layers and the
share of each layer that a configuration file says one chip holds.

The equations, ``rms(x) = x * rsqrt(mean(x^2) + eps) * gain``, ``LN`` a layer
norm with gain and bias, ``c = hidden_size``, ``d = head_dim``, ``H, e =
sa_config``'s ``indexer_num_heads`` and ``indexer_head_dim``, ``K =
sa_config["topk"]``; every layer the same:

- ``u = rms(h)``. ``q = rms_h(u Wq)`` to ``num_attention_heads`` heads of ``d``,
  ``k = rms_h(u Wk)`` and ``v = u Wv`` to ``num_key_value_heads`` heads; query
  head ``n`` reads key-value head ``n // (heads / kv heads)``; ``rms_h`` is an
  RMS norm over a head's channels with one gain a channel. q and k are rotated
  over the whole head, channel ``i`` with ``i + d / 2`` (rotate-half),
  frequency ``rope_theta ** (-2 i / d)``: M-RoPE's three sections carry one
  position for text and reduce to this.
- the indexer reads ``ub = stop_gradient(u)``: ``qI = rope(ub WqI)`` to ``H``
  heads of ``e``, ``kI = rope(LN(ub WkI))`` one head of ``e``, ``w = ub Ww /
  sqrt(H e)``; ``I[t, s] = sum_j w_j[t] relu(qI_j[t] . kI[s])`` for ``s <= t``.
- ``S_t``: the ``min(t + 1, K)`` keys ``s <= t`` of largest ``I[t, s]``
  (``jax.lax.top_k`` on float32).
- ``P^n[t] = softmax over s in S_t of q^n[t] . k[s] / sqrt(d)``; ``h = h +
  (sum_s P^n[t, s] v[s])_n Wo``.
- the indexer's loss: ``p[t] = stop_gradient(mean_n P^n[t])``, ``L_I = mean_t
  KL(p[t] || softmax_{s in S_t} I[t, s])``, one a layer, summed over layers
  and added to the LM loss with weight 1.
- the experts: ``x = rms(h)``; ``r = x Wr`` over ``router_width`` (the
  published ``num_experts``); the ``num_experts_per_tok`` largest chosen,
  weighted by a softmax over those chosen (Qwen3-MoE's softmax over all,
  renormalised over the chosen: the same weights); ``h = h + sum_{e chosen}
  g_e down_e(silu(gate_e x) * up_e x)``, no shared expert, no bias.
- after the last layer one more RMS norm, then logits against a head of its
  own (``tie_word_embeddings`` false).

The share: experts ``expert_offset .. expert_offset + num_experts - 1`` of
``router_width``, layers ``0 .. num_layers - 1``, ``vocab_size`` rows of the
embedding and of the head; the whole attention and indexer. Every held expert
is computed for every token and weighted by 0 where the token did not choose
it.

Imports nothing of the program. Every matrix product goes through
``blocks.mm`` (six-pass ``highest`` unless a control lowers it); the indexer's
and the router's too. A layer is computed in blocks of ``QUERY_BLOCK``
queries: each block's indexer scores (one indexer head at a time), its
selection, its attention (one key-value head at a time) and its part of the
indexer's loss; the experts one after another and the loss in chunks of
positions; each recomputed in the backward pass, so that a 16,384-token row
fits one chip beside AdamW's state and nothing ``(n, n)`` in float32 lives
whole.

Planted faults for the calibration (keys of the configuration dict):
``_dense_attention`` (every causal key, no selection), ``_topk`` (another
``K``), ``_no_indexer_loss`` (the loss left out: the indexer's gradients 0),
``_selection_shift`` (every query takes the keys one position before those it
chose), ``_skip_experts`` (held experts left out).
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
#: recompute every layer, every block of queries, every expert and every chunk
#: of logits in the backward pass, so that the real size fits; off, the same
#: arithmetic once
RECOMPUTE = True
IGNORE = -100
_NEG = jnp.finfo(jnp.float32).min


def param_shapes(cfg: dict) -> dict:
    c, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    sa = cfg["sa_config"]
    hi, e = sa["indexer_num_heads"], sa["indexer_head_dim"]
    x, r, f = cfg["num_experts"], cfg["router_width"], cfg["moe_intermediate_size"]
    shapes = {"emb.tok": (cfg["vocab_size"], c), "head.w": (c, cfg["vocab_size"]), "out_norm.g": (c,)}
    for i in range(cfg["num_layers"]):
        p = f"layer.{i}"
        shapes.update({
            f"{p}.op_norm.g": (c,), f"{p}.ffn_norm.g": (c,),
            f"{p}.attn.q.w": (c, q), f"{p}.attn.k.w": (c, kv), f"{p}.attn.v.w": (c, kv),
            f"{p}.attn.o.w": (q, c), f"{p}.attn.q_norm.g": (d,), f"{p}.attn.k_norm.g": (d,),
            f"{p}.idx.q.w": (c, hi * e), f"{p}.idx.k.w": (c, e), f"{p}.idx.k_norm.g": (e,),
            f"{p}.idx.k_norm.b": (e,), f"{p}.idx.w.w": (c, hi),
            f"{p}.moe.router.w": (c, r),
            f"{p}.moe.gate": (x, c, f), f"{p}.moe.up": (x, c, f), f"{p}.moe.down": (x, f, c),
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


def layer_norm(x, gain, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def topk(cfg: dict) -> int:
    return cfg.get("_topk", cfg["sa_config"]["topk"])


def index_scores(qi, ki, w):
    """``(b, m, n)``: ``sum_j w_j relu(qi_j . ki)`` for ``qi`` ``(H, b, m,
    e)``, ``ki`` ``(b, n, e)``, ``w`` ``(H, b, m)``; one head at a time."""
    def head(total, at):
        q_j, w_j = at
        return total + w_j[..., None] * jax.nn.relu(mm("bme,bne->bmn", q_j, ki)), None

    zero = jnp.zeros((qi.shape[1], qi.shape[2], ki.shape[1]), jnp.float32)
    return jax.lax.scan(head, zero, (qi, w))[0]


def selected(scores, rows, k: int):
    """``(b, m, n)`` bool: the ``min(t + 1, k)`` keys ``s <= t`` of largest
    score for the queries at positions ``rows``, by ``lax.top_k``."""
    b, m, n = scores.shape
    causal = jnp.arange(n)[None, :] <= rows[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(k, n))
    chosen = jnp.zeros((b, m, n), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(m)[None, :, None], idx].set(True)
    return chosen & causal


def attention(u, p, name, cfg):
    """The layer's attention output ``(b, n, c)`` and the sum over its rows
    of the indexer's KL term."""
    b, n, _ = u.shape
    h, hk, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa, eps, theta = cfg["sa_config"], cfg["rms_norm_eps"], cfg["rope_theta"]
    hi, e = sa["indexer_num_heads"], sa["indexer_head_dim"]

    def heads(x, count, width):
        return x.reshape(b, n, count, width).transpose(0, 2, 1, 3)

    q = rotary(rms_norm(heads(mm("bnc,cd->bnd", u, p[name + ".q.w"]), h, d), p[name + ".q_norm.g"], eps), theta)
    k = rotary(rms_norm(heads(mm("bnc,cd->bnd", u, p[name + ".k.w"]), hk, d), p[name + ".k_norm.g"], eps), theta)
    v = heads(mm("bnc,cd->bnd", u, p[name + ".v.w"]), hk, d)
    ub = jax.lax.stop_gradient(u)
    qi = rotary(heads(mm("bnc,cd->bnd", ub, p["idx.q.w"]), hi, e), theta)  # (b, H, n, e)
    ki = layer_norm(mm("bnc,cd->bnd", ub, p["idx.k.w"]), p["idx.k_norm.g"], p["idx.k_norm.b"], eps)
    ki = rotary(ki[:, None], theta)[:, 0]
    w = mm("bnc,ch->bnh", ub, p["idx.w.w"]) * (hi * e) ** -0.5
    step = min(QUERY_BLOCK, n)
    if n % step:
        raise ValueError(f"{n} positions are no whole number of blocks of {step} queries")
    kv = (k, v)

    def block(at):
        q_blk, qi_blk, w_blk, first = at  # (b, h, m, d), (b, H, m, e), (b, m, H)
        rows = first + jnp.arange(step)
        scores = index_scores(qi_blk.transpose(1, 0, 2, 3), ki, w_blk.transpose(2, 0, 1))
        if cfg.get("_dense_attention"):
            chosen = jnp.arange(n)[None, None, :] <= rows[None, :, None]
        else:
            chosen = selected(jax.lax.stop_gradient(scores), rows, topk(cfg))
        if cfg.get("_selection_shift"):  # key s where the indexer chose s + 1
            chosen = jnp.pad(chosen[..., 1:], ((0, 0), (0, 0), (0, 1)))

        def kv_head(probs_sum, at):
            q_g, k_h, v_h = at  # (b, g, m, d), (b, n, d), (b, n, d)
            logits = jnp.where(chosen[:, None], mm("bgmd,bnd->bgmn", q_g, k_h) * d ** -0.5, _NEG)
            probs = jax.nn.softmax(logits, axis=-1)
            return probs_sum + probs.sum(axis=1), mm("bgmn,bnd->bgmd", probs, v_h)

        kv_head = jax.checkpoint(kv_head) if RECOMPUTE else kv_head
        by_kv = (jnp.moveaxis(q_blk.reshape(b, hk, h // hk, step, d), 1, 0),
                 jnp.moveaxis(kv[0], 1, 0), jnp.moveaxis(kv[1], 1, 0))
        probs_sum, o = jax.lax.scan(kv_head, jnp.zeros((b, step, n), jnp.float32), by_kv)
        target = jax.lax.stop_gradient(probs_sum / h)
        log_q = jax.nn.log_softmax(jnp.where(chosen, scores, _NEG), axis=-1)
        kl = jnp.where(chosen, jax.scipy.special.xlogy(target, target) - target * log_q, 0.0)
        return jnp.moveaxis(o, 0, 1), jnp.sum(kl)  # (b, hk, g, m, d)

    block = jax.checkpoint(block) if RECOMPUTE else block
    by_block = lambda x, axis: jnp.moveaxis(
        x.reshape(*x.shape[:axis], n // step, step, *x.shape[axis + 1:]), axis, 0)
    o, kl = jax.lax.map(block, (by_block(q, 2), by_block(qi, 2), by_block(w, 1), jnp.arange(0, n, step)))
    # (blocks, b, hk, g, m, d) -> (b, n, h * d)
    o = jnp.moveaxis(o, 0, 3).reshape(b, h, n, d).transpose(0, 2, 1, 3).reshape(b, n, h * d)
    index_loss = 0.0 if cfg.get("_no_indexer_loss") else jnp.sum(kl) / (b * n)
    return mm("bnd,dc->bnc", o, p[name + ".o.w"]), index_loss


def route(x, p, name, cfg):
    """``(indices, weights)`` ``(..., k)`` over the router's full width:
    the largest logits, weighted by a softmax over the chosen."""
    logits = mm("...c,cr->...r", x, p[name + ".router.w"])
    top, idx = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    return idx, jax.nn.softmax(top, axis=-1)


def swiglu(x, gate, up, down):
    return mm("...f,fc->...c", jax.nn.silu(mm("...c,cf->...f", x, gate)) * mm("...c,cf->...f", x, up), down)


def experts(x, p, name, cfg, skip=()):
    """The held experts' part of the layer's output for ``x``; the experts
    one after another. ``skip`` leaves held experts out (a planted fault)."""
    idx, w = route(x, p, name, cfg)
    held = cfg["num_experts"]
    keep = jnp.array([e not in skip for e in range(held)])

    def add_expert(out, at):
        e, kept, gate, up, down = at
        w_e = jnp.where(idx == e + cfg.get("expert_offset", 0), w, 0.0).sum(axis=-1) * kept
        return out + w_e[..., None] * swiglu(x, gate, up, down), None

    add_expert = jax.checkpoint(add_expert) if RECOMPUTE else add_expert
    each = (jnp.arange(held), keep, p[name + ".gate"], p[name + ".up"], p[name + ".down"])
    return jax.lax.scan(add_expert, jnp.zeros_like(x), each)[0]


def layer(h, lp, cfg):
    """One layer: ``(h, the layer's indexer loss)``."""
    u = rms_norm(h, lp["op_norm.g"], cfg["rms_norm_eps"])
    out, index_loss = attention(u, lp, "attn", cfg)
    h = h + out
    x = rms_norm(h, lp["ffn_norm.g"], cfg["rms_norm_eps"])
    return h + experts(x, lp, "moe", cfg, skip=cfg.get("_skip_experts", ())), index_loss


def hidden(p, cfg, input_ids):
    """The last held layer's output, before the output norm, and the layers'
    indexer losses summed."""
    h, total = p["emb.tok"][input_ids], 0.0
    for i in range(cfg["num_layers"]):
        run = jax.checkpoint(layer, static_argnums=(2,)) if RECOMPUTE else layer
        h, index_loss = run(h, blocks.layer_params(p, f"layer.{i}"), _Static(cfg))
        total = total + index_loss
    return h, total


def _head_logits(x, gain, head, eps):
    return mm("bnc,cv->bnv", rms_norm(x, gain, eps), head)


def logits(p, cfg, input_ids):
    x, _ = hidden(p, cfg, input_ids)
    return _head_logits(x, p["out_norm.g"], p["head.w"], cfg["rms_norm_eps"])


def losses(p, cfg, batch):
    """``(lm_loss, indexer_loss)`` of a batch: the mean next-token loss over
    its labels, and the layers' indexer losses summed."""
    total, count, index_loss = _sums(p, cfg, batch)
    return total / count, index_loss


def _sums(p, cfg, batch):
    labels = jnp.where(batch["pad_mask"], IGNORE, batch["labels"])
    x, index_loss = hidden(p, cfg, batch["input_ids"])
    eps = cfg["rms_norm_eps"]

    def chunk(x_c, gain, head, labels_c):
        return blocks.token_nll(_head_logits(x_c, gain, head, eps), labels_c, IGNORE)

    run = jax.checkpoint(chunk) if RECOMPUTE else chunk
    total, count = 0.0, 0
    for lo in range(0, x.shape[1], LOSS_CHUNK):
        t, c = run(x[:, lo:lo + LOSS_CHUNK], p["out_norm.g"], p["head.w"], labels[:, lo:lo + LOSS_CHUNK])
        total, count = total + t, count + c
    return total, count, index_loss


def train_nll(p, cfg, batch, aux=None):
    """The block of rows' part of ``lm_loss + indexer_loss`` as a sum and a
    label count: the summed next-token loss plus the indexer loss once for
    every label, so that the mean over the batch's labels is the sum of the
    two means (every position of a packed row has a label)."""
    total, count, index_loss = _sums(p, cfg, batch)
    return total + count * index_loss, count


def train_aux(cfg: dict, trainer_seed: int, step: int, batch: dict):
    """The step draws nothing at random."""
    return None

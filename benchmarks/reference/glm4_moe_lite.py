"""Plain reference of GLM-4.7-Flash's decoder (``model_type``
``glm4_moe_lite``; huggingface.co/zai-org/GLM-4.7-Flash ``config.json``),
forward, loss and gradients in float32 ``jax.numpy``, for the layers and the
share of each layer that a configuration file says one chip holds.

The equations (the DeepSeek-V3 forms), ``rms(x) = x * rsqrt(mean(x^2) + eps)
* gain``, ``c = hidden_size``, ``h = num_attention_heads``:

- layer: ``x = x + attention(rms(x))``, then ``x = x + ffn(rms(x))``; after the
  last layer one more RMS norm, then logits against a head of its own
  (``tie_word_embeddings`` false).
- latent attention: ``cq = rms(u Wqa)`` (``q_lora_rank``); ``q = cq Wqb`` ->
  ``h`` heads of ``[q_nope qk_nope_head_dim | q_rot qk_rope_head_dim]``.
  ``a = u Wkva``: ``ckv = rms(a[:kv_lora_rank])``, ``k_rot = a[kv_lora_rank:]``,
  one head. ``ckv Wkvb`` -> ``h`` heads of ``[k_nope | v v_head_dim]``.
  ``q_h = [q_nope_h | rope(q_rot_h)]``, ``k_h = [k_nope_h | rope(k_rot)]``, the
  same rotary key for every head; rotary pairs channel ``i`` with ``i +
  qk_rope_head_dim / 2`` at ``rope_theta``, no scaling. Scores ``q_h k_h^T /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax, times ``v_h``;
  the ``h * v_head_dim`` outputs through ``Wo``. No bias anywhere.
- feed-forward: the first ``first_k_dense_replace`` layers
  ``down(silu(gate u) * up u)`` at ``intermediate_size``. The others: ``s =
  sigmoid(u Wr)`` over ``router_width`` experts; the ``num_experts_per_tok``
  highest of ``s + bias`` are chosen (``noaux_tc`` with ``n_group =
  topk_group = 1``: no group limit); their weights are those values of ``s``
  over (their sum + 1e-6) if ``norm_topk_prob``, times
  ``routed_scaling_factor``; a chosen expert is the same SwiGLU at
  ``moe_intermediate_size``; **plus** ``n_shared_experts`` shared experts, one
  SwiGLU of ``n_shared_experts * moe_intermediate_size`` that every token
  takes, unweighted. The bias only chooses and takes no gradient.
- prediction module (``num_nextn_predict_layers`` 1; the DeepSeek-V3 form,
  assumed: the config gives only the count): at position ``i`` with the last
  layer's hidden state ``h_i`` (before the output norm) and the next token
  ``t_{i+1}``: ``z_i = [rms_e(Emb(t_{i+1})) | rms_h(h_i)] Weh`` (``2 c -> c``),
  one whole expert layer on ``z``, ``logits' = rms'(z') Whead`` with the
  model's own embedding and head; its label is ``t_{i+2}``, and a row's last
  position has none. ``loss = CE(logits, t_{i+1}) + mtp_loss_weight *
  CE(logits', t_{i+2})``, each a mean over its own labels.

The share: the configuration holds experts ``expert_offset .. expert_offset
+ n_routed_experts - 1`` of ``router_width``, the shared expert whole, layers
``first_layer .. first_layer + num_layers - 1`` and ``vocab_size`` rows of
the embedding and of the head. The router keeps its width; an expert layer's
output is the held experts' weighted outputs for the tokens that chose them,
the shared expert's, and nothing for the other choices. Every held expert is
computed for every token and weighted by 0 where the token did not choose
it: the plainest form, and one that cannot drop a token.

Imports nothing of the program. Every matrix product goes through
``blocks.mm`` (six-pass ``highest`` unless a control lowers it); the router's
too. Attention is computed head by head and, in a head, in blocks of queries,
and the held experts one after another, each recomputed in the backward pass;
every layer and each of its halves is recomputed likewise and the two losses
are taken in chunks of positions, so that the real size fits one chip beside
AdamW's state. The heads, the blocks, the slices and the experts are loops of
one compiled body (``lax.map``, ``lax.scan``: ``_each``, ``_fold``), so that
the six layers' program compiles in about a minute and not in four.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import blocks
from .blocks import mm
from .lfm2_moe import _Static, rms_norm, rotary, swiglu  # the same plain functions

QUERY_BLOCK = 1024
#: positions whose logits exist at a time in either loss
LOSS_CHUNK = 2048
#: hidden channels of the dense feed-forward whose activations exist at a time
DENSE_SLICE = 2048
#: recompute every layer, every block of queries and every chunk of logits
#: in the backward pass, so that the real size fits; off, the same arithmetic
#: once (XLA's cost analysis would count the recomputation)
RECOMPUTE = True
#: the loops over heads, blocks of queries, slices and experts as Python
#: loops: the same arithmetic, but XLA's cost analysis counts a loop's body once
UNROLL_LOOPS = False
#: ``init_params``' scale of the selection bias (``reference/lfm2_moe.py``)
BIAS_SCALE = 0.01
IGNORE = -100


def held_layers(cfg: dict) -> list:
    """Of each layer held, in order, whether its feed-forward is dense."""
    first = cfg.get("first_layer", 0)
    return [first + i < cfg["first_k_dense_replace"] for i in range(cfg["num_layers"])]


def _layer_shapes(cfg: dict, dense: bool) -> dict:
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    shapes = {
        "op_norm.g": (c,), "ffn_norm.g": (c,),
        "attn.q_a.w": (c, rq), "attn.q_a_norm.g": (rq,), "attn.q_b.w": (rq, h * (dn + dr)),
        "attn.kv_a.w": (c, rkv + dr), "attn.kv_a_norm.g": (rkv,),
        "attn.kv_b.w": (rkv, h * (dn + dv)), "attn.o.w": (h * dv, c),
    }
    if dense:
        m = cfg["intermediate_size"]
        shapes.update({"mlp.gate.w": (c, m), "mlp.up.w": (c, m), "mlp.down.w": (m, c)})
        return shapes
    e, r, f = cfg["n_routed_experts"], cfg["router_width"], cfg["moe_intermediate_size"]
    s = cfg["n_shared_experts"] * f
    shapes.update({
        "moe.router.w": (c, r), "moe.bias": (r,),
        "moe.gate": (e, c, f), "moe.up": (e, c, f), "moe.down": (e, f, c),
        "shared.gate.w": (c, s), "shared.up.w": (c, s), "shared.down.w": (s, c),
    })
    return shapes


def param_shapes(cfg: dict) -> dict:
    c = cfg["hidden_size"]
    shapes = {"emb.tok": (cfg["vocab_size"], c), "head.w": (c, cfg["vocab_size"]), "out_norm.g": (c,)}
    for i, dense in enumerate(held_layers(cfg)):
        shapes.update({f"layer.{i}.{k}": v for k, v in _layer_shapes(cfg, dense).items()})
    if cfg["num_nextn_predict_layers"]:
        shapes.update({"mtp.embed_norm.g": (c,), "mtp.hidden_norm.g": (c,), "mtp.eh.w": (2 * c, c),
                       "mtp.out_norm.g": (c,)})
        shapes.update({f"mtp.layer.{k}": v for k, v in _layer_shapes(cfg, False).items()})
    return shapes


def init_params(key, cfg: dict) -> dict:
    """One array a name from ``key``: normal at ``init_scale`` (0.02), gains
    around one, the selection bias at 0.01, the embedding at
    ``embed_init_scale`` (``init_scale`` unless the file says otherwise: with
    rows of 0.02 the first attention layer's output, a running mean of values
    that is nearly the same at every position, is several times the embedding,
    every position's hidden state is nearly one vector and every token chooses
    the same experts; PERF.md, PR 33)."""
    scale = cfg.get("init_scale", 0.02)
    scales = {"moe.bias": BIAS_SCALE, "emb.tok": cfg.get("embed_init_scale", scale)}
    out = {}
    for idx, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        s = next((v for k, v in scales.items() if name.endswith(k)), scale)
        x = s * jax.random.normal(jax.random.fold_in(key, idx), shape, jnp.float32)
        out[name] = 1.0 + x if name.endswith(".g") else x
    return out


def _each(fn, xs):
    """``fn`` of every item along the leading axis of ``xs``, one after
    another, the results stacked."""
    if not UNROLL_LOOPS:
        return jax.lax.map(fn, xs)
    count = jax.tree_util.tree_leaves(xs)[0].shape[0]
    return jnp.stack([fn(jax.tree_util.tree_map(lambda x: x[i], xs)) for i in range(count)])


def _fold(fn, start, xs):
    """``fn(total, item)`` over the leading axis of ``xs``, from ``start``."""
    if not UNROLL_LOOPS:
        return jax.lax.scan(lambda total, item: (fn(total, item), None), start, xs)[0]
    for i in range(jax.tree_util.tree_leaves(xs)[0].shape[0]):
        start = fn(start, jax.tree_util.tree_map(lambda x: x[i], xs))
    return start


def causal_attention(q, k, v):
    """One head: ``q``, ``k`` ``(b, n, d)`` and ``v`` ``(b, n, dv)``; the
    queries in blocks of ``QUERY_BLOCK`` one after another, each over every
    key with those after a query's own position masked."""
    b, n, d = q.shape
    step = min(QUERY_BLOCK, n)
    if n % step:
        raise ValueError(f"{n} positions are no whole number of blocks of {step} queries")

    def block(at):
        q_blk, first_row = at
        logits = mm("bid,bjd->bij", q_blk, k) * (d ** -0.5)
        allowed = jnp.arange(n)[None, :] <= first_row + jnp.arange(step)[:, None]
        logits = jnp.where(allowed, logits, jnp.finfo(jnp.float32).min)
        return mm("bij,bjd->bid", jax.nn.softmax(logits, axis=-1), v)

    block = jax.checkpoint(block) if RECOMPUTE else block
    blocks_of_q = q.reshape(b, n // step, step, d).transpose(1, 0, 2, 3)
    o = _each(block, (blocks_of_q, jnp.arange(0, n, step)))
    return o.transpose(1, 0, 2, 3).reshape(b, n, -1)


def attention(u, p, name, cfg):
    """Latent attention head by head (one head's program a layer; one head's
    queries, keys, values and scores alive)."""
    b, n, _ = u.shape
    h, rkv, eps = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    dn, theta = cfg["qk_nope_head_dim"], cfg["rope_theta"]
    cq = rms_norm(mm("bnc,cr->bnr", u, p[name + ".q_a.w"]), p[name + ".q_a_norm.g"], eps)
    a = mm("bnc,cr->bnr", u, p[name + ".kv_a.w"])
    ckv = rms_norm(a[..., :rkv], p[name + ".kv_a_norm.g"], eps)
    k_rot = a[..., rkv:]  # one rotary key head, the same for every head
    if not cfg.get("_unrotated_key"):  # the planted fault: the key's positions lost
        k_rot = rotary(k_rot, theta)

    def head(weights):
        w_q, w_kv = weights  # this head's columns of the two up-projections
        q, kv = mm("bnr,rd->bnd", cq, w_q), mm("bnr,rd->bnd", ckv, w_kv)
        q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], theta)], axis=-1)
        k = jnp.concatenate([kv[..., :dn], k_rot], axis=-1)
        return causal_attention(q, k, kv[..., dn:])

    head = jax.checkpoint(head) if RECOMPUTE else head
    by_head = lambda w: w.reshape(w.shape[0], h, -1).transpose(1, 0, 2)
    o = _each(head, (by_head(p[name + ".q_b.w"]), by_head(p[name + ".kv_b.w"])))
    o = o.transpose(1, 2, 0, 3).reshape(b, n, -1)  # (h, b, n, dv) -> (b, n, h dv)
    return mm("bnd,dc->bnc", o, p[name + ".o.w"])


def route(u, p, name, cfg):
    """``(indices, weights)`` ``(..., k)`` over the router's full width."""
    scores = jax.nn.sigmoid(mm("...c,cr->...r", u, p[name + ".router.w"]))
    chosen = scores + jax.lax.stop_gradient(p[name + ".bias"])
    _, idx = jax.lax.top_k(chosen, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return idx, w * cfg["routed_scaling_factor"]


def experts(u, p, name, cfg, skip=()):
    """The held routed experts' part of the layer's output, the experts one
    after another (one expert's program, one expert's activations alive).
    ``skip`` leaves held experts out (a planted fault of the calibration)."""
    idx, w = route(u, p, name, cfg)
    keep = jnp.array([e not in skip for e in range(cfg["n_routed_experts"])])

    def add_expert(out, at):
        e, kept, gate, up, down = at
        w_e = jnp.where(idx == e + cfg.get("expert_offset", 0), w, 0.0).sum(axis=-1) * kept
        return out + w_e[..., None] * swiglu(u, gate, up, down)

    add_expert = jax.checkpoint(add_expert) if RECOMPUTE else add_expert
    held = (jnp.arange(cfg["n_routed_experts"]), keep, p[name + ".gate"], p[name + ".up"], p[name + ".down"])
    return _fold(add_expert, jnp.zeros_like(u), held)


def shared_expert(u, p, name):
    return swiglu(u, p[name + ".gate.w"], p[name + ".up.w"], p[name + ".down.w"])


def dense_feed_forward(u, gate, up, down):
    """``down(silu(gate u) * up u)`` as the sum over slices of the hidden
    channels, one after another, as the experts are: the model's widest
    activations, a slice alive at a time."""
    m = gate.shape[1]
    parts = m // DENSE_SLICE if m % DENSE_SLICE == 0 else 1

    def add_slice(out, weights):
        return out + swiglu(u, *weights)

    add_slice = jax.checkpoint(add_slice) if RECOMPUTE else add_slice
    columns = lambda w: w.reshape(w.shape[0], parts, -1).transpose(1, 0, 2)
    slices = (columns(gate), columns(up), down.reshape(parts, -1, down.shape[1]))
    return _fold(add_slice, jnp.zeros_like(u), slices)


def feed_forward(u, lp, cfg, dense: bool):
    if dense:
        return dense_feed_forward(u, lp["mlp.gate.w"], lp["mlp.up.w"], lp["mlp.down.w"])
    out = experts(u, lp, "moe", cfg, skip=cfg.get("_skip_experts", ()))
    if not cfg.get("_skip_shared"):  # the planted fault: the shared expert left out
        out = out + shared_expert(u, lp, "shared")
    return out


def layer(h, lp, cfg, dense: bool):
    """One layer. Its two halves are recomputed each on its own in the
    backward pass (inside the layer's own recomputation), so that the
    attention's activations and the feed-forward's are never alive together."""
    attend, ffn = attention, feed_forward
    if RECOMPUTE:
        attend = jax.checkpoint(attention, static_argnums=(2, 3))
        ffn = jax.checkpoint(feed_forward, static_argnums=(2, 3))
    h = h + attend(rms_norm(h, lp["op_norm.g"], cfg["rms_norm_eps"]), lp, "attn", cfg)
    return h + ffn(rms_norm(h, lp["ffn_norm.g"], cfg["rms_norm_eps"]), lp, cfg, dense)


def _run_layer(h, lp, cfg, dense: bool):
    run = jax.checkpoint(layer, static_argnums=(2, 3)) if RECOMPUTE else layer
    return run(h, lp, _Static(cfg), dense)


def hidden(p, cfg, input_ids):
    """The last held layer's output, before the output norm."""
    h = p["emb.tok"][input_ids]
    for i, dense in enumerate(held_layers(cfg)):
        h = _run_layer(h, blocks.layer_params(p, f"layer.{i}"), cfg, dense)
    return h


def mtp_hidden(p, cfg, h, next_ids):
    """The prediction module's output before its norm, from the main model's
    last hidden state and the next tokens' ids."""
    eps = cfg["rms_norm_eps"]
    e = rms_norm(p["emb.tok"][next_ids], p["mtp.embed_norm.g"], eps)
    z = mm("bnd,dc->bnc", jnp.concatenate([e, rms_norm(h, p["mtp.hidden_norm.g"], eps)], axis=-1),
           p["mtp.eh.w"])
    return _run_layer(z, blocks.layer_params(p, "mtp.layer"), cfg, False)


def _head_logits(x, gain, head, eps):
    return mm("bnc,cv->bnv", rms_norm(x, gain, eps), head)


def logits(p, cfg, input_ids):
    return _head_logits(hidden(p, cfg, input_ids), p["out_norm.g"], p["head.w"], cfg["rms_norm_eps"])


def mtp_logits(p, cfg, input_ids, next_ids):
    z = mtp_hidden(p, cfg, hidden(p, cfg, input_ids), next_ids)
    return _head_logits(z, p["mtp.out_norm.g"], p["head.w"], cfg["rms_norm_eps"])


def _chunked_nll(x, gain, head, labels, eps):
    """Summed loss and label count of ``x``'s positions under ``labels``,
    ``LOSS_CHUNK`` positions' logits at a time."""
    def chunk(x_c, gain, head, labels_c):
        return blocks.token_nll(_head_logits(x_c, gain, head, eps), labels_c, IGNORE)

    run = jax.checkpoint(chunk) if RECOMPUTE else chunk
    total, count = 0.0, 0
    for lo in range(0, x.shape[1], LOSS_CHUNK):
        t, c = run(x[:, lo:lo + LOSS_CHUNK], gain, head, labels[:, lo:lo + LOSS_CHUNK])
        total, count = total + t, count + c
    return total, count


def train_nll(p, cfg, batch, aux=None):
    """``(total, count)`` of one block of rows, such that ``total / count`` over
    the batch's blocks is ``CE(logits, t_{i+1}) + mtp_loss_weight *
    CE(logits', t_{i+2})``: the next-token term's summed loss and label count,
    the second term's mean scaled to the first's count. The scaling is the
    block's own, which is the batch's wherever every row has as many labels
    as every other (packed rows: ``n`` and ``n - 1``). Padded labels are
    ignored; the module reads a padded label as token 0 and ignores it."""
    labels = jnp.where(batch["pad_mask"], IGNORE, batch["labels"])
    h = hidden(p, cfg, batch["input_ids"])
    eps = cfg["rms_norm_eps"]
    total, count = _chunked_nll(h, p["out_norm.g"], p["head.w"], labels, eps)
    if not cfg["num_nextn_predict_layers"] or cfg.get("_skip_mtp_loss"):  # the second: a planted fault
        return total, count
    z = mtp_hidden(p, cfg, h, jnp.maximum(labels, 0))
    after = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)), constant_values=IGNORE)
    after = jnp.where(labels == IGNORE, IGNORE, after)
    mtp_total, mtp_count = _chunked_nll(z, p["mtp.out_norm.g"], p["head.w"], after, eps)
    weight = cfg.get("mtp_loss_weight", 0.3)
    return total + weight * mtp_total * count / jnp.maximum(mtp_count, 1), count


def train_aux(cfg: dict, trainer_seed: int, step: int, batch: dict):
    """The step draws nothing at random."""
    return None

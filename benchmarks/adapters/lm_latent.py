"""GLM-4.7-Flash's decoder (``glm4_moe_lite``: latent attention, routed and
shared experts, an untied head, a multi-token-prediction module) through the
program's decoder-only family (``perceiver_io_tpu.scripts.text.lm``). The
configuration file keeps the names of the published ``config.json``; this
maps them onto the program's settings and lays the reference's weights out
as its tree.

Two departures in the layout, both a reordering of columns that leaves every
score as it was (a score is a dot product over a head's channels, so their
order is free where q and k share it). The program's ``LatentAttention`` lays
a head's query channels out rotary first, ``[rotary | no-position]``, where
the published form is ``[no-position | rotary]``; and its ``RotaryEmbedding``
rotates adjacent channels ``(2i, 2i + 1)`` where the reference pairs channel
``i`` with ``i + qk_rope_head_dim / 2``. So the columns of ``q_b`` are
reordered within each head, and the rotary columns of ``kv_a`` (the one
shared key head) likewise (``_columns``). ``kv_b`` and every other leaf lie
as published. ``common`` is this module's own: the shared functions with
that reordering around them.
"""
from __future__ import annotations

import types

import jax
import numpy as np

from . import common as _shared
from .lm import _pair_order

_LAYER_LEAVES = {
    "op_norm.g": ("operator_norm", "scale"), "ffn_norm.g": ("ffn_norm", "scale"),
    "moe.router.w": ("moe", "router"), "moe.bias": ("moe", "expert_bias"),
    "moe.gate": ("moe", "gate"), "moe.up": ("moe", "up"), "moe.down": ("moe", "down"),
}
_MODULES = {"attn": "attention", "mlp": "mlp", "shared": "shared_expert"}


def _below_layer(rest: str) -> tuple:
    if rest in _LAYER_LEAVES:
        return _LAYER_LEAVES[rest]
    module, leaf, kind = rest.split(".")  # attn.q_a.w, attn.q_a_norm.g, mlp.gate.w, shared.up.w
    if kind == "g":
        return (_MODULES[module], leaf, "scale")
    return (_MODULES[module], leaf + "_proj" if module == "attn" else leaf, "kernel")


def path_of(name: str) -> tuple:
    top = {"emb.tok": ("embed", "embedding"), "head.w": ("head", "kernel"),
           "out_norm.g": ("out_norm", "scale"), "mtp.eh.w": ("mtp", "eh_proj", "kernel")}
    if name in top:
        return top[name]
    scope, rest = name.split(".", 1)
    if scope == "layer":
        idx, rest = rest.split(".", 1)
        return (f"layers_{idx}",) + _below_layer(rest)
    if rest.startswith("layer."):
        return ("mtp", "layer") + _below_layer(rest[len("layer."):])
    return ("mtp", rest[:-len(".g")], "scale")  # embed_norm, hidden_norm, out_norm


def _columns(name: str, width: int, heads: int, rope: int) -> np.ndarray | None:
    """For a leaf whose ``width`` columns the program orders otherwise, the
    reference's column that each program column holds; ``None`` for every
    other leaf. ``rope`` is ``qk_rope_head_dim``."""
    pairs = _pair_order(rope)
    if name.endswith("attn.kv_a.w"):  # [latent | the shared rotary key]
        latent = width - rope
        return np.concatenate([np.arange(latent), latent + pairs])
    if name.endswith("attn.q_b.w"):  # by head [no-position | rotary] -> [rotary | no-position]
        head = width // heads
        within = np.concatenate([head - rope + pairs, np.arange(head - rope)])
        return (np.arange(0, width, head)[:, None] + within[None, :]).reshape(-1)
    return None


def _reorder(name: str, value, heads: int, rope: int, inverse: bool):
    cols = _columns(name, value.shape[-1], heads, rope)
    if cols is None:
        return value
    return value[..., np.argsort(cols) if inverse else cols]


def _seeded_tree(ref, config: dict, path_of_, seed: int):
    heads, rope = config["num_attention_heads"], config["qk_rope_head_dim"]

    def make(key):
        flat = ref.init_params(key, config)
        flat = {n: _reorder(n, v, heads, rope, inverse=False) for n, v in flat.items()}
        return _shared.to_tree(flat, path_of_)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2**31)))


def _leaves_by_name(tree, names, path_of_) -> dict:
    """The tree's leaves under the reference's names, the reordered columns
    put back. The widths are read off the leaves: ``kv_a`` is ``kv_lora_rank +
    rope`` columns and its norm's gain ``kv_lora_rank``; ``q_b``, ``kv_b`` and
    ``o`` are ``h (nope + rope)``, ``h (nope + v)`` and ``h v`` wide."""
    found = _shared.leaves_by_name(tree, names, path_of_)
    at = next((n[:-len("kv_a.w")] for n in found if n.endswith("attn.kv_a.w")), None)
    if at is None:
        return found
    rope = found[at + "kv_a.w"].shape[-1] - found[at + "kv_a_norm.g"].shape[-1]
    heads = (found[at + "q_b.w"].shape[-1] - found[at + "kv_b.w"].shape[-1]
             + found[at + "o.w"].shape[0]) // rope
    return {n: _reorder(n, v, heads, rope, inverse=True) for n, v in found.items()}


common = types.SimpleNamespace(
    seeded_tree=_seeded_tree, leaves_by_name=_leaves_by_name,
    registry_counter=_shared.registry_counter,
)


def model_config(config: dict, model: dict | None = None):
    """The family's config for the layers, experts and vocabulary rows the
    file holds; ``model`` are the mix's own model settings (recomputation by
    layer)."""
    from perceiver_io_tpu.scripts.cli import build_dataclass
    from perceiver_io_tpu.scripts.text.lm import FAMILY

    c = config
    first = c.get("first_layer", 0)
    settings = {
        "vocab_size": c["vocab_size"], "max_seq_len": c["max_position_embeddings"],
        "num_channels": c["hidden_size"], "num_heads": c["num_attention_heads"],
        "layer_types": ("latent_attention",) * c["num_layers"],
        "num_dense_layers": max(0, min(c["num_layers"], c["first_k_dense_replace"] - first)),
        "mlp_channels": c["intermediate_size"], "expert_channels": c["moe_intermediate_size"],
        "router_width": c["router_width"], "num_experts": c["n_routed_experts"],
        "expert_offset": c.get("expert_offset", 0), "experts_per_token": c["num_experts_per_tok"],
        "num_shared_experts": c["n_shared_experts"], "use_expert_bias": True,
        "norm_topk_prob": c["norm_topk_prob"],
        "routed_scaling_factor": float(c["routed_scaling_factor"]),
        "norm_eps": c["rms_norm_eps"], "rope_theta": float(c["rope_theta"]),
        "q_lora_rank": c["q_lora_rank"], "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"], "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"], "tie_word_embeddings": c["tie_word_embeddings"],
        "num_nextn_predict_layers": c["num_nextn_predict_layers"],
        "mtp_loss_weight": c.get("mtp_loss_weight", 0.3),
        "init_scale": c.get("init_scale", 0.02), **(model or {}),
    }
    values = {**FAMILY.defaults, **{f"model.{k}": v for k, v in settings.items()}}
    return build_dataclass(FAMILY.config_class, values, "model", FAMILY.nested)


def build_fit(config: dict, fit: dict, root_dir: str):
    """``(trainer, optimizer)``: the fit loop as ``lm fit`` builds it, with
    the mix's own ``--model.*``, ``--optimizer.*`` and ``--lr_scheduler.*``
    settings beside its ``--trainer.*`` ones (``adapters/lm.py``)."""
    import dataclasses

    from perceiver_io_tpu.scripts.text.lm import FAMILY

    flags = {f"{group}.{k}": v for group in ("optimizer", "lr_scheduler")
             for k, v in fit.get(group, {}).items()}
    family = dataclasses.replace(FAMILY, defaults={**FAMILY.defaults, **flags})
    return _shared.build_trainer(family, model_config(config, fit.get("model")), fit, root_dir)

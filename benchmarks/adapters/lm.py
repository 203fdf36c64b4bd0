"""LFM2's mixture-of-experts decoder through the program's decoder-only
family (``perceiver_io_tpu.scripts.text.lm``). The configuration file keeps
the names of the published ``config.json``; this maps them onto the
program's settings and lays the reference's weights out as its tree.

One departure in the layout: the program's ``RotaryEmbedding`` rotates
adjacent channels ``(2i, 2i + 1)``, the published form channel ``i`` with
``i + head / 2``. Both are the same rotation of the same pairs once the
columns of the q and k projections, and the q/k norm gains, are reordered
within each head (``_to_program``): scores are dot products over a head's
channels, so the order of the channels is free. ``common`` is this module's
own: the shared functions with that reordering around them.
"""
from __future__ import annotations

import types

import jax
import numpy as np

from . import common as _shared


def path_of(name: str) -> tuple:
    if name == "emb.tok":
        return ("embed", "embedding")
    if name == "out_norm.g":
        return ("out_norm", "scale")
    _, idx, rest = name.split(".", 2)
    layer = (f"layers_{idx}",)
    parts = rest.split(".")
    if parts[0] in ("op_norm", "ffn_norm"):
        return layer + ({"op_norm": "operator_norm", "ffn_norm": "ffn_norm"}[parts[0]], "scale")
    if parts[0] == "conv":
        if parts[1] == "filter":
            return layer + ("conv", "filter")
        return layer + ("conv", parts[1] + "_proj", "kernel")
    if parts[0] == "attn":
        if parts[1].endswith("_norm"):
            return layer + ("attention", parts[1], "scale")
        return layer + ("attention", parts[1] + "_proj", "kernel")
    if parts[0] == "mlp":
        return layer + ("mlp", parts[1], "kernel")
    leaf = {"router": "router", "bias": "expert_bias"}.get(parts[1], parts[1])
    return layer + ("moe", leaf)


def _pair_order(head: int) -> np.ndarray:
    """Program channel ``t`` of a head holds the reference's channel
    ``order[t]``: ``(2i, 2i + 1) <- (i, i + head / 2)``."""
    order = np.empty(head, dtype=np.int64)
    order[0::2], order[1::2] = np.arange(head // 2), np.arange(head // 2) + head // 2
    return order


def _reorder(name: str, value, head: int, inverse: bool):
    """A q or k leaf with its heads' channels reordered; others as they are."""
    parts = name.split(".")
    if len(parts) < 4 or parts[2] != "attn" or parts[3] not in ("q", "k", "q_norm", "k_norm"):
        return value
    order = _pair_order(head)
    order = np.argsort(order) if inverse else order
    width = value.shape[-1]
    cols = (np.arange(0, width, head)[:, None] + order[None, :]).reshape(-1)
    return value[..., cols]


def _head(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def _seeded_tree(ref, config: dict, path_of_, seed: int):
    head = _head(config)

    def make(key):
        flat = ref.init_params(key, config)
        flat = {n: _reorder(n, v, head, inverse=False) for n, v in flat.items()}
        return _shared.to_tree(flat, path_of_)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2**31)))


def _leaves_by_name(tree, names, path_of_) -> dict:
    found = _shared.leaves_by_name(tree, names, path_of_)
    heads = {v.shape[-1] for n, v in found.items() if n.endswith("_norm.g") and ".attn." in n}
    if not heads:
        return found
    (head,) = heads
    return {n: _reorder(n, v, head, inverse=True) for n, v in found.items()}


common = types.SimpleNamespace(
    seeded_tree=_seeded_tree, leaves_by_name=_leaves_by_name,
    registry_counter=_shared.registry_counter,
)


def model_config(config: dict, model: dict | None = None):
    """The family's config for the layers and experts the file holds;
    ``model`` are the mix's own model settings (recomputation by layer)."""
    from perceiver_io_tpu.scripts.cli import build_dataclass
    from perceiver_io_tpu.scripts.text.lm import FAMILY

    c = config
    first = c.get("first_layer", 0)
    settings = {
        "vocab_size": c["vocab_size"], "max_seq_len": c["max_position_embeddings"],
        "num_channels": c["hidden_size"], "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"],
        "layer_types": tuple(c["layer_types"][first:first + c["num_layers"]]),
        "num_dense_layers": c["num_dense_layers"], "mlp_channels": c["intermediate_size"],
        "expert_channels": c["moe_intermediate_size"], "router_width": c["router_width"],
        "num_experts": c["num_experts"], "expert_offset": c.get("expert_offset", 0),
        "experts_per_token": c["num_experts_per_tok"], "use_expert_bias": c["use_expert_bias"],
        "norm_topk_prob": c["norm_topk_prob"],
        "routed_scaling_factor": float(c["routed_scaling_factor"]),
        "conv_kernel_size": c["conv_L_cache"], "norm_eps": c["norm_eps"],
        "rope_theta": float(c["rope_parameters"]["rope_theta"]),
        "init_scale": c.get("init_scale", 0.02), **(model or {}),
    }
    values = {**FAMILY.defaults, **{f"model.{k}": v for k, v in settings.items()}}
    return build_dataclass(FAMILY.config_class, values, "model", FAMILY.nested)


def build_fit(config: dict, fit: dict, root_dir: str):
    """``(trainer, optimizer)``: the fit loop as ``lm fit`` builds it, with
    the mix's own ``--model.*``, ``--optimizer.*`` and ``--lr_scheduler.*``
    settings (``fit.model``, ``fit.optimizer``, ``fit.lr_scheduler``) beside
    its ``--trainer.*`` ones."""
    import dataclasses

    from perceiver_io_tpu.scripts.text.lm import FAMILY

    flags = {f"{group}.{k}": v for group in ("optimizer", "lr_scheduler")
             for k, v in fit.get(group, {}).items()}
    family = dataclasses.replace(FAMILY, defaults={**FAMILY.defaults, **flags})
    return _shared.build_trainer(family, model_config(config, fit.get("model")), fit, root_dir)

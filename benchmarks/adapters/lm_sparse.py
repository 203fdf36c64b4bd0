"""Keye-VL-2.0's language model (learned sparse attention: grouped-query
attention over the keys a lightning indexer selects, with the indexer's own
loss; sparse experts; an untied head) through the program's decoder-only
family (``perceiver_io_tpu.scripts.text.lm``). The configuration file keeps
the names of the published ``config.json``; this maps them onto the
program's settings and lays the reference's weights out as its tree.

One departure in the layout, ``adapters/lm.py``'s: the program's
``RotaryEmbedding`` rotates adjacent channels ``(2i, 2i + 1)``, the published
form channel ``i`` with ``i + head / 2``. Both are the same rotation of the
same pairs once the columns of the q and k projections, and of the norms on
them, are reordered within each head: the attention's heads of ``head_dim``
(q, k and the q/k norms' gains) and the indexer's of ``indexer_head_dim``
(its q, its one k and the layer norm's gain and bias). A score is a dot
product over a head's channels and a layer norm's statistics are over all of
them, so no score changes. ``common`` is this module's own: the shared
functions with that reordering around them.
"""
from __future__ import annotations

import types

import jax
import numpy as np

from . import common as _shared
from .lm import _pair_order

_LAYER_LEAVES = {
    "op_norm.g": ("operator_norm", "scale"), "ffn_norm.g": ("ffn_norm", "scale"),
    "attn.q_norm.g": ("attention", "q_norm", "scale"), "attn.k_norm.g": ("attention", "k_norm", "scale"),
    "idx.q.w": ("indexer", "wq", "kernel"), "idx.k.w": ("indexer", "wk", "kernel"),
    "idx.k_norm.g": ("indexer", "k_norm", "scale"), "idx.k_norm.b": ("indexer", "k_norm", "bias"),
    "idx.w.w": ("indexer", "weights_proj", "kernel"),
    "moe.router.w": ("moe", "router"),
    "moe.gate": ("moe", "gate"), "moe.up": ("moe", "up"), "moe.down": ("moe", "down"),
}
#: the leaves whose columns run by rotated head, and the head's width key
_ROTATED = {"attn.q.w": "head_dim", "attn.k.w": "head_dim", "attn.q_norm.g": "head_dim",
            "attn.k_norm.g": "head_dim", "idx.q.w": "indexer_head_dim", "idx.k.w": "indexer_head_dim",
            "idx.k_norm.g": "indexer_head_dim", "idx.k_norm.b": "indexer_head_dim"}


def path_of(name: str) -> tuple:
    top = {"emb.tok": ("embed", "embedding"), "head.w": ("head", "kernel"),
           "out_norm.g": ("out_norm", "scale")}
    if name in top:
        return top[name]
    _, idx, rest = name.split(".", 2)
    if rest in _LAYER_LEAVES:
        return (f"layers_{idx}",) + _LAYER_LEAVES[rest]
    _, leaf, _ = rest.split(".")  # attn.q.w
    return (f"layers_{idx}", "attention", leaf + "_proj", "kernel")


def _width(config: dict, key: str) -> int:
    return config["head_dim"] if key == "head_dim" else config["sa_config"][key]


def _reorder(name: str, value, config: dict, inverse: bool):
    """A rotated leaf with its heads' channels in the other pairing's order;
    others as they are."""
    parts = name.split(".", 2)
    key = _ROTATED.get(parts[2]) if len(parts) == 3 else None
    if key is None:
        return value
    head = _width(config, key)
    order = _pair_order(head)
    order = np.argsort(order) if inverse else order
    cols = (np.arange(0, value.shape[-1], head)[:, None] + order[None, :]).reshape(-1)
    return value[..., cols]


def _seeded_tree(ref, config: dict, path_of_, seed: int):
    def make(key):
        flat = ref.init_params(key, config)
        return _shared.to_tree({n: _reorder(n, v, config, inverse=False) for n, v in flat.items()}, path_of_)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2**31)))


def reference_order(leaves: dict, config: dict) -> dict:
    """Leaves by the reference's names with the rotated columns put back in
    the reference's order, for a comparison element by element.
    ``common.leaves_by_name`` leaves them in the program's order: the train
    driver takes each leaf's norm, which no order of columns changes."""
    return {n: _reorder(n, v, config, inverse=True) for n, v in leaves.items()}


common = types.SimpleNamespace(
    seeded_tree=_seeded_tree, leaves_by_name=_shared.leaves_by_name,
    registry_counter=_shared.registry_counter,
)


def model_config(config: dict, model: dict | None = None):
    """The family's config for the layers, experts and vocabulary rows the
    file holds; ``model`` are the mix's own model settings (recomputation by
    layer)."""
    from perceiver_io_tpu.scripts.cli import build_dataclass
    from perceiver_io_tpu.scripts.text.lm import FAMILY

    c, sa = config, config["sa_config"]
    settings = {
        "vocab_size": c["vocab_size"], "max_seq_len": c["max_position_embeddings"],
        "num_channels": c["hidden_size"], "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"], "qk_norm": True,
        "layer_types": ("sparse_attention",) * c["num_layers"],
        "rotary_layer_types": ("sparse_attention",),
        "index_n_heads": sa["indexer_num_heads"], "index_head_dim": sa["indexer_head_dim"],
        "index_topk": sa["topk"], "num_dense_layers": 0,
        "expert_channels": c["moe_intermediate_size"], "router_width": c["router_width"],
        "num_experts": c["num_experts"], "expert_offset": c.get("expert_offset", 0),
        "experts_per_token": c["num_experts_per_tok"], "use_expert_bias": False,
        "norm_topk_prob": c["norm_topk_prob"], "router_score": "softmax_topk",
        "expert_activation": "silu", "router_input": "ffn",
        "norm_eps": c["rms_norm_eps"], "rope_theta": float(c["rope_theta"]),
        "tie_word_embeddings": c["tie_word_embeddings"],
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

"""SmallThinker's decoder (window and global attention in one stack, a router
that reads the attention's input, ReGLU experts, an untied head) through the
program's decoder-only family (``perceiver_io_tpu.scripts.text.lm``). The
configuration file keeps the names of the published ``config.json``; this
maps them onto the program's settings and lays the reference's weights out
as its tree.

One departure in the layout, ``adapters/lm.py``'s: the program's
``RotaryEmbedding`` rotates adjacent channels ``(2i, 2i + 1)``, the published
form channel ``i`` with ``i + head_dim / 2``. Both are the same rotation of
the same pairs once the columns of the q and k projections are reordered
within each head (``adapters/lm.py::_pair_order``); a score is a dot product
over a head's channels, so their order is free where q and k share it, which
is why the layers that are not rotated are reordered too. ``common`` is this module's
own: the shared functions with that reordering around them.
"""
from __future__ import annotations

import types

import jax

from . import common as _shared
from .lm import _reorder  # q and k leaves with their heads' columns in the other pairing's order

_LAYER_LEAVES = {
    "op_norm.g": ("operator_norm", "scale"), "ffn_norm.g": ("ffn_norm", "scale"),
    "moe.router.w": ("moe", "router"),
    "moe.gate": ("moe", "gate"), "moe.up": ("moe", "up"), "moe.down": ("moe", "down"),
}
_KINDS = {False: "full_attention", True: "window_attention"}


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


def _seeded_tree(ref, config: dict, path_of_, seed: int):
    head = config["head_dim"]

    def make(key):
        flat = ref.init_params(key, config)
        flat = {n: _reorder(n, v, head, inverse=False) for n, v in flat.items()}
        return _shared.to_tree(flat, path_of_)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2**31)))


def reference_order(leaves: dict, config: dict) -> dict:
    """Leaves by the reference's names with the q and k columns put back in
    the reference's order, for a comparison element by element.
    ``common.leaves_by_name`` leaves them in the program's order: the tree
    does not say how its q columns divide into heads, and the train driver
    takes each leaf's norm, which no order of columns changes."""
    return {n: _reorder(n, v, config["head_dim"], inverse=True) for n, v in leaves.items()}


common = types.SimpleNamespace(
    seeded_tree=_seeded_tree, leaves_by_name=_shared.leaves_by_name,
    registry_counter=_shared.registry_counter,
)


def layer_kinds(config: dict) -> tuple:
    """``(layer_types, rotary_layer_types)`` of the layers held: a layer is
    ``window_attention`` where ``sliding_window_layout`` says so, and a kind is
    rotated if ``rope_layout`` rotates its layers, which must be all or none
    of them: the program declares rotary by kind."""
    first = config.get("first_layer", 0)
    held = range(first, first + config["num_layers"])
    kinds = tuple(_KINDS[bool(config["sliding_window_layout"][l])] for l in held)
    rotated = {}
    for l, kind in zip(held, kinds):
        if rotated.setdefault(kind, bool(config["rope_layout"][l])) != bool(config["rope_layout"][l]):
            raise ValueError(f"rope_layout rotates some {kind} layers and not others")
    return kinds, tuple(kind for kind, on in rotated.items() if on)


def model_config(config: dict, model: dict | None = None):
    """The family's config for the layers, experts and vocabulary rows the
    file holds; ``model`` are the mix's own model settings (recomputation by
    layer)."""
    from perceiver_io_tpu.scripts.cli import build_dataclass
    from perceiver_io_tpu.scripts.text.lm import FAMILY

    c = config
    kinds, rotated = layer_kinds(c)
    settings = {
        "vocab_size": c["vocab_size"], "max_seq_len": c["max_position_embeddings"],
        "num_channels": c["hidden_size"], "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"], "qk_norm": False,
        "layer_types": kinds, "rotary_layer_types": rotated,
        "sliding_window": c["sliding_window_size"], "num_dense_layers": 0,
        "expert_channels": c["moe_ffn_hidden_size"], "router_width": c["router_width"],
        "num_experts": c["moe_num_primary_experts"], "expert_offset": c.get("expert_offset", 0),
        "experts_per_token": c["moe_num_active_primary_experts"], "use_expert_bias": False,
        "norm_topk_prob": c["norm_topk_prob"],
        "router_score": "softmax_topk" if c["moe_primary_router_apply_softmax"] else "sigmoid",
        "expert_activation": "relu", "router_input": "operator",
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

"""Perceiver AR through the program's causal-language-model family
(``perceiver_io_tpu.scripts.text.clm``)."""
from __future__ import annotations

from . import common

_MODEL_KEYS = (
    "vocab_size", "max_seq_len", "max_latents", "num_channels", "num_heads",
    "num_self_attention_layers", "self_attention_widening_factor",
    "cross_attention_widening_factor", "cross_attention_dropout",
    "post_attention_dropout", "residual_dropout", "abs_pos_emb", "output_norm",
    "output_bias", "init_scale",
)


def path_of(name: str) -> tuple:
    if name == "emb.tok":
        return ("perceiver_ar", "input_adapter", "txt_embedding", "embedding")
    if name == "emb.pos":
        return ("perceiver_ar", "input_adapter", "pos_embedding", "embedding")
    if name == "head.bias":
        return ("output_adapter", "bias")
    if name.startswith("cross."):
        return ("perceiver_ar", "cross_attention") + common.layer_path(name[6:], "cross_attn")
    _, idx, rest = name.split(".", 2)
    return ("perceiver_ar", "self_attention", f"layers_{idx}") + common.layer_path(rest, "self_attn")


def model_config(config: dict):
    from perceiver_io_tpu.scripts.cli import build_dataclass
    from perceiver_io_tpu.scripts.text.clm import FAMILY

    values = {**FAMILY.defaults, **{f"model.{k}": config[k] for k in _MODEL_KEYS if k in config}}
    return build_dataclass(FAMILY.config_class, values, "model", FAMILY.nested)


def build_fit(config: dict, fit: dict, root_dir: str):
    """``(trainer, optimizer)``: the fit loop as ``clm fit`` builds it."""
    from perceiver_io_tpu.scripts.text.clm import FAMILY

    return common.build_trainer(FAMILY, model_config(config), fit, root_dir)

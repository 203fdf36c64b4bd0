"""The Perceiver IO masked language model through the program's family
(``perceiver_io_tpu.scripts.text.mlm``). The configuration file keeps the
names of deepmind/language-perceiver's ``config.json``; this maps them onto
the program's encoder and decoder settings."""
from __future__ import annotations

from . import common


def path_of(name: str) -> tuple:
    if name == "emb.tok":
        return ("encoder", "input_adapter", "txt_embedding", "embedding")
    if name == "emb.pos":
        return ("encoder", "input_adapter", "pos_embedding", "embedding")
    if name == "latents":
        return ("encoder", "latent_provider", "query")
    if name == "dec.query":
        return ("decoder", "output_query_provider", "query")
    if name == "head.bias":
        return ("decoder", "output_adapter", "bias")
    if name.startswith("cross."):
        return ("encoder", "cross_attn_1") + common.layer_path(name[6:], "cross_attn")
    if name.startswith("dec."):
        return ("decoder", "cross_attn") + common.layer_path(name[4:], "cross_attn")
    _, idx, rest = name.split(".", 2)
    return ("encoder", "self_attn_1", f"layers_{idx}") + common.layer_path(rest, "self_attn")


def model_config(config: dict):
    from perceiver_io_tpu.scripts.cli import build_dataclass
    from perceiver_io_tpu.scripts.text.mlm import FAMILY

    c = config
    enc = {
        "vocab_size": c["vocab_size"], "max_seq_len": c["max_position_embeddings"],
        "num_input_channels": c["d_model"],
        "num_cross_attention_qk_channels": c["qk_channels"],
        "num_cross_attention_v_channels": c["v_channels"],
        "num_cross_attention_heads": c["num_cross_attention_heads"],
        "num_self_attention_qk_channels": c["qk_channels"],
        "num_self_attention_v_channels": c["v_channels"],
        "num_self_attention_heads": c["num_self_attention_heads"],
        "num_self_attention_layers_per_block": c["num_self_attends_per_block"],
        "num_self_attention_blocks": c["num_blocks"],
        "cross_attention_widening_factor": c["cross_attention_widening_factor"],
        "self_attention_widening_factor": c["self_attention_widening_factor"],
        "dropout": c["attention_probs_dropout_prob"],
        "init_scale": c["initializer_range"],
    }
    dec = {
        "vocab_size": c["vocab_size"], "max_seq_len": c["max_position_embeddings"],
        "num_cross_attention_qk_channels": c["qk_channels"],
        "num_cross_attention_v_channels": c["d_model"],
        "num_cross_attention_heads": c["num_cross_attention_heads"],
        "cross_attention_widening_factor": c["cross_attention_widening_factor"],
        "cross_attention_residual": False,
        "dropout": c["attention_probs_dropout_prob"],
        "init_scale": c["initializer_range"],
    }
    values = {
        **FAMILY.defaults,
        **{f"model.encoder.{k}": v for k, v in enc.items()},
        **{f"model.decoder.{k}": v for k, v in dec.items()},
        "model.num_latents": c["num_latents"], "model.num_latent_channels": c["d_latents"],
    }
    return build_dataclass(FAMILY.config_class, values, "model", FAMILY.nested)


def build_fit(config: dict, fit: dict, root_dir: str):
    """``(trainer, optimizer)``: the fit loop as ``mlm fit`` builds it."""
    from perceiver_io_tpu.scripts.text.mlm import FAMILY

    return common.build_trainer(FAMILY, model_config(config), fit, root_dir)

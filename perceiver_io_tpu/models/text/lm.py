"""Decoder-only language model whose layers are declared one by one
(docs/lm.md): every layer is ``h = h + operator(rms(h))`` then
``h = h + ffn(rms(h))``, where the operator is a gated short convolution
(``"conv"``) or causal grouped-query attention with RMS-normed q and k and
whole-head rotary (``"full_attention"``), and the feed-forward is a gated
MLP in the first ``num_dense_layers`` layers and a layer of sparse experts
in the others. After the last layer one more RMS norm, then logits against
the embedding table.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from perceiver_io_tpu.models.core.config import register_config
from perceiver_io_tpu.models.core.hybrid import GatedMLP, ShortConv, SparseExperts
from perceiver_io_tpu.models.core.modules import MultiHeadAttention, RMSNorm, _remat_policy
from perceiver_io_tpu.models.sequence import TiedOutputAdapter
from perceiver_io_tpu.ops.position import RotaryEmbedding, frequency_position_encoding, positions

LAYER_TYPES = ("conv", "full_attention")


@register_config
@dataclass
class DecoderLMConfig:
    """``layer_types`` names each layer's operator, in order; the first
    ``num_dense_layers`` layers have the gated MLP of ``mlp_channels``, the
    others ``experts_per_token`` of ``router_width`` experts of
    ``expert_channels``. ``num_experts`` is how many of those experts this
    model holds, from ``expert_offset`` on: equal to ``router_width`` (and
    offset 0) for the whole model, fewer for one chip's share of an
    expert-parallel layer, whose output is then the held experts' part."""

    vocab_size: int = 262
    max_seq_len: int = 4096
    num_channels: int = 512
    num_heads: int = 8
    num_kv_heads: int = 2
    layer_types: Tuple[str, ...] = ("conv", "conv", "full_attention", "conv")
    num_dense_layers: int = 1
    mlp_channels: int = 2048
    expert_channels: int = 512
    router_width: int = 8
    num_experts: int = 8
    expert_offset: int = 0
    experts_per_token: int = 2
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_kernel_size: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    init_scale: float = 0.02
    activation_checkpointing: bool = False

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        unknown = set(self.layer_types) - set(LAYER_TYPES)
        if unknown:
            raise ValueError(f"layer_types has {sorted(unknown)}; known: {LAYER_TYPES}")
        if self.num_channels % self.num_heads:
            raise ValueError("num_channels must be divisible by num_heads")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def has_experts(self) -> bool:
        return self.num_dense_layers < self.num_layers


class DecoderLayer(nn.Module):
    """One layer; returns ``(h, stats)`` with the expert layer's ``[pairs
    computed, load max over mean, ran on the row bound]`` (zeros in a dense
    layer)."""

    config: DecoderLMConfig
    layer_type: str
    dense: bool
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    @nn.compact
    def __call__(self, h, pad_mask: Optional[jnp.ndarray], rot: Optional[RotaryEmbedding]):
        cfg = self.config
        h = checkpoint_name(h, "remat_layer_input")
        u = RMSNorm(cfg.norm_eps, self.dtype, name="operator_norm")(h)
        if self.layer_type == "conv":
            op = ShortConv(
                cfg.num_channels, cfg.conv_kernel_size, cfg.init_scale, self.dtype, name="conv"
            )(u, pad_mask)
        else:
            op = MultiHeadAttention(
                num_heads=cfg.num_heads, num_q_input_channels=cfg.num_channels,
                num_kv_input_channels=cfg.num_channels, causal_attention=True,
                qkv_bias=False, out_bias=False, init_scale=cfg.init_scale, dtype=self.dtype,
                attention_impl=self.attention_impl, num_kv_heads=cfg.num_kv_heads,
                qk_norm=True, norm_eps=cfg.norm_eps, name="attention",
            )(u, u, pad_mask=pad_mask, rot_pos_emb_q=rot, rot_pos_emb_k=rot)
        h = h + op
        u = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")(h)
        if self.dense:
            out = GatedMLP(
                cfg.num_channels, cfg.mlp_channels, cfg.init_scale, self.dtype, name="mlp")(u)
            stats = jnp.zeros((3,), jnp.float32)
        else:
            out, stats = SparseExperts(
                num_channels=cfg.num_channels, hidden_channels=cfg.expert_channels,
                router_width=cfg.router_width, num_experts=cfg.num_experts,
                expert_offset=cfg.expert_offset, top_k=cfg.experts_per_token,
                use_expert_bias=cfg.use_expert_bias, norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor, init_scale=cfg.init_scale,
                dtype=self.dtype, name="moe",
            )(u)
        return h + out, stats


class DecoderLM(nn.Module):
    """``(b, n)`` token ids -> ``(b, n, vocab_size)`` logits, and with
    ``return_stats`` also ``{"moe_assignments_held", "moe_expert_load_max_over_mean",
    "moe_layers_bounded"}``: token-expert pairs computed by the held experts,
    summed over the expert layers; the worst layer's fullest held expert over
    its mean; and the expert layers whose held pairs fitted the row bound."""

    config: DecoderLMConfig
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.num_channels,
            embedding_init=nn.initializers.normal(stddev=cfg.init_scale), name="embed",
        )
        layer_cls = DecoderLayer
        if cfg.activation_checkpointing:
            layer_cls = nn.remat(DecoderLayer, policy=_remat_policy(offload=False))
        self.layers = [
            layer_cls(cfg, kind, i < cfg.num_dense_layers, self.dtype, self.attention_impl,
                      name=f"layers_{i}")
            for i, kind in enumerate(cfg.layer_types)
        ]
        self.out_norm = RMSNorm(cfg.norm_eps, self.dtype, name="out_norm")
        self.output_adapter = TiedOutputAdapter(
            vocab_size=cfg.vocab_size, emb_bias=False, dtype=self.dtype, name="output_adapter")

    def __call__(self, x: jnp.ndarray, pad_mask: Optional[jnp.ndarray] = None,
                 return_stats: bool = False):
        cfg = self.config
        if x.shape[1] > cfg.max_seq_len:
            raise ValueError(f"sequence length ({x.shape[1]}) exceeds max_seq_len ({cfg.max_seq_len})")
        shift = None if pad_mask is None else pad_mask.sum(axis=1, keepdims=True)
        angles = frequency_position_encoding(
            positions(*x.shape, shift=shift), cfg.num_channels // cfg.num_heads, cfg.rope_theta)
        rot = RotaryEmbedding(angles)
        h = self.embed(x).astype(self.dtype)
        stats = []
        for layer in self.layers:
            h, s = layer(h, pad_mask, rot)
            stats.append(s)
        logits = self.output_adapter(self.out_norm(h), self.embed.embedding)
        if not return_stats:
            return logits
        stats = jnp.stack(stats)
        return logits, {
            "moe_assignments_held": stats[:, 0].sum(),
            "moe_expert_load_max_over_mean": stats[:, 1].max(),
            "moe_layers_bounded": stats[:, 2].sum(),
        }

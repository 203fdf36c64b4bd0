"""Decoder-only language model whose layers are declared one by one
(docs/lm.md): every layer is ``h = h + operator(rms(h))`` then
``h = h + ffn(rms(h))``, where the operator is a gated short convolution
(``"conv"``), causal grouped-query attention over every earlier position
(``"full_attention"``), over the ``sliding_window`` latest
(``"window_attention"``) or over the ``index_topk`` earlier positions a
lightning indexer selects for each query (``"sparse_attention"``, whose
indexer has a loss of its own), each with RMS-normed q and k unless
``qk_norm`` is off and with whole-head rotary if its kind is among
``rotary_layer_types``, or causal attention out of low-rank latents with one
shared rotary key head (``"latent_attention"``), and the feed-forward is a gated MLP in the first
``num_dense_layers`` layers and a layer of sparse experts, with
``num_shared_experts`` experts beside them that every token takes, in the
others (their router scores by a sigmoid or by a softmax over the chosen
logits and reads the layer's own normed input or the operator's; an expert
gates with ``silu`` or ``relu``). After the last layer one more RMS norm,
then logits against the embedding table or, untied, a head of its own. With
``num_nextn_predict_layers`` one more expert layer predicts the token after
the next from the last hidden state and the next token's embedding.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from perceiver_io_tpu.models.core.config import register_config
from perceiver_io_tpu.models.core.hybrid import GatedMLP, ShortConv, SparseExperts
from perceiver_io_tpu.models.core.modules import (
    Indexer,
    LatentAttention,
    MultiHeadAttention,
    RMSNorm,
    _remat_policy,
)
from perceiver_io_tpu.models.sequence import TiedOutputAdapter
from perceiver_io_tpu.ops import sparse_attention
from perceiver_io_tpu.ops.attention import selected_attention
from perceiver_io_tpu.ops.position import RotaryEmbedding, frequency_position_encoding, positions

LAYER_TYPES = ("conv", "full_attention", "window_attention", "latent_attention", "sparse_attention")
#: the operator kinds that are grouped-query attention over plain heads
_HEAD_ATTENTION = ("full_attention", "window_attention", "sparse_attention")
#: the named scope around each kind's attention (docs/observability.md)
ATTENTION_SCOPES = {"full_attention": "global_attention", "window_attention": "window_attention",
                    "sparse_attention": "sparse_attention"}
#: the named scope around a sparse layer's indexer: projections, selection, loss
INDEXER_SCOPE = "indexer"


@register_config
@dataclass
class DecoderLMConfig:
    """``layer_types`` names each layer's operator, in order; the first
    ``num_dense_layers`` layers have the gated MLP of ``mlp_channels``, the
    others ``experts_per_token`` of ``router_width`` experts of
    ``expert_channels``. ``num_experts`` is how many of those experts this
    model holds, from ``expert_offset`` on: equal to ``router_width`` (and
    offset 0) for the whole model, fewer for one chip's share of an
    expert-parallel layer, whose output is then the held experts' part.
    ``num_shared_experts`` more experts of the same width stand beside them
    as one gated MLP that takes every token, unweighted, and that every share
    holds whole. ``latent_attention`` layers take their widths from
    ``q_lora_rank``, ``kv_lora_rank`` and the three head widths, not from
    ``num_channels / num_heads``. ``tie_word_embeddings`` off gives the model
    a ``(num_channels, vocab_size)`` head of its own.
    ``num_nextn_predict_layers`` (0 or 1) adds the multi-token-prediction
    module, whose loss ``lm_loss_fn`` adds under ``mtp_loss_weight``.

    ``full_attention``, ``window_attention`` and ``sparse_attention`` layers
    have ``num_heads`` query heads on ``num_kv_heads`` key-value heads of
    ``head_dim`` channels (0: ``num_channels / num_heads``), RMS-normed q and
    k if ``qk_norm``; a ``window_attention`` layer sees the ``sliding_window``
    latest positions, its own among them; in a ``sparse_attention`` layer
    query ``t`` sees the ``min(t + 1, index_topk)`` earlier positions of
    largest score by an indexer of ``index_n_heads`` heads of
    ``index_head_dim`` channels (every earlier position where a row has no
    more than ``index_topk``), and the layer's indexer loss is added to the
    model's (``lm_loss_fn``). ``rotary_layer_types`` names the operator kinds whose
    q and k are rotated: a kind left out gets no position signal and no
    rotary table is built for it. Experts: ``router_score`` is ``sigmoid``
    (the chosen experts' sigmoid scores, normalised if ``norm_topk_prob``) or
    ``softmax_topk`` (top-k on the logits, a softmax over the chosen);
    ``expert_activation`` is ``silu`` or ``relu``; ``router_input`` says
    what the router reads: ``ffn``, the expert layer's own normed input, or
    ``operator``, the normed input of the layer's operator (a router that
    stands before the attention)."""

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
    num_shared_experts: int = 0
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_kernel_size: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    init_scale: float = 0.02
    activation_checkpointing: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    tie_word_embeddings: bool = True
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    head_dim: int = 0
    qk_norm: bool = True
    sliding_window: int = 0
    rotary_layer_types: Tuple[str, ...] = ("full_attention", "window_attention", "latent_attention", "sparse_attention")
    router_score: str = "sigmoid"
    expert_activation: str = "silu"
    router_input: str = "ffn"
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.rotary_layer_types = tuple(self.rotary_layer_types)
        unknown = (set(self.layer_types) | set(self.rotary_layer_types)) - set(LAYER_TYPES)
        if unknown:
            raise ValueError(f"layer_types has {sorted(unknown)}; known: {LAYER_TYPES}")
        if set(_HEAD_ATTENTION) & set(self.layer_types):
            if not self.head_dim and self.num_channels % self.num_heads:
                raise ValueError("num_channels must be divisible by num_heads (or set head_dim)")
            if self.attention_head_dim % 2:
                raise ValueError("a rotated head has an even number of channels")
        if "window_attention" in self.layer_types and self.sliding_window < 1:
            raise ValueError("window_attention layers need sliding_window >= 1")
        if "sparse_attention" in self.layer_types:
            if min(self.index_n_heads, self.index_head_dim, self.index_topk) < 1 or self.index_head_dim % 2:
                raise ValueError("sparse_attention layers need index_n_heads, index_head_dim (even) "
                                 "and index_topk positive")
            if self.num_nextn_predict_layers:
                raise ValueError("the prediction module's layer cannot be a sparse_attention layer")
        if self.router_input not in ("ffn", "operator"):
            raise ValueError(f"router_input is 'ffn' or 'operator', not {self.router_input!r}")
        if "latent_attention" in self.layer_types:
            widths = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
            missing = [w for w in widths if getattr(self, w) <= 0]
            if missing or self.qk_rope_head_dim % 2:
                raise ValueError(
                    f"latent_attention needs {widths} positive and qk_rope_head_dim even; "
                    f"not set: {missing}")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers is 0 or 1: one prediction module is implemented")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def attention_head_dim(self) -> int:
        """Channels of a ``full_attention`` / ``window_attention`` head."""
        return self.head_dim or self.num_channels // self.num_heads

    @property
    def has_indexer(self) -> bool:
        return "sparse_attention" in self.layer_types

    @property
    def has_experts(self) -> bool:
        return self.num_dense_layers < self.num_layers or self.num_nextn_predict_layers > 0


class DecoderLayer(nn.Module):
    """One layer; returns ``(h, stats)`` with the expert layer's ``[pairs
    computed, load max over mean, ran on the row bound]`` (zeros in a dense
    layer) and, in a model with an indexer, the layer's indexer loss after
    them (0 in a layer without one)."""

    config: DecoderLMConfig
    layer_type: str
    dense: bool
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    @nn.compact
    def __call__(self, h, pad_mask: Optional[jnp.ndarray], rot: Optional[RotaryEmbedding],
                 index_rot: Optional[RotaryEmbedding] = None):
        cfg = self.config
        h = checkpoint_name(h, "remat_layer_input")
        u = RMSNorm(cfg.norm_eps, self.dtype, name="operator_norm")(h)
        index_loss = jnp.zeros((), jnp.float32)
        if self.layer_type == "sparse_attention":
            op, index_loss = self._sparse_attention(u, pad_mask, rot, index_rot)
        elif self.layer_type == "conv":
            op = ShortConv(
                cfg.num_channels, cfg.conv_kernel_size, cfg.init_scale, self.dtype, name="conv"
            )(u, pad_mask)
        elif self.layer_type == "latent_attention":
            op = LatentAttention(
                num_heads=cfg.num_heads, num_input_channels=cfg.num_channels,
                q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim, norm_eps=cfg.norm_eps, init_scale=cfg.init_scale,
                dtype=self.dtype, attention_impl=self.attention_impl, name="attention",
            )(u, pad_mask, rot)
        else:
            window = cfg.sliding_window if self.layer_type == "window_attention" else None
            with jax.named_scope(ATTENTION_SCOPES[self.layer_type]):
                op = self._head_attention(window)(
                    u, u, pad_mask=pad_mask, rot_pos_emb_q=rot, rot_pos_emb_k=rot)
        h = h + op
        seen = u if cfg.router_input == "operator" else None  # what the router reads, if not its own
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
                dtype=self.dtype, router_score=cfg.router_score,
                activation=cfg.expert_activation, name="moe",
            )(u, seen)
            if cfg.num_shared_experts:
                out = out + GatedMLP(
                    cfg.num_channels, cfg.num_shared_experts * cfg.expert_channels, cfg.init_scale,
                    self.dtype, name="shared_expert")(u)
        if cfg.has_indexer:
            stats = jnp.concatenate([stats, index_loss[None]])
        return h + out, stats

    def _head_attention(self, window=None) -> MultiHeadAttention:
        """The grouped-query attention module of the plain-head kinds."""
        cfg = self.config
        return MultiHeadAttention(
            num_heads=cfg.num_heads, num_q_input_channels=cfg.num_channels,
            num_kv_input_channels=cfg.num_channels,
            # the published head width, whatever num_channels / num_heads is;
            # left unset where the two agree, as the module always was built
            num_qk_channels=cfg.num_heads * cfg.head_dim if cfg.head_dim else None,
            causal_attention=True, qkv_bias=False, out_bias=False,
            init_scale=cfg.init_scale, dtype=self.dtype,
            attention_impl=self.attention_impl, num_kv_heads=cfg.num_kv_heads,
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps, window=window, name="attention",
        )

    def _sparse_attention(self, u, pad_mask, rot, index_rot):
        """The attention of a ``sparse_attention`` layer and its indexer's
        loss. The indexer reads ``u`` detached and selects each query's keys
        (exactly, ``ops/sparse_attention.py``) where a row has more positions
        than ``index_topk``; the attention is the grouped-query attention of
        the other kinds over those keys alone; the loss holds the indexer's
        scores to the heads' mean of the attention's probabilities over them,
        detached. So the LM loss trains all but the indexer, the indexer loss
        the indexer alone."""
        cfg = self.config
        with jax.named_scope(INDEXER_SCOPE):
            q_i, k_i, w = Indexer(
                cfg.index_n_heads, cfg.index_head_dim, cfg.norm_eps, cfg.init_scale, self.dtype,
                name="indexer")(jax.lax.stop_gradient(u), index_rot)
            bits = None
            if u.shape[1] > cfg.index_topk:
                bits = checkpoint_name(
                    sparse_attention.select(q_i, k_i, w, cfg.index_topk), sparse_attention.SELECTION_NAME)
        with jax.named_scope(ATTENTION_SCOPES["sparse_attention"]):
            attention = self._head_attention()
            q = attention.project_q(u, rot)
            k, v = attention.project_kv(u, rot)
            if bits is None:  # every causal key is selected: the causal path
                op, lse = attention.attend(q, k, v, pad_mask=pad_mask), None
            else:
                o, lse = selected_attention(q, k, v, bits, pad_mask=pad_mask, impl=self.attention_impl)
                op = attention.project_out(o)
        with jax.named_scope(INDEXER_SCOPE):
            loss = sparse_attention.indexer_loss(q, k, lse, q_i, k_i, w, bits)
        return op, loss


def _layer_class(cfg: DecoderLMConfig):
    """``DecoderLayer``, recomputed in the backward pass if the config says so."""
    if cfg.activation_checkpointing:
        return nn.remat(DecoderLayer, policy=_remat_policy(offload=False))
    return DecoderLayer


class NextTokenModule(nn.Module):
    """The multi-token-prediction module (the DeepSeek-V3 form): at position
    ``i`` the last layer's hidden state ``h_i`` and the next token's embedding
    go, each through an RMS norm of its own, side by side through ``eh_proj``
    (``2 c -> c``), then through one whole expert layer of the last layer's
    operator kind, then one more RMS norm. Returns that and the layer's
    stats; the model's own embedding and head stand before and after it."""

    config: DecoderLMConfig
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    @nn.compact
    def __call__(self, h, next_emb, pad_mask: Optional[jnp.ndarray], rot: Optional[RotaryEmbedding]):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.norm_eps, self.dtype, name=name)
        z = jnp.concatenate([norm("embed_norm")(next_emb), norm("hidden_norm")(h)], axis=-1)
        z = nn.Dense(
            cfg.num_channels, use_bias=False, dtype=self.dtype, name="eh_proj",
            kernel_init=nn.initializers.normal(stddev=cfg.init_scale),
        )(z)
        z, stats = _layer_class(cfg)(
            cfg, cfg.layer_types[-1], False, self.dtype, self.attention_impl, name="layer",
        )(z, pad_mask, rot)
        return norm("out_norm")(z), stats


class DecoderLM(nn.Module):
    """``(b, n)`` token ids -> ``(b, n, vocab_size)`` logits, and with
    ``return_stats`` also ``{"moe_assignments_held", "moe_expert_load_max_over_mean",
    "moe_layers_bounded"}``: token-expert pairs computed by the held experts,
    summed over the expert layers; the worst layer's fullest held expert over
    its mean; and the expert layers whose held pairs fitted the row bound. A
    model with ``sparse_attention`` layers adds ``"indexer_loss"``, their
    indexers' losses summed. With ``next_ids`` ``(b, n)``, the token after each position, a model with
    the prediction module returns ``(logits, mtp_logits)`` in the logits'
    place: ``mtp_logits[:, i]`` predicts the token after ``next_ids[:, i]``,
    and the module's expert layer counts in the stats."""

    config: DecoderLMConfig
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.num_channels,
            embedding_init=nn.initializers.normal(stddev=cfg.init_scale), name="embed",
        )
        layer_cls = _layer_class(cfg)
        self.layers = [
            layer_cls(cfg, kind, i < cfg.num_dense_layers, self.dtype, self.attention_impl,
                      name=f"layers_{i}")
            for i, kind in enumerate(cfg.layer_types)
        ]
        self.out_norm = RMSNorm(cfg.norm_eps, self.dtype, name="out_norm")
        if cfg.tie_word_embeddings:
            self.output_adapter = TiedOutputAdapter(
                vocab_size=cfg.vocab_size, emb_bias=False, dtype=self.dtype, name="output_adapter")
        else:
            self.head = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=self.dtype, name="head",
                kernel_init=nn.initializers.normal(stddev=cfg.init_scale),
            )
        if cfg.num_nextn_predict_layers:
            self.mtp = NextTokenModule(cfg, self.dtype, self.attention_impl, name="mtp")

    def _logits(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.config.tie_word_embeddings:
            return self.output_adapter(x, self.embed.embedding)
        return self.head(x)

    def __call__(self, x: jnp.ndarray, pad_mask: Optional[jnp.ndarray] = None,
                 return_stats: bool = False, next_ids: Optional[jnp.ndarray] = None):
        cfg = self.config
        if x.shape[1] > cfg.max_seq_len:
            raise ValueError(f"sequence length ({x.shape[1]}) exceeds max_seq_len ({cfg.max_seq_len})")
        if next_ids is not None and not cfg.num_nextn_predict_layers:
            raise ValueError("next_ids are the prediction module's: num_nextn_predict_layers is 0")
        shift = None if pad_mask is None else pad_mask.sum(axis=1, keepdims=True)
        pos = positions(*x.shape, shift=shift)
        # rotary tables by operator kind: over a whole head, or over a latent
        # head's rotary channels; one table a width, none for a kind whose
        # layers are not rotated
        widths = {"latent_attention": cfg.qk_rope_head_dim}
        if set(_HEAD_ATTENTION) & set(cfg.layer_types):
            widths.update(dict.fromkeys(_HEAD_ATTENTION, cfg.attention_head_dim))
        tables, rots = {}, {}

        def table(width):
            if width not in tables:
                tables[width] = RotaryEmbedding(frequency_position_encoding(pos, width, cfg.rope_theta))
            return tables[width]

        for kind, width in widths.items():
            if kind in cfg.layer_types and kind in cfg.rotary_layer_types:
                rots[kind] = table(width)
        # an indexer's heads are rotated with its layer's kind
        index_rot = table(cfg.index_head_dim) if "sparse_attention" in rots else None
        h = self.embed(x).astype(self.dtype)
        stats = []
        for layer, kind in zip(self.layers, cfg.layer_types):
            extra = (index_rot,) if kind == "sparse_attention" else ()
            h, s = layer(h, pad_mask, rots.get(kind), *extra)
            stats.append(s)
        logits = self._logits(self.out_norm(h))
        if next_ids is not None:
            with jax.named_scope("mtp"):
                next_emb = self.embed(next_ids).astype(self.dtype)
            z, s = self.mtp(h, next_emb, pad_mask, rots.get(cfg.layer_types[-1]))
            stats.append(s)
            with jax.named_scope("mtp"):
                logits = logits, self._logits(z)
        if not return_stats:
            return logits
        stats = jnp.stack(stats)
        out = {
            "moe_assignments_held": stats[:, 0].sum(),
            "moe_expert_load_max_over_mean": stats[:, 1].max(),
            "moe_layers_bounded": stats[:, 2].sum(),
        }
        if cfg.has_indexer:
            out["indexer_loss"] = stats[:, 3].sum()
        return logits, out

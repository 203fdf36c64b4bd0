"""Core Perceiver runtime — attention modules, Perceiver IO encoder/decoder,
and Perceiver AR — as flax linen modules.

Capability parity with reference ``perceiver/model/core/modules.py``; built
TPU-first:

- all control flow is static (python loops over static layer counts unroll at
  trace time; weight sharing is module reuse, which XLA sees as the same
  parameters applied at several depths);
- attention math lives in :func:`perceiver_io_tpu.ops.attention.dot_product_attention`
  (fp32 softmax, Pallas flash dispatch);
- activation checkpointing maps to ``flax.linen.remat`` over attention layers
  (the fairscale ``checkpoint_wrapper`` equivalent, reference
  ``modules.py:347-348,452-454``), which keeps each flash call's output and
  log-sum-exp and recomputes all else (:func:`_remat_policy`);
- dtype policy: parameters are fp32; ``dtype`` selects the computation dtype
  (bf16 on TPU keeps the MXU fed at full rate).

Dropout rngs: ``'dropout'`` for attention/residual dropout, ``'prefix'`` for
Perceiver AR cross-attention (prefix) dropout. Pass ``deterministic=True``
for inference.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from perceiver_io_tpu.models.core.adapter import TrainableQueryProvider
from perceiver_io_tpu.ops.attention import dot_product_attention
from perceiver_io_tpu.ops.position import RotaryEmbedding, positions

# torch defaults, required for numerical parity with the reference.
LAYER_NORM_EPS = 1e-5


def _dense(features: int, use_bias: bool, init_scale: float, dtype, name: str) -> nn.Dense:
    return nn.Dense(
        features,
        use_bias=use_bias,
        kernel_init=nn.initializers.normal(stddev=init_scale),
        bias_init=nn.initializers.zeros,
        dtype=dtype,
        name=name,
    )


def _layer_norm(dtype, name: str) -> nn.LayerNorm:
    # use_fast_variance=False: two-pass variance matches torch numerically
    return nn.LayerNorm(epsilon=LAYER_NORM_EPS, dtype=dtype, name=name, use_fast_variance=False)


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x ** 2) + eps) * scale`` over the last axis, no mean
    taken off and no bias. Statistics and the gain in float32, one rounding
    to ``dtype``."""

    epsilon: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.epsilon) * scale).astype(self.dtype)


def _remat_policy(offload: bool):
    """Remat saving policy for activation checkpointing: what a recomputed
    layer keeps between its forward and its backward. Either way it keeps, by
    name, each flash call's output and log-sum-exp
    (``flash_attention.SAVED_NAMES``: ``o`` ``(b, h, i, dv)`` in the compute
    dtype and ``lse`` ``(b, h, i, 128)`` float32 a call), so the backward's
    re-run of the layer hands them to the flash backward and the forward
    kernel does not run a second time; on the einsum path no such name exists
    and nothing is kept. All else is rematerialized. ``offload=True`` is the
    TPU-native equivalent of the reference's
    ``checkpoint_wrapper(offload_to_cpu=True)`` (reference
    ``modules.py:347-348``): the layer-boundary inputs (tagged
    ``remat_layer_input`` via ``checkpoint_name``) are saved too, but moved to
    pinned host memory — HBM holds no per-layer activations between forward
    and backward but the kernels' two."""
    from perceiver_io_tpu.ops.flash_attention import SAVED_NAMES
    from perceiver_io_tpu.ops.sparse_attention import KL_GRAD_NAMES, SELECTION_NAME

    # a sparse layer's packed selection (32 MB at 16,384 positions) is kept
    # too, and the float32 gradients its indexer loss's kernels form in the
    # forward (72 MB): its backward runs neither the selection nor the kernels
    saved = (*SAVED_NAMES, SELECTION_NAME, *KL_GRAD_NAMES)
    if not offload:
        return jax.checkpoint_policies.save_only_these_names(*saved)
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=list(saved),
        names_which_can_be_offloaded=["remat_layer_input"],
        offload_src="device",
        offload_dst="pinned_host",
    )


class MultiHeadAttention(nn.Module):
    """Multi-head attention (Perceiver IO paper App. E) with optional rotary
    embeddings and causal attention over right-aligned q/kv.

    Reference: ``perceiver/model/core/modules.py:19-154``.

    ``num_kv_heads`` (a divisor of ``num_heads``) is grouped-query attention:
    k and v are projected to that many heads of the same size, and query head
    ``n`` reads key-value head ``n // (num_heads // num_kv_heads)``; the
    attention paths index the shared head and repeat nothing. ``qk_norm``
    puts an :class:`RMSNorm` over each head's channels of q and of k (one
    gain per channel, shared by the heads) before the scale and the rotation.
    ``window`` (with ``causal_attention``) is a sliding window: a query sees
    the ``window`` latest keys, its own position among them. None of the three
    changes the program of a module that leaves it unset.
    """

    num_heads: int
    num_q_input_channels: int
    num_kv_input_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    num_output_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    dropout: float = 0.0
    qkv_bias: bool = True
    out_bias: bool = True
    init_scale: float = 0.02
    dtype: Any = jnp.float32
    attention_impl: str = "auto"
    num_kv_heads: Optional[int] = None
    qk_norm: bool = False
    norm_eps: float = 1e-5
    window: Optional[int] = None

    def _channels(self) -> Tuple[int, int, int]:
        qk = self.num_qk_channels or self.num_q_input_channels
        v = self.num_v_channels or qk
        out = self.num_output_channels or self.num_q_input_channels
        if qk % self.num_heads != 0:
            raise ValueError("num_qk_channels must be divisible by num_heads")
        if v % self.num_heads != 0:
            raise ValueError("num_v_channels must be divisible by num_heads")
        if self.num_heads % self._kv_heads != 0:
            raise ValueError("num_heads must be divisible by num_kv_heads")
        return qk, v, out

    @property
    def _kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def setup(self):
        qk, v, out = self._channels()
        shared = self.num_heads // self._kv_heads  # query heads a key-value head
        self.q_proj = _dense(qk, self.qkv_bias, self.init_scale, self.dtype, "q_proj")
        self.k_proj = _dense(qk // shared, self.qkv_bias, self.init_scale, self.dtype, "k_proj")
        self.v_proj = _dense(v // shared, self.qkv_bias, self.init_scale, self.dtype, "v_proj")
        self.o_proj = _dense(out, self.out_bias, self.init_scale, self.dtype, "o_proj")
        if self.qk_norm:
            self.q_norm = RMSNorm(self.norm_eps, self.dtype, name="q_norm")
            self.k_norm = RMSNorm(self.norm_eps, self.dtype, name="k_norm")

    def _split_heads(self, x: jnp.ndarray, num_heads: Optional[int] = None) -> jnp.ndarray:
        b, n, _ = x.shape
        return x.reshape(b, n, num_heads or self.num_heads, -1).transpose(0, 2, 1, 3)

    @staticmethod
    def _norm_heads(norm: RMSNorm, x_flat: jnp.ndarray, num_heads: int) -> jnp.ndarray:
        """``norm`` over each head's channels of a projection's flat output."""
        b, n, _ = x_flat.shape
        return norm(x_flat.reshape(b, n, num_heads, -1)).reshape(x_flat.shape)

    def _merge_heads(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, n, c = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, n, h * c)

    def project_q(self, x_q: jnp.ndarray, rot_pos_emb: Optional[RotaryEmbedding] = None) -> jnp.ndarray:
        """(b, n, Dq) -> scaled + rotated (b, h, n, ck). Exposed for the
        KV-cache decode loop."""
        return self._finish_q(self.q_proj(x_q), rot_pos_emb)

    def _finish_q(
        self, q_flat: jnp.ndarray, rot_pos_emb: Optional[RotaryEmbedding]
    ) -> jnp.ndarray:
        """Scale, then rotate (the reference's order of operations), then
        split heads."""
        qk, _, _ = self._channels()
        if self.qk_norm:
            q_flat = self._norm_heads(self.q_norm, q_flat, self.num_heads)
        q_flat = q_flat * ((qk // self.num_heads) ** -0.5)
        return self._split_heads(self._rotary(q_flat, rot_pos_emb))

    def _finish_k(
        self, k_flat: jnp.ndarray, rot_pos_emb: Optional[RotaryEmbedding]
    ) -> jnp.ndarray:
        if self.qk_norm:
            k_flat = self._norm_heads(self.k_norm, k_flat, self._kv_heads)
        return self._split_heads(self._rotary(k_flat, rot_pos_emb, self._kv_heads), self._kv_heads)

    def _rotary(
        self, x_flat: jnp.ndarray, rot_pos_emb: Optional[RotaryEmbedding],
        num_heads: Optional[int] = None,
    ) -> jnp.ndarray:
        """Rotate the projection's ``(b, n, h * c)`` output as it stands:
        one elementwise pass over full rows, so the head split is the only
        relayout between the projection and the kernel."""
        if rot_pos_emb is None:
            return x_flat
        with jax.named_scope("rotary"):
            return rot_pos_emb.rotate(x_flat, num_heads or self.num_heads)

    def project_kv(
        self, x_kv: jnp.ndarray, rot_pos_emb: Optional[RotaryEmbedding] = None
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(b, n, Dkv) -> rotated (b, h, n, ck), (b, h, n, cv). Exposed for
        the KV-cache decode loop (keys are cached post-rotation; rotary is
        relative so a global position offset cancels in attention scores)."""
        k_flat, v_flat = self.k_proj(x_kv), self.v_proj(x_kv)
        return self._finish_k(k_flat, rot_pos_emb), self._split_heads(v_flat, self._kv_heads)

    def project_out(self, o: jnp.ndarray) -> jnp.ndarray:
        """(b, h, n, cv) raw attention -> merged + output-projected
        (b, n, out). Exposed for attention implementations that bypass
        :meth:`attend` (the ragged paged kernel returns raw per-head
        attention; this is the projection ``attend`` would have applied)."""
        return self.o_proj(self._merge_heads(o))

    def attend(
        self,
        q: jnp.ndarray,
        k: jnp.ndarray,
        v: jnp.ndarray,
        pad_mask: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        """Attention + output projection over pre-projected heads."""
        dropout_rng = None
        if not deterministic and self.dropout > 0.0:
            dropout_rng = self.make_rng("dropout")
        o = dot_product_attention(
            q,
            k,
            v,
            pad_mask=pad_mask,
            causal=self.causal_attention,
            dropout_rate=0.0 if deterministic else self.dropout,
            dropout_rng=dropout_rng,
            max_heads_parallel=self.max_heads_parallel,
            impl=self.attention_impl,
            window=self.window,
        )
        return self.project_out(o)

    def __call__(
        self,
        x_q: jnp.ndarray,
        x_kv: jnp.ndarray,
        pad_mask: Optional[jnp.ndarray] = None,
        rot_pos_emb_q: Optional[RotaryEmbedding] = None,
        rot_pos_emb_k: Optional[RotaryEmbedding] = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        q = self.project_q(x_q, rot_pos_emb_q)
        k, v = self.project_kv(x_kv, rot_pos_emb_k)
        return self.attend(q, k, v, pad_mask=pad_mask, deterministic=deterministic)


class LatentAttention(nn.Module):
    """Causal self-attention whose queries, keys and values come out of
    low-rank latents (multi-head latent attention, the DeepSeek-V3 form;
    docs/lm.md): ``cq = rms(u Wqa)`` and ``q = cq Wqb`` to ``num_heads`` heads
    of ``qk_rope_head_dim`` rotary and ``qk_nope_head_dim`` other channels;
    ``u Wkva`` gives ``ckv`` (``kv_lora_rank`` channels, then ``rms``) and one
    rotary key head beside it, which every head shares; ``ckv Wkvb`` gives
    each head its ``qk_nope_head_dim`` key channels and ``v_head_dim`` values.
    Scores are over the ``qk_rope_head_dim + qk_nope_head_dim`` channels of a
    head, scaled by their number; no bias anywhere.

    The training form: keys and values are expanded to every head and the
    rotary key is broadcast, so the kernels of ``dot_product_attention`` see
    plain heads. A head's channels are laid out rotary first (``q_b_proj``'s
    columns by head ``[rotary | other]``), so that :class:`RotaryEmbedding`,
    which rotates a head's leading channels in adjacent pairs, runs on the
    projection's flat output as in :class:`MultiHeadAttention`; ``kv_b_proj``'s
    columns are by head ``[key | value]``. The scopes ``latent_q``,
    ``latent_kv`` and ``latent_assemble`` name the two low-rank paths and what
    stands between them and the kernels (docs/observability.md).
    """

    num_heads: int
    num_input_channels: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    norm_eps: float = 1e-5
    init_scale: float = 0.02
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    @nn.compact
    def __call__(
        self, u: jnp.ndarray, pad_mask: Optional[jnp.ndarray] = None,
        rot_pos_emb: Optional[RotaryEmbedding] = None,
    ) -> jnp.ndarray:
        b, n, _ = u.shape
        h, dn, dr, dv = self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        dense = lambda features, name: _dense(features, False, self.init_scale, self.dtype, name)
        norm = lambda name: RMSNorm(self.norm_eps, self.dtype, name=name)
        with jax.named_scope("latent_q"):
            q_flat = dense(h * (dr + dn), "q_b_proj")(norm("q_a_norm")(dense(self.q_lora_rank, "q_a_proj")(u)))
        with jax.named_scope("latent_kv"):
            kv_a = dense(self.kv_lora_rank + dr, "kv_a_proj")(u)
            kv = dense(h * (dn + dv), "kv_b_proj")(norm("kv_a_norm")(kv_a[..., :self.kv_lora_rank]))
        with jax.named_scope("latent_assemble"):
            q_flat = q_flat * ((dr + dn) ** -0.5)
            k_rot = kv_a[..., self.kv_lora_rank:]
            if rot_pos_emb is not None:
                with jax.named_scope("rotary"):
                    q_flat = rot_pos_emb.rotate(q_flat, h)
                    k_rot = rot_pos_emb.rotate(k_rot, 1)
            kv = kv.reshape(b, n, h, dn + dv)
            k = jnp.concatenate(
                [jnp.broadcast_to(k_rot[:, :, None, :], (b, n, h, dr)), kv[..., :dn]], axis=-1)
            q = q_flat.reshape(b, n, h, dr + dn).transpose(0, 2, 1, 3)
            k, v = k.transpose(0, 2, 1, 3), kv[..., dn:].transpose(0, 2, 1, 3)
        o = dot_product_attention(q, k, v, pad_mask=pad_mask, causal=True, impl=self.attention_impl)
        with jax.named_scope("latent_assemble"):
            o = o.transpose(0, 2, 1, 3).reshape(b, n, h * dv)
        return dense(self.num_input_channels, "o_proj")(o)


class Indexer(nn.Module):
    """The lightning indexer of a learned sparse attention layer (the
    DeepSeek-V3.2 form without a query latent; docs/lm.md): from ``u`` ``(b,
    n, c)``, ``q_i = rope(u Wq)`` to ``num_heads`` heads of ``head_dim``,
    ``k_i = rope(LN(u Wk))`` one head of ``head_dim`` shared by all, and the
    heads' weights ``w = u Ww / sqrt(num_heads * head_dim)`` in float32. The
    rotation is over the whole head. Returns ``(q_i (b, n, H, d), k_i (b, n,
    d), w (b, n, H))``; ``ops/sparse_attention.py`` scores and selects from
    them. The projections are named apart from the attention's (``wq``,
    ``wk``, ``weights_proj``), so that no head-parallel sharding rule reads
    them as the attention's heads (``parallel/partition.py``)."""

    num_heads: int
    head_dim: int
    norm_eps: float = 1e-6
    init_scale: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: jnp.ndarray, rot_pos_emb: Optional[RotaryEmbedding] = None):
        b, n, _ = u.shape
        h, d = self.num_heads, self.head_dim
        q = _dense(h * d, False, self.init_scale, self.dtype, "wq")(u)
        k = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, name="k_norm",
                         use_fast_variance=False)(_dense(d, False, self.init_scale, self.dtype, "wk")(u))
        if rot_pos_emb is not None:
            with jax.named_scope("rotary"):
                q, k = rot_pos_emb.rotate(q, h), rot_pos_emb.rotate(k, 1)
        # float32 out: the scores, and with them the selection, are float32
        w = _dense(h, False, self.init_scale, jnp.float32, "weights_proj")(u) * (h * d) ** -0.5
        return q.reshape(b, n, h, d), k, w


class CrossAttention(nn.Module):
    """Pre-layer-norm cross-attention with the Perceiver-AR ``x_kv_prefix``
    path: keys/values = concat(prefix, query) so latents self-attend at the
    sequence tail (reference ``modules.py:157-203``)."""

    num_heads: int
    num_q_input_channels: int
    num_kv_input_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    dropout: float = 0.0
    qkv_bias: bool = True
    out_bias: bool = True
    init_scale: float = 0.02
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        self.q_norm = _layer_norm(self.dtype, "q_norm")
        self.kv_norm = _layer_norm(self.dtype, "kv_norm")
        self.attention = MultiHeadAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_q_input_channels,
            num_kv_input_channels=self.num_kv_input_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            name="attention",
        )

    def __call__(
        self,
        x_q: jnp.ndarray,
        x_kv: Optional[jnp.ndarray] = None,
        x_kv_prefix: Optional[jnp.ndarray] = None,
        pad_mask: Optional[jnp.ndarray] = None,
        rot_pos_emb_q: Optional[RotaryEmbedding] = None,
        rot_pos_emb_k: Optional[RotaryEmbedding] = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        x_q = self.q_norm(x_q)
        if x_kv is None:
            x_kv_prefix = self.kv_norm(x_kv_prefix)
            x_kv = jnp.concatenate([x_kv_prefix, x_q], axis=1)
        else:
            x_kv = self.kv_norm(x_kv)
        return self.attention(
            x_q,
            x_kv,
            pad_mask=pad_mask,
            rot_pos_emb_q=rot_pos_emb_q,
            rot_pos_emb_k=rot_pos_emb_k,
            deterministic=deterministic,
        )


class SelfAttention(nn.Module):
    """Pre-layer-norm self-attention (reference ``modules.py:206-238``)."""

    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    dropout: float = 0.0
    qkv_bias: bool = True
    out_bias: bool = True
    init_scale: float = 0.02
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        self.norm = _layer_norm(self.dtype, "norm")
        self.attention = MultiHeadAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_channels,
            num_kv_input_channels=self.num_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            name="attention",
        )

    def __call__(
        self,
        x: jnp.ndarray,
        pad_mask: Optional[jnp.ndarray] = None,
        rot_pos_emb: Optional[RotaryEmbedding] = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        x = self.norm(x)
        return self.attention(
            x,
            x,
            pad_mask=pad_mask,
            rot_pos_emb_q=rot_pos_emb,
            rot_pos_emb_k=rot_pos_emb,
            deterministic=deterministic,
        )


class MLP(nn.Module):
    """LayerNorm -> Dense(widening*ch) -> GELU(exact) -> Dense(ch)
    (reference ``modules.py:353-360``)."""

    num_channels: int
    widening_factor: int
    bias: bool = True
    init_scale: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = _layer_norm(self.dtype, "norm")(x)
        x = _dense(self.widening_factor * self.num_channels, self.bias, self.init_scale, self.dtype, "hidden")(x)
        x = nn.gelu(x, approximate=False)
        x = _dense(self.num_channels, self.bias, self.init_scale, self.dtype, "out")(x)
        return x


class _ResidualDropout(nn.Module):
    """Dropout on the residual branch before adding (reference
    ``utils.py:17-24``)."""

    rate: float

    @nn.compact
    def __call__(self, branch: jnp.ndarray, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        branch = nn.Dropout(rate=self.rate, name="drop")(branch, deterministic=deterministic)
        return branch + x


class CrossAttentionLayer(nn.Module):
    """Residual cross-attention + residual MLP (reference ``modules.py:241-274``)."""

    num_heads: int
    num_q_input_channels: int
    num_kv_input_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    attention_residual: bool = True
    qkv_bias: bool = True
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        self.cross_attn = CrossAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_q_input_channels,
            num_kv_input_channels=self.num_kv_input_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            name="cross_attn",
        )
        self.mlp = MLP(
            num_channels=self.num_q_input_channels,
            widening_factor=self.widening_factor,
            bias=self.mlp_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            name="mlp",
        )
        self.attn_residual = _ResidualDropout(self.residual_dropout, name="attn_residual")
        self.mlp_residual = _ResidualDropout(self.residual_dropout, name="mlp_residual")

    def __call__(
        self,
        x_q: jnp.ndarray,
        x_kv: Optional[jnp.ndarray] = None,
        x_kv_prefix: Optional[jnp.ndarray] = None,
        pad_mask: Optional[jnp.ndarray] = None,
        rot_pos_emb_q: Optional[RotaryEmbedding] = None,
        rot_pos_emb_k: Optional[RotaryEmbedding] = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        x_q = checkpoint_name(x_q, "remat_layer_input")
        attn_out = self.cross_attn(
            x_q,
            x_kv=x_kv,
            x_kv_prefix=x_kv_prefix,
            pad_mask=pad_mask,
            rot_pos_emb_q=rot_pos_emb_q,
            rot_pos_emb_k=rot_pos_emb_k,
            deterministic=deterministic,
        )
        if self.attention_residual:
            x = self.attn_residual(attn_out, x_q, deterministic=deterministic)
        else:
            x = attn_out
        return self.mlp_residual(self.mlp(x), x, deterministic=deterministic)


class SelfAttentionLayer(nn.Module):
    """Residual self-attention + residual MLP (reference ``modules.py:277-307``)."""

    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    qkv_bias: bool = True
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        self.self_attn = SelfAttention(
            num_heads=self.num_heads,
            num_channels=self.num_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            name="self_attn",
        )
        self.mlp = MLP(
            num_channels=self.num_channels,
            widening_factor=self.widening_factor,
            bias=self.mlp_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            name="mlp",
        )
        self.attn_residual = _ResidualDropout(self.residual_dropout, name="attn_residual")
        self.mlp_residual = _ResidualDropout(self.residual_dropout, name="mlp_residual")

    def __call__(
        self,
        x: jnp.ndarray,
        pad_mask: Optional[jnp.ndarray] = None,
        rot_pos_emb: Optional[RotaryEmbedding] = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        x = checkpoint_name(x, "remat_layer_input")
        attn_out = self.self_attn(x, pad_mask=pad_mask, rot_pos_emb=rot_pos_emb, deterministic=deterministic)
        x = self.attn_residual(attn_out, x, deterministic=deterministic)
        return self.mlp_residual(self.mlp(x), x, deterministic=deterministic)


class SelfAttentionBlock(nn.Module):
    """Stack of self-attention layers; ``activation_checkpointing`` remats
    each layer (fairscale ``checkpoint_wrapper`` equivalent, reference
    ``modules.py:310-350``).

    ``rotary_all_layers=False`` replicates a load-bearing reference behavior:
    its custom ``Sequential`` forwards kwargs only to the *first* submodule
    (reference ``utils.py:4-14``), so rotary embeddings reach only the first
    self-attention layer of a block — Perceiver AR checkpoints are trained
    with that semantics. Set True for rotary at every layer. ``pad_mask`` is
    always forwarded to every layer (no reference call site passes one to a
    block, so parity is unaffected)."""

    num_layers: int
    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    activation_checkpointing: bool = False
    activation_offloading: bool = False
    rotary_all_layers: bool = False
    qkv_bias: bool = True
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        layer_cls = SelfAttentionLayer
        if self.activation_checkpointing:
            # argnums include the module as 0: (x=1, pad_mask=2, rot_pos_emb=3, deterministic=4)
            layer_cls = nn.remat(
                SelfAttentionLayer,
                static_argnums=(4,),
                policy=_remat_policy(self.activation_offloading),
            )
        self.layers = [
            layer_cls(
                num_heads=self.num_heads,
                num_channels=self.num_channels,
                num_qk_channels=self.num_qk_channels,
                num_v_channels=self.num_v_channels,
                max_heads_parallel=self.max_heads_parallel,
                causal_attention=self.causal_attention,
                widening_factor=self.widening_factor,
                dropout=self.dropout,
                residual_dropout=self.residual_dropout,
                qkv_bias=self.qkv_bias,
                out_bias=self.out_bias,
                mlp_bias=self.mlp_bias,
                init_scale=self.init_scale,
                dtype=self.dtype,
                attention_impl=self.attention_impl,
                name=f"layers_{i}",
            )
            for i in range(self.num_layers)
        ]

    def __call__(
        self,
        x: jnp.ndarray,
        pad_mask: Optional[jnp.ndarray] = None,
        rot_pos_emb: Optional[RotaryEmbedding] = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        for i, layer in enumerate(self.layers):
            rot = rot_pos_emb if (i == 0 or self.rotary_all_layers) else None
            x = layer(x, pad_mask, rot, deterministic)
        return x


class PerceiverEncoder(nn.Module):
    """Perceiver IO encoder: a trainable latent array cross-attends to the
    adapted input, followed by self-attention blocks; supports repeated
    cross-attention with weight-sharing rules (reference
    ``modules.py:363-513``).

    Weight sharing is module reuse: ``cross_attn_1``/``self_attn_1`` are
    reapplied at later depths unless an extra unshared module is configured —
    one parameter set appears once in the pytree regardless of how many times
    it is applied, which keeps checkpoint layout 1:1 with the reference.
    """

    input_adapter: nn.Module
    num_latents: int
    num_latent_channels: int
    num_cross_attention_heads: int = 4
    num_cross_attention_qk_channels: Optional[int] = None
    num_cross_attention_v_channels: Optional[int] = None
    num_cross_attention_layers: int = 1
    first_cross_attention_layer_shared: bool = False
    cross_attention_widening_factor: int = 1
    num_self_attention_heads: int = 4
    num_self_attention_qk_channels: Optional[int] = None
    num_self_attention_v_channels: Optional[int] = None
    num_self_attention_layers_per_block: int = 6
    num_self_attention_blocks: int = 1
    first_self_attention_block_shared: bool = True
    self_attention_widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    init_scale: float = 0.02
    activation_checkpointing: bool = False
    activation_offloading: bool = False
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    @property
    def extra_cross_attention_layer(self) -> bool:
        return self.num_cross_attention_layers > 1 and not self.first_cross_attention_layer_shared

    @property
    def extra_self_attention_block(self) -> bool:
        return self.num_self_attention_blocks > 1 and not self.first_self_attention_block_shared

    def setup(self):
        if self.num_cross_attention_layers <= 0:
            raise ValueError("num_cross_attention_layers must be > 0")
        if self.num_self_attention_blocks <= 0:
            raise ValueError("num_self_attention_blocks must be > 0")
        if self.num_cross_attention_layers > self.num_self_attention_blocks:
            raise ValueError("num_cross_attention_layers must be <= num_self_attention_blocks")

        self.latent_provider = TrainableQueryProvider(
            num_queries=self.num_latents,
            num_query_channels_=self.num_latent_channels,
            init_scale=self.init_scale,
            dtype=self.dtype,
            name="latent_provider",
        )

        def cross_attn(name):
            cls = CrossAttentionLayer
            if self.activation_checkpointing:
                # argnums include the module as 0: (x_q=1, x_kv=2, x_kv_prefix=3, pad_mask=4,
                # rot_q=5, rot_k=6, deterministic=7)
                cls = nn.remat(
                    CrossAttentionLayer,
                    static_argnums=(7,),
                    policy=_remat_policy(self.activation_offloading),
                )
            return cls(
                num_heads=self.num_cross_attention_heads,
                num_q_input_channels=self.num_latent_channels,
                num_kv_input_channels=self.input_adapter.num_input_channels,
                num_qk_channels=self.num_cross_attention_qk_channels,
                num_v_channels=self.num_cross_attention_v_channels,
                widening_factor=self.cross_attention_widening_factor,
                dropout=self.dropout,
                residual_dropout=self.residual_dropout,
                init_scale=self.init_scale,
                dtype=self.dtype,
                attention_impl=self.attention_impl,
                name=name,
            )

        def self_attn(name):
            return SelfAttentionBlock(
                num_layers=self.num_self_attention_layers_per_block,
                num_heads=self.num_self_attention_heads,
                num_channels=self.num_latent_channels,
                num_qk_channels=self.num_self_attention_qk_channels,
                num_v_channels=self.num_self_attention_v_channels,
                widening_factor=self.self_attention_widening_factor,
                dropout=self.dropout,
                residual_dropout=self.residual_dropout,
                activation_checkpointing=self.activation_checkpointing,
                activation_offloading=self.activation_offloading,
                init_scale=self.init_scale,
                dtype=self.dtype,
                attention_impl=self.attention_impl,
                name=name,
            )

        self.cross_attn_1 = cross_attn("cross_attn_1")
        self.self_attn_1 = self_attn("self_attn_1")
        if self.extra_cross_attention_layer:
            self.cross_attn_n = cross_attn("cross_attn_n")
        if self.extra_self_attention_block:
            self.self_attn_n = self_attn("self_attn_n")

    def __call__(
        self,
        x: jnp.ndarray,
        pad_mask: Optional[jnp.ndarray] = None,
        return_adapted_input: bool = False,
        deterministic: bool = True,
    ):
        x_adapted = self.input_adapter(x)
        b = x_adapted.shape[0]
        x_latent = jnp.broadcast_to(
            self.latent_provider(), (b, self.num_latents, self.num_latent_channels)
        )

        # Positional calls: rematted modules index static_argnums positionally.
        x_latent = self.cross_attn_1(x_latent, x_adapted, None, pad_mask, None, None, deterministic)
        x_latent = self.self_attn_1(x_latent, None, None, deterministic)

        cross_attn_n = self.cross_attn_n if self.extra_cross_attention_layer else self.cross_attn_1
        self_attn_n = self.self_attn_n if self.extra_self_attention_block else self.self_attn_1

        for i in range(1, self.num_self_attention_blocks):
            if i < self.num_cross_attention_layers:
                x_latent = cross_attn_n(x_latent, x_adapted, None, pad_mask, None, None, deterministic)
            x_latent = self_attn_n(x_latent, None, None, deterministic)

        if return_adapted_input:
            return x_latent, x_adapted
        return x_latent


class PerceiverDecoder(nn.Module):
    """Perceiver IO decoder: output queries cross-attend to latents; optional
    non-residual cross-attention (MLM); output adapter maps to task output
    (reference ``modules.py:516-581``).

    ``output_query_provider`` may be None, in which case decoder queries are
    the adapted encoder input passed via ``x_adapted`` (optical flow,
    reference ``backend.py:124,135-137``).
    """

    output_adapter: nn.Module
    output_query_provider: Optional[nn.Module]
    num_latent_channels: int
    num_output_query_channels: int
    num_cross_attention_heads: int = 4
    num_cross_attention_qk_channels: Optional[int] = None
    num_cross_attention_v_channels: Optional[int] = None
    cross_attention_widening_factor: int = 1
    cross_attention_residual: bool = True
    dropout: float = 0.0
    init_scale: float = 0.02
    activation_checkpointing: bool = False
    activation_offloading: bool = False
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        cls = CrossAttentionLayer
        if self.activation_checkpointing:
            cls = nn.remat(
                CrossAttentionLayer,
                static_argnums=(7,),
                policy=_remat_policy(self.activation_offloading),
            )
        self.cross_attn = cls(
            num_heads=self.num_cross_attention_heads,
            num_q_input_channels=self.num_output_query_channels,
            num_kv_input_channels=self.num_latent_channels,
            num_qk_channels=self.num_cross_attention_qk_channels,
            num_v_channels=self.num_cross_attention_v_channels,
            widening_factor=self.cross_attention_widening_factor,
            attention_residual=self.cross_attention_residual,
            dropout=self.dropout,
            init_scale=self.init_scale,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            name="cross_attn",
        )

    def __call__(
        self,
        x_latent: jnp.ndarray,
        x_adapted: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
        **adapter_kwargs,
    ) -> jnp.ndarray:
        if self.output_query_provider is not None:
            output_query = self.output_query_provider(x_adapted)
            if output_query.shape[0] == 1 and x_latent.shape[0] > 1:
                output_query = jnp.broadcast_to(
                    output_query, (x_latent.shape[0], *output_query.shape[1:])
                )
        else:
            output_query = x_adapted
        output = self.cross_attn(output_query, x_latent, None, None, None, None, deterministic)
        return self.output_adapter(output, **adapter_kwargs)


class PerceiverIO(nn.Module):
    """Encoder + decoder container (reference ``modules.py:584-594``)."""

    encoder: nn.Module
    decoder: nn.Module

    def __call__(self, x, pad_mask=None, deterministic: bool = True, **decoder_kwargs):
        x_latent = self.encoder(x, pad_mask=pad_mask, deterministic=deterministic)
        return self.decoder(x_latent, deterministic=deterministic, **decoder_kwargs)


class PerceiverAR(nn.Module):
    """Perceiver AR (https://arxiv.org/abs/2202.07765): a causal cross-attention
    of latents (the sequence tail) over [prefix ‖ latents], followed by a causal
    self-attention stack over latents, with rotary position embeddings and
    train-time cross-attention (prefix) dropout (reference
    ``modules.py:597-735``).

    ``input_adapter`` must return ``(x_embedded, frq_pos_enc)`` given
    ``(token_ids, abs_pos)`` — the RotarySupport contract
    (reference ``adapter.py:22-32``).

    Prefix dropout keeps a *static* number of positions
    ``keep = prefix_len - int(prefix_len * p)`` chosen by per-row ``top_k``
    over uniform scores with indices re-sorted to preserve order — a
    fixed-shape formulation of the reference's ragged boolean-mask gather
    (``modules.py:697-714``), required for XLA static shapes.
    """

    input_adapter: nn.Module
    num_heads: int = 8
    max_heads_parallel: Optional[int] = None
    num_self_attention_layers: int = 6
    self_attention_widening_factor: int = 4
    cross_attention_widening_factor: int = 4
    cross_attention_dropout: float = 0.5
    post_attention_dropout: float = 0.0
    residual_dropout: float = 0.0
    activation_checkpointing: bool = False
    activation_offloading: bool = False
    init_scale: float = 0.02
    dtype: Any = jnp.float32
    attention_impl: str = "auto"

    def setup(self):
        num_channels = self.input_adapter.num_input_channels
        cls = CrossAttentionLayer
        if self.activation_checkpointing:
            cls = nn.remat(
                CrossAttentionLayer,
                static_argnums=(7,),
                policy=_remat_policy(self.activation_offloading),
            )
        self.cross_attention = cls(
            num_heads=self.num_heads,
            num_q_input_channels=num_channels,
            num_kv_input_channels=num_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=True,
            widening_factor=self.cross_attention_widening_factor,
            dropout=self.post_attention_dropout,
            residual_dropout=self.residual_dropout,
            qkv_bias=False,
            out_bias=True,
            mlp_bias=False,
            init_scale=self.init_scale,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            name="cross_attention",
        )
        self.self_attention = SelfAttentionBlock(
            num_layers=self.num_self_attention_layers,
            num_heads=self.num_heads,
            num_channels=num_channels,
            causal_attention=True,
            widening_factor=self.self_attention_widening_factor,
            dropout=self.post_attention_dropout,
            residual_dropout=self.residual_dropout,
            activation_checkpointing=self.activation_checkpointing,
            activation_offloading=self.activation_offloading,
            qkv_bias=False,
            out_bias=False,
            mlp_bias=False,
            init_scale=self.init_scale,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            name="self_attention",
        )

    def __call__(
        self,
        x: jnp.ndarray,
        prefix_len: int,
        pad_mask: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        b, n = x.shape
        if not 0 <= prefix_len < n:
            raise ValueError(f"prefix_len ({prefix_len}) out of valid range [0..{n})")

        if pad_mask is None:
            shift = None
        else:
            # caller must ensure that x is left-padded
            shift = pad_mask.sum(axis=1, keepdims=True)

        x, frq_pos_enc = self.input_adapter(x, abs_pos=positions(b, n, shift=shift))

        x_latent = x[:, prefix_len:]
        x_prefix = x[:, :prefix_len]
        frq_pos_enc_latent = frq_pos_enc[:, prefix_len:]
        frq_pos_enc_prefix = frq_pos_enc[:, :prefix_len]
        pad_mask_latent = pad_mask[:, prefix_len:] if pad_mask is not None else None
        pad_mask_prefix = pad_mask[:, :prefix_len] if pad_mask is not None else None

        if not deterministic and prefix_len > 0 and self.cross_attention_dropout > 0.0:
            keep = prefix_len - int(prefix_len * self.cross_attention_dropout)
            rand = jax.random.uniform(self.make_rng("prefix"), (b, prefix_len))
            _, keep_indices = jax.lax.top_k(rand, keep)
            keep_indices = jnp.sort(keep_indices, axis=-1)  # preserve sequence order
            x_prefix = jnp.take_along_axis(x_prefix, keep_indices[..., None], axis=1)
            frq_pos_enc_prefix = jnp.take_along_axis(frq_pos_enc_prefix, keep_indices[..., None], axis=1)
            if pad_mask_prefix is not None:
                pad_mask_prefix = jnp.take_along_axis(pad_mask_prefix, keep_indices, axis=1)

        frq_pos_enc_q = frq_pos_enc_latent
        frq_pos_enc_k = jnp.concatenate([frq_pos_enc_prefix, frq_pos_enc_latent], axis=1)

        if pad_mask is not None:
            pad_mask = jnp.concatenate([pad_mask_prefix, pad_mask_latent], axis=1)

        x_latent = self.cross_attention(
            x_latent,
            None,
            x_prefix,
            pad_mask,
            RotaryEmbedding(frq_pos_enc_q, right_align=True),
            RotaryEmbedding(frq_pos_enc_k, right_align=True),
            deterministic,
        )
        x_latent = self.self_attention(
            x_latent,
            None,
            RotaryEmbedding(frq_pos_enc_latent, right_align=True),
            deterministic,
        )
        return x_latent

"""Unit tests for the functional ops layer: positions, rotary, Fourier
features, and the attention primitive (mask semantics, head chunking)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.ops.attention import dot_product_attention
from perceiver_io_tpu.ops.position import (
    FourierPositionEncoding,
    RotaryEmbedding,
    frequency_position_encoding,
    positions,
    swap_pairs,
)


def naive_attention(q, k, v, pad_mask=None, causal=False):
    q, k, v = np.asarray(q, np.float64), np.asarray(k, np.float64), np.asarray(v, np.float64)
    logits = np.einsum("bhic,bhjc->bhij", q, k)
    i, j = q.shape[2], k.shape[2]
    if pad_mask is not None:
        logits = np.where(np.asarray(pad_mask)[:, None, None, :], -1e30, logits)
    if causal:
        ii = np.arange(i)[:, None]
        jj = np.arange(j)[None, :]
        logits = np.where(jj <= ii + (j - i), logits, -1e30)
    attn = np.exp(logits - logits.max(-1, keepdims=True))
    attn = attn / attn.sum(-1, keepdims=True)
    return np.einsum("bhij,bhjc->bhic", attn, v)


class TestPositions:
    def test_basic(self):
        p = positions(2, 4)
        np.testing.assert_array_equal(p, [[0, 1, 2, 3], [0, 1, 2, 3]])

    def test_shift_clamps_at_zero(self):
        shift = jnp.array([[2], [0]])
        p = positions(2, 4, shift=shift)
        np.testing.assert_array_equal(p, [[0, 0, 0, 1], [0, 1, 2, 3]])

    def test_shift_shape_validation(self):
        with pytest.raises(ValueError):
            positions(2, 4, shift=jnp.zeros((2,), jnp.int32))


def rotate_oracle(t, frq_pos_enc, right_align=False):
    """The rotation as it was written before the lane-dense pass: slice off
    the rotated channels of ``(b, h, m, c)`` heads, pair them through a
    ``(..., 2)`` view and a ``stack``, concatenate the rest back."""

    def rotate_half(x):
        x = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
        return jnp.stack((-x[..., 1], x[..., 0]), axis=-1).reshape(*x.shape[:-2], -1)

    m, n, rotate_dim = t.shape[-2], frq_pos_enc.shape[-2], frq_pos_enc.shape[-1]
    pos_enc = frq_pos_enc[:, None, n - m :] if right_align else frq_pos_enc[:, None, :m]
    pos_enc = pos_enc.astype(jnp.float32)
    t_rot, t_pass = t[..., :rotate_dim], t[..., rotate_dim:]
    rotated = t_rot.astype(jnp.float32)
    rotated = rotated * jnp.cos(pos_enc) + rotate_half(rotated) * jnp.sin(pos_enc)
    return jnp.concatenate((rotated.astype(t.dtype), t_pass), axis=-1)


def _ulps(x, y, dtype):
    """|x - y| in units of the last place of the larger magnitude."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    eps = float(jnp.finfo(dtype).eps)
    spacing = eps * 2.0 ** np.floor(np.log2(np.maximum(np.maximum(np.abs(x), np.abs(y)), 1e-30)))
    return np.abs(x - y) / spacing


class TestRotary:
    def test_swap_pairs(self):
        x = jnp.arange(1.0, 7.0).reshape(1, 6)
        np.testing.assert_array_equal(swap_pairs(x)[0], [2.0, 1.0, 4.0, 3.0, 6.0, 5.0])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize(
        "rotate_dim,n,m,right_align",
        [(8, 6, 6, False), (16, 6, 6, False), (8, 6, 6, True), (8, 6, 4, True),
         (8, 6, 4, False), (8, 6, 1, True), (16, 6, 1, False)],
        ids=["half", "all_channels", "right_align", "right_align_m_lt_n", "left_m_lt_n",
             "right_align_m1", "all_channels_m1"],
    )
    def test_lane_dense_pass_is_the_paired_rotation(self, rng, dtype, rotate_dim, n, m, right_align):
        """Heads rotated as ``(b, h, m, c)`` and as the projection's flat
        ``(b, m, h * c)`` output both equal the oracle, values and gradients
        (the hand-written backward pass among them): float32 to 1e-6, bfloat16
        to one unit in the last place."""
        b, h, c = 2, 3, 16
        t = jnp.asarray(rng.normal(size=(b, h, m, c)), dtype)
        w = jnp.asarray(rng.normal(size=(b, h, m, c)), dtype)
        enc = frequency_position_encoding(jnp.asarray(rng.integers(0, 500, size=(b, n))), rotate_dim)

        def paired(t, enc):
            return rotate_oracle(t, enc, right_align)

        def by_heads(t, enc):
            return RotaryEmbedding(enc, right_align=right_align).rotate(t)

        def flat(t, enc):
            rot = RotaryEmbedding(enc, right_align=right_align)
            y = rot.rotate(t.transpose(0, 2, 1, 3).reshape(b, m, h * c), num_heads=h)
            return y.reshape(b, m, h, c).transpose(0, 2, 1, 3)

        def value_and_grads(f):
            loss = lambda t, enc: jnp.sum((f(t, enc) * w).astype(jnp.float32))
            return (f(t, enc), *jax.grad(loss, argnums=(0, 1))(t, enc))

        want = value_and_grads(paired)
        for f in (by_heads, flat):
            y, dt, denc = value_and_grads(f)
            # the angles' gradient sums h * c float32 products in another order
            np.testing.assert_allclose(np.asarray(denc), np.asarray(want[2]), rtol=1e-4, atol=1e-4)
            for got, ref in ((y, want[0]), (dt, want[1])):
                assert got.dtype == dtype and got.shape == ref.shape
                if dtype == jnp.float32:
                    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6, rtol=0)
                else:
                    assert _ulps(got, ref, dtype).max() <= 1.0

    def test_frequency_pairing(self):
        enc = frequency_position_encoding(jnp.arange(3)[None], 4)
        assert enc.shape == (1, 3, 4)
        # consecutive channel pairs share a frequency
        np.testing.assert_allclose(enc[0, :, 0], enc[0, :, 1])
        np.testing.assert_allclose(enc[0, :, 2], enc[0, :, 3])

    def test_rotation_preserves_norm(self, rng):
        t = jnp.asarray(rng.normal(size=(2, 3, 5, 8)), jnp.float32)
        enc = frequency_position_encoding(jnp.arange(5)[None].repeat(2, 0), 8)
        rot = RotaryEmbedding(enc)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(rot.rotate(t)), axis=-1),
            np.linalg.norm(np.asarray(t), axis=-1),
            rtol=1e-5,
        )

    def test_relative_position_invariance(self, rng):
        """Attention scores q_i . k_j depend only on i - j: shifting all
        positions by a constant must not change the dot products."""
        dim = 8
        q = jnp.asarray(rng.normal(size=(1, 1, 4, dim)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 1, 4, dim)), jnp.float32)

        def scores(offset):
            enc = frequency_position_encoding(jnp.arange(4)[None] + offset, dim)
            rot = RotaryEmbedding(enc)
            return np.einsum("bhic,bhjc->bhij", np.asarray(rot.rotate(q)), np.asarray(rot.rotate(k)))

        np.testing.assert_allclose(scores(0), scores(17), atol=1e-4)

    def test_right_align(self, rng):
        """With right_align, a length-m input uses the last m positions."""
        dim = 8
        enc = frequency_position_encoding(jnp.arange(6)[None], dim)
        t = jnp.asarray(rng.normal(size=(1, 1, 2, dim)), jnp.float32)
        right = RotaryEmbedding(enc, right_align=True).rotate(t)
        direct = RotaryEmbedding(enc[:, 4:], right_align=False).rotate(t)
        np.testing.assert_allclose(np.asarray(right), np.asarray(direct), atol=1e-6)


class TestFourier:
    def test_channels(self):
        enc = FourierPositionEncoding((5, 7), num_frequency_bands=3)
        assert enc.num_channels == 2 * (2 * 3 + 1)
        out = enc(2)
        assert out.shape == (2, 35, enc.num_channels)

    def test_range(self):
        enc = FourierPositionEncoding((4,), num_frequency_bands=2)
        out = np.asarray(enc(1))
        # raw coordinate channel spans [-1, 1]
        assert out[0, 0, 0] == -1.0 and out[0, -1, 0] == 1.0
        assert np.abs(out).max() <= 1.0 + 1e-6


class TestAttention:
    def test_matches_naive(self, rng):
        q = jnp.asarray(rng.normal(size=(2, 3, 5, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 3, 7, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 3, 7, 4)), jnp.float32)
        out = dot_product_attention(q, k, v, impl="xla")
        np.testing.assert_allclose(np.asarray(out), naive_attention(q, k, v), atol=1e-5)

    def test_causal_right_aligned(self, rng):
        """q_len < kv_len: query i attends kv positions <= i + (j - i_len)."""
        q = jnp.asarray(rng.normal(size=(1, 2, 3, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 2, 7, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 2, 7, 4)), jnp.float32)
        out = dot_product_attention(q, k, v, causal=True, impl="xla")
        np.testing.assert_allclose(np.asarray(out), naive_attention(q, k, v, causal=True), atol=1e-5)

    def test_causal_last_query_sees_all(self, rng):
        """The final query must attend the entire kv sequence; perturbing the
        last key changes only rows allowed to see it."""
        q = jnp.asarray(rng.normal(size=(1, 1, 3, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 1, 5, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 1, 5, 4)), jnp.float32)
        out1 = dot_product_attention(q, k, v, causal=True, impl="xla")
        v2 = v.at[0, 0, -1].add(10.0)
        out2 = dot_product_attention(q, k, v2, causal=True, impl="xla")
        # queries 0..1 cannot see kv position 4; query 2 can
        np.testing.assert_allclose(np.asarray(out1[0, 0, :2]), np.asarray(out2[0, 0, :2]), atol=1e-6)
        assert not np.allclose(np.asarray(out1[0, 0, 2]), np.asarray(out2[0, 0, 2]))

    def test_pad_mask(self, rng):
        q = jnp.asarray(rng.normal(size=(2, 2, 3, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 2, 5, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 2, 5, 4)), jnp.float32)
        pad = jnp.zeros((2, 5), bool).at[0, :2].set(True)
        out = dot_product_attention(q, k, v, pad_mask=pad, impl="xla")
        np.testing.assert_allclose(np.asarray(out), naive_attention(q, k, v, pad_mask=pad), atol=1e-5)
        # padded keys have no influence
        k2 = k.at[0, :, :2].add(5.0)
        out2 = dot_product_attention(q, k2, v, pad_mask=pad, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)

    def test_head_chunking_equivalence(self, rng):
        q = jnp.asarray(rng.normal(size=(2, 6, 4, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 6, 9, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 6, 9, 8)), jnp.float32)
        full = dot_product_attention(q, k, v, causal=True, impl="xla")
        for chunk in (1, 2, 4):
            chunked = dot_product_attention(
                q, k, v, causal=True, max_heads_parallel=chunk, impl="xla"
            )
            np.testing.assert_allclose(np.asarray(full), np.asarray(chunked), atol=1e-6)

    def test_bf16_inputs_fp32_softmax(self, rng):
        q = jnp.asarray(rng.normal(size=(1, 2, 4, 8)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(1, 2, 4, 8)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(1, 2, 4, 4)), jnp.bfloat16)
        out = dot_product_attention(q, k, v, impl="xla")
        assert out.dtype == jnp.bfloat16
        ref = naive_attention(
            np.asarray(q, np.float32), np.asarray(k, np.float32), np.asarray(v, np.float32)
        )
        np.testing.assert_allclose(np.asarray(out, np.float32), ref, atol=0.05)

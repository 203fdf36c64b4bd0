"""Learned sparse attention (``ops/sparse_attention.py``, the selection path of
``ops/flash_attention.py``, the ``sparse_attention`` layer of the ``lm``
family): the exact selection against ``jax.lax.top_k``, the packed bits both
ways, the flash kernels (interpreted on the CPU) against the einsum path with
a selection, and the layer's dense path where a row is no longer than the
top-k."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.ops import flash_attention
from perceiver_io_tpu.ops import sparse_attention as sa


def _indexer(key, b, n, heads=2, d=16):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, n, heads, d)), jax.random.normal(ks[1], (b, n, d)),
            jax.random.normal(ks[2], (b, n, heads)))


def _top_k_mask(scores, topk):
    """What ``lax.top_k`` takes from each row with its later keys masked."""
    b, n, _ = scores.shape
    rows = jnp.arange(n)
    causal = rows[None, :] <= rows[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, n))
    chosen = jnp.zeros((b, n, n), bool).at[
        jnp.arange(b)[:, None, None], rows[None, :, None], idx].set(True)
    return chosen & causal


@pytest.mark.parametrize("n,topk,ties", [(64, 8, False), (80, 8, False), (256, 40, False), (512, 100, False),
                                         (256, 40, True)],
                         ids=["64_top8", "80_rows_in_words_of_32", "256_top40", "512_top100", "256_top40_ties"])
def test_selection_is_lax_top_k_of_each_rows_causal_scores(n, topk, ties):
    """Each row holds ``min(t + 1, k)`` keys, none after ``t``, and the same
    set as ``lax.top_k``: ties (scores rounded to halves, many equal) go to
    the lower position, and ``-0`` sorts under ``+0`` as there."""
    q_i, k_i, w = _indexer(jax.random.PRNGKey(n), 2, n)
    scores = jax.jit(sa.indexer_scores)(q_i, k_i, w)
    if ties:
        scores = jnp.round(scores * 2) / 2
        scores = scores.at[:, :, ::7].set(-0.0).at[:, :, ::11].set(0.0)
        rows = jnp.arange(n)
        got = jnp.concatenate([sa.select_block(scores[:, r:r + 64], rows[r:r + 64], topk)
                               for r in range(0, n, 64)], axis=1)
    else:
        got = sa.unpack_all(jax.jit(sa.select, static_argnums=3)(q_i, k_i, w, topk))
    want = _top_k_mask(scores, topk)
    assert (np.asarray(got) == np.asarray(want)).all()
    held = np.asarray(got.sum(-1))
    assert (held == np.minimum(np.arange(n) + 1, topk)[None]).all()
    assert not np.asarray(got & ~np.tril(np.ones((n, n), bool))).any()


def test_bits_pack_and_unpack_by_the_kernels_row_blocks():
    """The bits of a row block land where the kernels and ``unpack`` read
    them: at 1024 rows (blocks of 512, 16 words a column) every row's word and
    bit, and a block of 128 rows read back from any of its blocks."""
    n = 1024
    chosen = jax.random.bernoulli(jax.random.PRNGKey(0), 0.3, (1, n, n))
    rows = sa.selection_rows(n)
    parts = [sa.pack(chosen[:, r:r + 128], r, rows) for r in range(0, n, 128)]
    blocks = [sum(jax.lax.bitcast_convert_type(p, jnp.uint32) for p in parts[i:i + 4]) for i in (0, 4)]
    bits = jax.lax.bitcast_convert_type(jnp.concatenate(blocks, axis=1), jnp.int32)
    assert bits.shape == (1, n // 32, n) and rows == 512
    assert (np.asarray(sa.unpack_all(bits)) == np.asarray(chosen)).all()
    for first in (0, 384, 512, 896):
        assert (np.asarray(sa.unpack(bits, first, 128)) == np.asarray(chosen[:, first:first + 128])).all()
    # row r of a block is bit r // 16 of word r % 16
    word = int(np.asarray(bits)[0, 3, 5]) & 0xFFFFFFFF
    assert all(bool(word >> t & 1) == bool(chosen[0, t * 16 + 3, 5]) for t in range(32))
    flags = np.asarray(flash_attention.block_flags(bits, n)).reshape(2, 2)
    assert flags.tolist() == [[1, 1], [1, 1]]


def _attention_inputs(n, b=1, h=4, hk=2, d=32):
    ks = jax.random.split(jax.random.PRNGKey(n), 4)
    return (jax.random.normal(ks[0], (b, h, n, d)) * 0.5, jax.random.normal(ks[1], (b, hk, n, d)) * 0.5,
            jax.random.normal(ks[2], (b, hk, n, d)), jax.random.normal(ks[3], (b, h, n, d)))


@pytest.mark.parametrize("n,topk", [(256, 40), (512, 512), (1024, 100)],
                         ids=["256_top40", "512_every_causal_key", "1024_top100_empty_pairs"])
def test_selected_flash_kernels_match_the_einsum_path_forward_and_backward(n, topk):
    """The three kernels with a selection (interpreted) against the einsum
    path: ``o``, ``lse`` and the gradients of q, k and v, grouped heads. At
    ``topk >= n`` every causal key is selected and the kernels are the causal
    call; at 1024 rows the pair above the diagonal holds no selected key and
    is skipped (its flag is 0)."""
    q, k, v, ct = _attention_inputs(n)
    q_i, k_i, w = _indexer(jax.random.PRNGKey(7), 1, n)
    bits = jax.jit(sa.select, static_argnums=3)(q_i, k_i, w, topk)

    def run(attend):
        def loss(q, k, v):
            o, lse = attend(q, k, v)
            return jnp.sum(o * ct), (o, lse)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (_, (o_f, lse_f)), g_f = run(lambda q, k, v: flash_attention.flash_attention_selected(q, k, v, bits))
    (_, (o_x, lse_x)), g_x = run(lambda q, k, v: sa.attention_xla(q, k, v, bits))
    np.testing.assert_allclose(o_f, o_x, atol=1e-5)
    np.testing.assert_allclose(lse_f, lse_x, atol=1e-5)
    for a, b in zip(g_f, g_x):
        np.testing.assert_allclose(a, b, atol=2e-5)
    if topk >= n:
        causal = flash_attention.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(o_f, causal, atol=1e-5)
    flags = np.asarray(flash_attention.block_flags(bits, n))
    if n == 1024:
        assert flags.tolist() == [1, 0, 1, 1]


def test_a_block_pair_without_a_selected_key_is_skipped():
    """A selection that leaves a whole block pair under the diagonal empty:
    its flag is 0, and the kernels, which skip it, give the einsum path's
    result."""
    n = 1024
    rows = jnp.arange(n)
    # every query keeps itself and the keys of its own 512-block
    chosen = (rows[None, :] <= rows[:, None]) & (rows[None, :] // 512 == rows[:, None] // 512)
    parts = [sa.pack(chosen[None, r:r + 128], r, 512) for r in range(0, n, 128)]
    blocks = [sum(jax.lax.bitcast_convert_type(p, jnp.uint32) for p in parts[i:i + 4]) for i in (0, 4)]
    bits = jax.lax.bitcast_convert_type(jnp.concatenate(blocks, axis=1), jnp.int32)
    assert np.asarray(flash_attention.block_flags(bits, n)).tolist() == [1, 0, 0, 1]
    q, k, v, _ = _attention_inputs(n)
    o_f, _ = jax.jit(flash_attention.flash_attention_selected)(q, k, v, bits)
    o_x, _ = sa.attention_xla(q, k, v, bits)
    np.testing.assert_allclose(o_f, o_x, atol=1e-5)


def _block_diagonal_bits(n, rows=512):
    """Every query keeps itself and the earlier keys of its own block of
    ``rows``: the block pairs under the diagonal hold no selected key."""
    at = jnp.arange(n)
    chosen = (at[None, :] <= at[:, None]) & (at[None, :] // rows == at[:, None] // rows)
    parts = [sa.pack(chosen[None, r:r + 128], r, rows) for r in range(0, n, 128)]
    per = rows // 128
    blocks = [sum(jax.lax.bitcast_convert_type(p, jnp.uint32) for p in parts[i:i + per])
              for i in range(0, len(parts), per)]
    return jax.lax.bitcast_convert_type(jnp.concatenate(blocks, axis=1), jnp.int32)


@pytest.mark.parametrize("n,topk", [(256, 40), (80, 40), (512, 512), (1024, None)],
                         ids=["256", "80_rows_padded_to_words", "512_every_causal_key", "1024_empty_block_pair"])
def test_indexer_loss_in_blocks_is_the_kl_at_once(n, topk):
    """The loss against the KL written out whole and its gradient reaching
    the indexer's inputs alone, through the KL kernels (interpreted) where
    the rows are whole blocks and through XLA's blocked loop, which the
    kernels' numbers are held to as well, at 80 rows (whose blocks hold 16
    rows of padding, which add nothing). At 512 rows every causal key is
    selected; at 1024 the pair under the diagonal holds none (its flag is 0)."""
    b, h, hk, d = 1, 4, 2, 32
    q, k, _, _ = _attention_inputs(n)
    q_i, k_i, w = _indexer(jax.random.PRNGKey(3), b, n)
    bits = _block_diagonal_bits(n) if topk is None else jax.jit(sa.select, static_argnums=3)(q_i, k_i, w, topk)
    _, lse = sa.attention_xla(q, k, k, bits)
    kernels = sa._kl_kernels_fit(q, k, q_i)
    assert kernels == (n != 80)
    # heads whose row block alone is over the kernels' VMEM cap: XLA's loss
    wide = jax.ShapeDtypeStruct((b, 128, n, 512), jnp.bfloat16)
    assert n == 80 or not sa._kl_kernels_fit(wide, wide, q_i)
    if topk is None:
        assert np.asarray(flash_attention.block_flags(bits, n)).tolist() == [1, 0, 0, 1]

    def whole(q_i, k_i, w, q, k):
        chosen = sa.unpack_all(bits)
        logits = jnp.einsum("bkgic,bkjc->bkgij", q.reshape(b, hk, h // hk, n, d), k).reshape(b, h, n, n)
        p = jnp.where(chosen[:, None], jax.nn.softmax(jnp.where(chosen[:, None], logits, -1e30), -1), 0.0)
        p = jax.lax.stop_gradient(p.mean(1))
        log_q = jax.nn.log_softmax(jnp.where(chosen, sa.indexer_scores(q_i, k_i, w), -1e30), -1)
        return jnp.sum(jnp.where(chosen, jax.scipy.special.xlogy(p, p) - p * log_q, 0.0)) / (b * n)

    blocked = lambda q_i, k_i, w, q, k: sa.indexer_loss(q, k, lse, q_i, k_i, w, bits)
    in_xla = lambda q_i, k_i, w, q, k: sa.indexer_loss_xla(q, k, lse, q_i, k_i, w, bits)
    each = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(q_i, k_i, w, q, k)
    (got, g_got), (want, g_want), (xla, g_xla) = jax.jit(
        lambda: (each(blocked), each(whole), each(in_xla) if kernels else each(blocked)))()
    assert float(got) == pytest.approx(float(want), rel=1e-5) and float(got) > 0.0
    assert float(got) == pytest.approx(float(xla), rel=1e-5)
    for a, c, x in zip(g_got[:3], g_want[:3], g_xla[:3]):
        np.testing.assert_allclose(a, c, atol=1e-6)
        np.testing.assert_allclose(a, x, atol=1e-6)
    assert not np.asarray(g_got[3]).any() and not np.asarray(g_got[4]).any()
    # every causal key selected, the log-sum-exp taken from the scores
    dense = sa.indexer_loss(q, k, None, q_i, k_i, w, None)
    assert np.isfinite(float(dense)) and float(dense) > 0.0

"""Flash attention kernels vs the XLA einsum reference path.

Runs in Pallas interpret mode on CPU; the same kernels compile with Mosaic on
TPU (lowering pinned by ``tests/test_tpu_lowering.py``, compiled parity by
``chip_smoke.py``). Oracle: ``_attention_xla`` (itself torch-parity-tested in
``tests/test_torch_parity.py``), forward and gradients, over the Perceiver
masking patterns — plain, right-aligned causal with q_len != kv_len
(Perceiver AR cross attention, reference ``modules.py:120-125``), and key
padding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.ops import flash_attention
from perceiver_io_tpu.ops.attention import _attention_xla, dot_product_attention


def _qkv(rng, b, h, i, j, d, dv=None):
    dv = dv or d
    q = jnp.asarray(rng.standard_normal((b, h, i, d)), jnp.float32) * d**-0.5
    k = jnp.asarray(rng.standard_normal((b, h, j, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, j, dv)), jnp.float32)
    return q, k, v


CASES = [
    # (i, j, causal, with_pad)
    (128, 128, False, False),
    (128, 384, False, True),
    (128, 128, True, False),
    (128, 384, True, False),   # AR cross attention: offset = 256
    (256, 640, True, True),
    (128, 896, True, False),   # several fully-skipped kv blocks
]


@pytest.mark.parametrize("i,j,causal,with_pad", CASES)
def test_forward_matches_xla(rng, i, j, causal, with_pad):
    q, k, v = _qkv(rng, 2, 3, i, j, 64)
    pad = None
    if with_pad:
        pad = jnp.asarray(rng.random((2, j)) < 0.2)
    expected = _attention_xla(q, k, v, pad, causal, 0.0, None)
    actual = flash_attention.flash_attention(q, k, v, pad_mask=pad, causal=causal)
    np.testing.assert_allclose(actual, expected, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("i,j,causal,with_pad", CASES)
def test_grads_match_xla(rng, i, j, causal, with_pad):
    q, k, v = _qkv(rng, 1, 2, i, j, 64)
    pad = None
    if with_pad:
        pad = jnp.asarray(rng.random((1, j)) < 0.2)
    cot = jnp.asarray(rng.standard_normal((1, 2, i, 64)), jnp.float32)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_xla(q, k, v, pad, causal, 0.0, None) * cot)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention.flash_attention(q, k, v, pad_mask=pad, causal=causal) * cot
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


def test_supported_gating():
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, 1, 1, 128, 256, 64)
    assert flash_attention.supported(q, k, v, causal=True)
    # non-tileable lengths fall back
    q2 = jnp.zeros((1, 1, 100, 64))
    assert not flash_attention.supported(q2, k, v, causal=False)
    # tiny head dim falls back
    q3, k3, v3 = _qkv(rng, 1, 1, 128, 128, 16)
    assert not flash_attention.supported(q3, k3, v3, causal=False)


def test_block_candidates_env_override(monkeypatch):
    monkeypatch.setenv("PERCEIVER_FLASH_BLOCKS", "1024,256")
    assert flash_attention._candidates() == (1024, 256)
    assert flash_attention._pick_block(512) == 256
    assert flash_attention._pick_block(2048) == 1024
    # invalid values are ignored in favor of the default
    monkeypatch.setenv("PERCEIVER_FLASH_BLOCKS", "100,abc")
    assert flash_attention._candidates() == flash_attention._BLOCK_CANDIDATES
    monkeypatch.delenv("PERCEIVER_FLASH_BLOCKS")
    assert flash_attention._pick_block(512) == 512


def test_min_kv_env_gates_auto_dispatch(rng, monkeypatch):
    from perceiver_io_tpu.ops import attention

    q, k, v = _qkv(rng, 1, 2, 128, 256, 64)
    monkeypatch.setenv("PERCEIVER_FLASH_MIN_KV", "512")
    assert not attention._flash_eligible(q, k, v, 0.0)  # kv 256 < floor 512
    monkeypatch.setenv("PERCEIVER_FLASH_MIN_KV", "256")
    # kv >= floor: eligibility now depends only on the platform gate
    assert attention._flash_eligible(q, k, v, 0.0) == (jax.default_backend() == "tpu")
    # explicit impl='flash' ignores the auto floor
    monkeypatch.setenv("PERCEIVER_FLASH_MIN_KV", "4096")
    out = dot_product_attention(q, k, v, causal=True, impl="flash")
    expected = dot_product_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(out, expected, atol=2e-5, rtol=2e-5)


def test_dispatch_impl_flash(rng):
    q, k, v = _qkv(rng, 1, 2, 128, 256, 64)
    out = dot_product_attention(q, k, v, causal=True, impl="flash")
    expected = dot_product_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(out, expected, atol=2e-5, rtol=2e-5)


def test_bf16_forward_close(rng):
    q, k, v = _qkv(rng, 1, 2, 128, 256, 64)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention.flash_attention(qb, kb, vb, causal=True).astype(jnp.float32)
    expected = _attention_xla(qb, kb, vb, None, True, 0.0, None).astype(jnp.float32)
    np.testing.assert_allclose(out, expected, atol=2e-2, rtol=2e-2)

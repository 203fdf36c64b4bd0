"""Ring attention (sequence parallelism) vs the unsharded einsum oracle,
on the 8-device virtual CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from perceiver_io_tpu.ops.attention import _attention_xla
from perceiver_io_tpu.parallel import ring_attention_sharded


@pytest.fixture(scope="module")
def seq_mesh():
    ds = np.asarray(jax.devices()).reshape(8)
    return Mesh(ds, ("seq",))


def _qkv(rng, b, h, i, j, d):
    q = jnp.asarray(rng.standard_normal((b, h, i, d)), jnp.float32) * d**-0.5
    k = jnp.asarray(rng.standard_normal((b, h, j, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, j, d)), jnp.float32)
    return q, k, v


CASES = [
    # (i, j, causal, with_pad). 2026-08 runtime audit: the ~10s right-
    # aligned/causal re-proofs keep `slow` depth; the cheap square + padded
    # cases stay tier-1 as the jax-API drift signal.
    (64, 64, False, False),
    pytest.param(64, 64, True, False, marks=pytest.mark.slow),
    pytest.param(64, 192, True, False, marks=pytest.mark.slow),
    (64, 192, False, True),
    pytest.param(64, 192, True, True, marks=pytest.mark.slow),
]


@pytest.mark.parametrize("i,j,causal,with_pad", CASES)
def test_matches_unsharded(rng, seq_mesh, i, j, causal, with_pad):
    q, k, v = _qkv(rng, 2, 2, i, j, 16)
    pad = jnp.asarray(rng.random((2, j)) < 0.2) if with_pad else None
    expected = _attention_xla(q, k, v, pad, causal, 0.0, None)
    actual = ring_attention_sharded(
        q, k, v, seq_mesh, pad_mask=pad, causal=causal
    )
    np.testing.assert_allclose(actual, expected, atol=1e-5, rtol=1e-5)


@pytest.mark.slow  # 2026-08 audit: 33s grad re-proof; forward parity stays tier-1
def test_grads_flow(rng, seq_mesh):
    q, k, v = _qkv(rng, 1, 2, 64, 192, 16)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, seq_mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_xla(q, k, v, None, True, 0.0, None) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5, err_msg=f"d{name}")


def test_rejects_indivisible(rng, seq_mesh):
    q, k, v = _qkv(rng, 1, 1, 60, 64, 16)
    with pytest.raises(ValueError):
        ring_attention_sharded(q, k, v, seq_mesh)


def test_jit_under_mesh(rng, seq_mesh):
    q, k, v = _qkv(rng, 1, 2, 64, 64, 16)
    f = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, seq_mesh, causal=True))
    np.testing.assert_allclose(
        f(q, k, v), _attention_xla(q, k, v, None, True, 0.0, None), atol=1e-5, rtol=1e-5
    )


@pytest.mark.slow  # 2026-08 audit: 17s; op-level parity + jit dispatch stay tier-1
def test_model_level_ring_dispatch(rng):
    """attention_impl='ring' reaches the model path (VERDICT r2 ask #9):
    a CLM forward under a seq-sharded mesh must match the xla impl."""
    from perceiver_io_tpu.models.text.clm import (
        CausalLanguageModel,
        CausalLanguageModelConfig,
    )
    from perceiver_io_tpu.parallel import MeshConfig, make_mesh

    cfg = dict(
        vocab_size=32, max_seq_len=32, max_latents=16, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    ring_model = CausalLanguageModel(
        CausalLanguageModelConfig(**cfg), attention_impl="ring"
    )
    xla_model = CausalLanguageModel(
        CausalLanguageModelConfig(**cfg), attention_impl="xla"
    )
    params = xla_model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 32), jnp.int32), 16
    )["params"]
    ids = jnp.asarray(rng.integers(1, 32, (2, 32)), jnp.int32)

    mesh = make_mesh(MeshConfig(seq=4))
    with jax.set_mesh(mesh):
        out_ring = ring_model.apply({"params": params}, ids, 16)
    out_xla = xla_model.apply({"params": params}, ids, 16)
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(out_xla), atol=2e-5, rtol=2e-5
    )


def test_ring_without_seq_mesh_falls_back_with_warning(rng):
    # e.g. model.init outside the mesh context — ring degrades to the
    # numerically identical einsum path and warns.
    from perceiver_io_tpu.ops.attention import _attention_xla, dot_product_attention

    q, k, v = _qkv(rng, 1, 2, 16, 16, 16)
    with pytest.warns(UserWarning, match="seq"):
        out = dot_product_attention(q, k, v, impl="ring", causal=True)
    np.testing.assert_allclose(
        out, _attention_xla(q, k, v, None, True, 0.0, None), atol=1e-6, rtol=1e-6
    )

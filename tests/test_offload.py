"""Activation checkpointing + host offload smoke (VERDICT r2 ask #10): the
``pinned_host`` remat policy (modules.py `_remat_policy`) must produce
finite grads, and offloading must not change them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.training.tasks import clm_loss_fn

VOCAB, SEQ, LATENTS = 32, 32, 16


def _grads(checkpointing: bool, offloading: bool):
    cfg = CausalLanguageModelConfig(
        vocab_size=VOCAB, max_seq_len=SEQ, max_latents=LATENTS, num_channels=16,
        num_heads=2, num_self_attention_layers=2, cross_attention_dropout=0.5,
        activation_checkpointing=checkpointing, activation_offloading=offloading,
    )
    model = CausalLanguageModel(config=cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, SEQ), jnp.int32), SEQ - LATENTS
    )["params"]
    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB, (2, SEQ + 1))
    batch = {"input_ids": jnp.asarray(ids[:, :-1]), "labels": jnp.asarray(ids[:, 1:])}
    loss_fn = clm_loss_fn(model, LATENTS)
    (loss, _), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, batch, jax.random.PRNGKey(1)
    )
    return float(loss), grads


@pytest.mark.slow  # 2026-08 audit: ~12s grad re-proof; remat equivalence stays tier-1
def test_offload_grads_finite_and_match_plain_remat():
    loss_p, grads_p = _grads(checkpointing=True, offloading=False)
    try:
        loss_o, grads_o = _grads(checkpointing=True, offloading=True)
    except Exception as e:  # pragma: no cover - backend-dependent support
        pytest.skip(f"host offload unsupported on this backend: {type(e).__name__}: {e}")

    assert np.isfinite(loss_o)
    for g in jax.tree_util.tree_leaves(grads_o):
        assert np.isfinite(np.asarray(g)).all()
    # offload only changes *where* residuals live, not the math
    np.testing.assert_allclose(loss_o, loss_p, rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(grads_p), jax.tree_util.tree_leaves(grads_o)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_offloading_policy_moves_the_layer_input_to_the_host_and_keeps_the_flash_residuals_on_the_device():
    """Traced, not run: with offloading each recomputed layer's backward takes
    its input from the host (``remat_layer_input``) and the flash forward's
    output ``(b, h, n, dv)`` and log-sum-exp ``(b, h, n, 128)`` from the
    device, by name, so the forward kernel is traced once a layer (in the two
    branches of its platform switch) as it is without recomputation."""
    import re

    from perceiver_io_tpu.models.core.modules import SelfAttentionBlock

    b, n, c, h = 2, 128, 128, 2
    x = jnp.zeros((b, n, c))

    def traced(**how):
        block = SelfAttentionBlock(num_layers=2, num_heads=h, num_channels=c, attention_impl="flash", **how)
        params = block.init(jax.random.PRNGKey(0), x)
        return str(jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(block.apply(p, x) ** 2)))(params, x))

    plain = traced()
    offload = traced(activation_checkpointing=True, activation_offloading=True)
    assert offload.count("name=flash_fwd") == plain.count("name=flash_fwd") == 4
    assert "<host>" not in plain
    assert set(re.findall(r"f32<host>\[([\d,]+)\]", offload)) == {f"{b},{n},{c}"}
    # what a layer's recomputation is handed: its input from the host, o and lse as the kernel wrote them
    handed = [line for line in offload.splitlines() if "lambda ;" in line and "<host>" in line]
    assert len(handed) == 2
    for line in handed:
        assert f":f32[{b},{h},{n},{c // h}]" in line and f":f32[{b},{h},{n},128]" in line

"""FLOPs/params estimators and profiling utilities."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.utils import (
    ComputeEstimator,
    StepTimer,
    count_params,
    num_training_steps,
    num_training_tokens,
    trace,
    training_flops,
)
from perceiver_io_tpu.utils.flops import flops_approx


def test_estimator_matches_reference_formulas():
    est = ComputeEstimator(vocab_size=262, max_seq_len=4096, num_latents=512)
    c = 512
    # reference per-component formulas (flops.py:62-87)
    assert est._input_embed(c) == 4 * c
    assert est._mlp_layer(c) == 16 * c * c
    assert est._self_attn_layer(c) == 6 * c * c + 2 * c * 512 + 2 * c * c
    assert est._cross_attn_layer(c) == 4 * c * c + 2 * c * 512
    assert est._final_logits(c) == 2 * c * 262
    # fwd+bwd = 3x forward
    assert est.self_attn(c, 9) % 3 == 0
    # halving prefix dropout raises cross-attention compute
    assert est.cross_attn(c, 0.0) > est.cross_attn(c, 0.5)


def test_token_helpers_inverse():
    tokens = num_training_tokens(num_steps=100, num_latents=512, batch_size=8)
    assert tokens == 100 * 512 * 8
    assert num_training_steps(tokens, 512, 8) == 100


def test_training_flops_scales_linearly():
    est = ComputeEstimator(262, 2048, 512)
    f1, t1 = training_flops(est, 512, 9, num_steps=10, batch_size=4)
    f2, t2 = training_flops(est, 512, 9, num_steps=20, batch_size=4)
    assert f2 == 2 * f1 and t2 == 2 * t1
    f0, _ = training_flops(est, 512, 9, num_steps=10, batch_size=4, prefix_dropout=0.0)
    assert f0 > f1  # the whole prefix costs more than half of it


def test_count_params_no_allocation():
    from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=64, max_latents=32, num_channels=32,
        num_heads=2, num_self_attention_layers=2,
    )
    model = CausalLanguageModel(cfg)
    n = count_params(model, jnp.zeros((1, 64), jnp.int32), 32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32), 32)["params"]
    actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == actual
    # C = 6N approximation is positive and param-proportional
    assert flops_approx(n) == 6 * n


def test_step_timer():
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((128, 128))
    result = StepTimer(warmup=1).measure(lambda: f(x), iters=3, flops_per_step=2 * 128**3,
                                         peak_flops=1e12)
    assert result["step_time_s"] > 0
    assert result["flops_per_sec"] > 0
    assert 0 < result["mfu"] < 1e6


def test_trace_writes_capture(tmp_path):
    log_dir = str(tmp_path / "profile")
    with trace(log_dir):
        jax.block_until_ready(jnp.ones((8, 8)) * 2)
    # a plugins/profile capture directory must exist and be non-empty
    found = []
    for root, _, files in os.walk(log_dir):
        found.extend(files)
    assert found, "profiler trace produced no files"

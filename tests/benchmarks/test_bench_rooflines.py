"""The benchmark's FLOP functions against XLA's own count
(``jit(...).lower().cost_analysis()``) of the plain references at a small
size. XLA computes masked score entries too and counts the elementwise work
(softmax, layer norm, GELU, rotary), so the comparison is with masked pairs
counted and a margin of 15%: the matrix products are the bulk."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.reference import blocks, perceiver_ar, perceiver_io_mlm
from benchmarks.rooflines import perceiver_ar as ar_work
from benchmarks.rooflines import perceiver_io_mlm as mlm_work
from benchmarks.rooflines import work

AR = {
    "vocab_size": 262, "max_seq_len": 512, "max_latents": 128, "num_channels": 128,
    "num_heads": 4, "num_self_attention_layers": 3, "self_attention_widening_factor": 4,
    "cross_attention_widening_factor": 4, "cross_attention_dropout": 0.0,
}
MLM = {
    "vocab_size": 262, "max_position_embeddings": 256, "d_model": 96, "d_latents": 160,
    "num_latents": 32, "num_blocks": 1, "num_self_attends_per_block": 3,
    "num_self_attention_heads": 4, "num_cross_attention_heads": 4, "qk_channels": 64,
    "v_channels": 160, "cross_attention_widening_factor": 1, "self_attention_widening_factor": 1,
}
MARGIN = 0.15


@pytest.fixture(autouse=True)
def unrolled_stacks(monkeypatch):
    """XLA counts the body of a scan once: count the layers as a loop."""
    monkeypatch.setattr(blocks, "UNROLL_STACKS", True)


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


def _cases():
    ids = jnp.zeros((2, 512), jnp.int32)
    p = jax.eval_shape(lambda: perceiver_ar.init_params(jax.random.PRNGKey(0), AR))
    yield "perceiver_ar", ar_work.train_step_work(AR, 2, 512), (
        lambda q, x: perceiver_ar.logits(q, AR, x, 512 - 128)), (p, ids)
    ids = jnp.zeros((2, 256), jnp.int32)
    p = jax.eval_shape(lambda: perceiver_io_mlm.init_params(jax.random.PRNGKey(0), MLM))
    yield "perceiver_io_mlm", mlm_work.train_step_work(MLM, 2, 256), (
        lambda q, x: perceiver_io_mlm.logits(q, MLM, x)), (p, ids)


@pytest.mark.parametrize("name,step_work,fn,args", list(_cases()), ids=lambda c: c if isinstance(c, str) else "")
def test_forward_flops_against_xla(name, step_work, fn, args):
    ours = work.forward_flops(step_work, count_masked=True)
    xla = _xla_flops(fn, *args)
    assert abs(ours - xla) / xla < MARGIN, (ours, xla)
    assert ours <= xla  # XLA adds the elementwise work; we never count more


@pytest.mark.parametrize("name,step_work,fn,args", list(_cases()), ids=lambda c: c if isinstance(c, str) else "")
def test_train_flops_against_xla(name, step_work, fn, args):
    ours = work.train_step_flops(step_work, count_masked=True)
    xla = _xla_flops(jax.grad(lambda q, x: fn(q, x).sum()), *args)
    assert abs(ours - xla) / xla < MARGIN, (ours, xla)


def test_required_work_leaves_out_masked_pairs_and_dropped_prefix():
    full = ar_work.train_step_work({**AR, "cross_attention_dropout": 0.0}, 2, 512)
    half = ar_work.train_step_work({**AR, "cross_attention_dropout": 0.5}, 2, 512)
    assert work.train_step_flops(half) < work.train_step_flops(full)
    assert work.train_step_flops(full) < work.train_step_flops(full, count_masked=True)
    a = dict(b=1, h=1, i=4, j=6, dk=8, dv=8, causal=True)
    assert work.attention_pairs(a) == 3 + 4 + 5 + 6 and work.attention_pairs(a, True) == 24


def test_flash_least_time_names_its_bound():
    peak = {"flops_per_s_bf16": 197e12, "bytes_per_s": 819e9}
    wide = [dict(b=8, h=8, i=1024, j=4096, dk=64, dv=64, causal=True)]
    thin = [dict(b=8, h=8, i=256, j=256, dk=32, dv=160, causal=False)]
    assert work.flash_least_time(wide, peak)["bound"] == "flops"
    assert work.flash_least_time(thin, peak)["bound"] == "bytes"
    fwd = work.flash_least_time(wide, peak, training=False)["seconds"]
    assert 0 < fwd < work.flash_least_time(wide, peak)["seconds"]

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


def test_pick_block_takes_the_largest_candidate_that_divides():
    assert flash_attention._BLOCK_CANDIDATES == (512, 256, 128)
    assert flash_attention._pick_block(512) == 512
    assert flash_attention._pick_block(1024) == 512
    assert flash_attention._pick_block(768) == 256
    assert flash_attention._pick_block(384) == 128
    assert flash_attention._pick_block(100) is None


def test_auto_dispatch_depends_on_dropout_and_backend_alone(rng, monkeypatch):
    from perceiver_io_tpu.ops import attention

    assert attention._flash_eligible(0.0) == (jax.default_backend() == "tpu")
    assert not attention._flash_eligible(0.1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._flash_eligible(0.0) and not attention._flash_eligible(0.1)

    # 'auto' on a TPU takes the kernel at any supported length (256 keys here)
    # and the einsum path under attention dropout; 'flash' equals 'xla'
    calls = []
    monkeypatch.setattr(
        attention, "_flash_over_mesh",
        lambda *a: calls.append(a[0].shape) or attention._attention_xla(*a[:5], 0.0, None, window=a[5]),
    )
    q, k, v = _qkv(rng, 1, 2, 128, 256, 64)
    dot_product_attention(q, k, v, causal=True, impl="auto")
    assert calls == [q.shape]
    dot_product_attention(
        q, k, v, causal=True, impl="auto", dropout_rate=0.1, dropout_rng=jax.random.PRNGKey(0)
    )
    assert calls == [q.shape]
    monkeypatch.undo()
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


# h, hk, i, j, d, dv, causal, pad lengths by batch row (keys padded on the left)
FUSED_CASES = {
    # three query blocks on five key blocks of 128, offset 256: blocks above the diagonal are skipped
    "causal_right_aligned": (2, 2, 384, 640, 64, 64, True, None),
    # key block 0 wholly padded in row 0; its query rows 0..4 see nothing but padding (dead)
    "pad_block_and_dead_row": (2, 2, 256, 384, 64, 64, True, (133, 7)),
    "grouped_heads": (4, 2, 256, 384, 64, 64, True, (0, 40)),
    "mlm_head_widths": (2, 2, 256, 512, 32, 160, False, (0, 130)),
}


def _grads(fn, q, k, v, cot):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_backward_matches_two_call_and_einsum(rng, monkeypatch, case, dtype):
    h, hk, i, j, d, dv, causal, lengths = FUSED_CASES[case]
    b = 2
    q = (jnp.asarray(rng.standard_normal((b, h, i, d)), jnp.float32) * d**-0.5).astype(dtype)
    k = jnp.asarray(rng.standard_normal((b, hk, j, d)), jnp.float32).astype(dtype)
    v = jnp.asarray(rng.standard_normal((b, hk, j, dv)), jnp.float32).astype(dtype)
    cot = jnp.asarray(rng.standard_normal((b, h, i, dv)), jnp.float32)
    pad = None if lengths is None else jnp.asarray(np.arange(j)[None, :] < np.asarray(lengths)[:, None])
    flash = lambda q, k, v: flash_attention.flash_attention(q, k, v, pad_mask=pad, causal=causal)

    assert flash_attention._resident_heads(q, k) == h // hk
    fused = _grads(flash, q, k, v, cot)
    monkeypatch.setattr(flash_attention, "_DQ_VMEM_BUDGET_BYTES", 0)
    two_call = _grads(flash, q, k, v, cot)
    for a, e, name in zip(fused, two_call, "qkv"):  # the same sums in the same order
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(e, np.float32), err_msg=f"d{name}")

    # the einsum path leaks through dead rows (flash_attention's docstring); a loss masks them
    if pad is not None:
        visible = ~pad[:, None, None, :]
        if causal:
            visible = visible & (jnp.arange(j)[None, :] <= jnp.arange(i)[:, None] + (j - i))
        alive = jnp.any(visible, axis=-1)  # (b, 1, i)
        if case == "pad_block_and_dead_row":
            assert not bool(alive[0, 0, 4]) and bool(alive[0, 0, 5]) and bool(alive[1].all())
            assert not np.asarray(fused[0], np.float32)[0, :, :5].any()  # dead rows: zero dQ
        cot = cot * alive[..., None]
        fused = _grads(flash, q, k, v, cot)
    einsum = _grads(
        lambda q, k, v: dot_product_attention(q, k, v, pad_mask=pad, causal=causal, impl="xla"),
        q, k, v, cot,
    )
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    for a, e, name in zip(fused, einsum, "qkv"):
        a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
        np.testing.assert_allclose(a, e, atol=tol * max(1.0, np.abs(e).max()), rtol=tol, err_msg=f"d{name}")


def _traced_backward(h, hk, i, d=64):
    """The jaxpr of a flash forward and backward at ``i`` query rows, traced and not run."""
    q = jax.ShapeDtypeStruct((1, h, i, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, hk, 128, d), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(flash_attention.flash_attention(q, k, v).astype(jnp.float32))
    return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv))


def test_backward_is_one_kernel_while_dq_fits_the_budget():
    from perceiver_io_tpu.observability import default_registry

    counters = ("flash_backward_two_call_total", "flash_backward_sliced_total")
    budget = flash_attention._DQ_VMEM_BUDGET_BYTES
    rows = budget // (flash_attention.LANES * 4)  # 64-wide heads take whole lanes all the same

    def kernels(h, hk, i):
        before = [default_registry().counter(c) for c in counters]
        text = _traced_backward(h, hk, i)
        names = {n for n in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv") if f"name={n}" in text}
        return (names, *(default_registry().counter(c) - b for c, b in zip(counters, before)))

    one, two = {"flash_fwd", "flash_bwd_dkv"}, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert kernels(1, 1, rows) == (one, 0, 0)
    assert kernels(4, 1, rows // 4) == (one, 0, 0)  # the group's heads share it
    assert kernels(1, 1, rows + 128) == (two, 1, 0)  # one head alone is over it
    assert kernels(4, 1, rows // 4 + 128) == (one, 0, 1)  # the group in two slices of two heads
    assert kernels(4, 4, rows // 4 + 128) == (one, 0, 0)
    assert kernels(7, 1, rows // 4) == (one, 0, 1)  # a prime group: a head a slice
    assert kernels(4, 1, rows + 128) == (two, 1, 0)


MiB = 1 << 20
# (h, hk, i, d) -> the query heads of a key-value head whose dQ stays resident together
RESIDENT_HEADS = {
    "ar8k-train cross-attention": ((8, 8, 1024, 64), 1),  # 0.5 MiB
    "mlm201m-train decoder": ((8, 8, 2048, 32), 1),  # 1 MiB at 128 lanes
    "lfm2moe-train-8k": ((32, 8, 8192, 64), 4),  # the whole group, exactly 16 MiB
    "glm47flash-train-8k": ((20, 20, 8192, 256), 1),  # 8 MiB
    "smallthinker-train-16k": ((28, 4, 16384, 128), 1),  # a group of 7 is 56 MiB, a head 8
    "group exactly at the budget": ((8, 1, 4096, 128), 8),
    "one row block over it, a divisor fits": ((8, 1, 4096 + 512, 128), 4),
    "one row block over it, group of 6": ((6, 1, 5632, 128), 3),  # 16.5 MiB whole, 8.25 by 3
    "a prime group over it": ((7, 1, 8192, 128), 1),
    "a prime group that fits": ((7, 1, 4096, 128), 7),  # 14 MiB
    "a head exactly at the budget": ((4, 2, 16384, 256), 1),
    "a head that alone does not fit": ((4, 2, 16384 + 512, 256), 0),
    "a wide head that does not fit": ((2, 2, 16384, 512), 0),
}


@pytest.mark.parametrize("case", sorted(RESIDENT_HEADS))
def test_resident_heads_is_the_largest_divisor_of_the_group_within_16_mib(case):
    """The rule of ``_flash_bwd``, from the shapes alone: the five cells' and
    the edges. What it chooses decides the kernels and both counters."""
    import perceiver_io_tpu.observability as observability

    (h, hk, i, d), expected = RESIDENT_HEADS[case]
    assert flash_attention._DQ_VMEM_BUDGET_BYTES == 16 * MiB
    q = jax.ShapeDtypeStruct((1, h, i, d), jnp.bfloat16)
    assert flash_attention._resident_heads(q, jax.ShapeDtypeStruct((1, hk, 128, d), jnp.bfloat16)) == expected

    registry, group = observability.MetricsRegistry(), h // hk
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(observability, "default_registry", lambda: registry)
        text = _traced_backward(h, hk, i, d)
    assert ("name=flash_bwd_dq" in text) == (expected == 0)
    assert registry.counters()["flash_backward_two_call_total"] == float(expected == 0)
    assert registry.counters()["flash_backward_sliced_total"] == float(0 < expected < group)
    if 0 < expected < group:  # each slice's dK and dV in float32, group / g slices a key-value head
        assert f"f32[1,{hk * group // expected},128,{d}]" in text


def test_fused_vmem_limit_stays_32_mib_under_2_mib_of_dq_and_follows_the_shape_above():
    """What the one-kernel backward asks of VMEM: the limit every such kernel
    had while its dQ was at most 2 MiB, so those kernels are the programs they
    were; above, the blocks and the resident dQ three times over (bfloat16
    output: twice), over what Mosaic was found to need (the comment at
    ``_DQ_VMEM_BUDGET_BYTES``) and under the v5e's 128 MiB."""
    limit = flash_attention._fused_vmem_limit
    for d, dv, rows, itemsize in [(64, 64, 1024, 2), (32, 160, 2048, 2), (32, 96, 2048, 2), (64, 64, 4096, 4),
                                  (256, 256, 1024, 4), (512, 512, 1024, 4)]:
        assert rows * max(d, 128) * 4 <= 2 * MiB
        assert limit(512, 512, d, dv, rows, itemsize, itemsize) == 32 * MiB
    # (bi, bj, d, dv, rows, itemsize, dK/dV's itemsize), the least limit Mosaic compiled it with for a v5e, in MiB
    needs = {
        (512, 512, 64, 64, 4 * 8192, 2, 2): 35.8,    # lfm2moe-train-8k
        (512, 512, 256, 256, 8192, 2, 2): 23.5,      # glm47flash-train-8k
        (512, 512, 128, 128, 16384, 2, 4): 21.0,     # smallthinker-train-16k, a head a slice
        (512, 512, 256, 256, 16384, 4, 4): 57.7,     # float32 at the budget
        (512, 512, 512, 512, 8192, 4, 4): 65.2,
    }
    for shape, least in needs.items():
        assert least * MiB < limit(*shape) <= 72 * MiB, shape


SLICED_CASES = {
    # (h, hk, i, j, d, window, padded keys); blocks of 128
    "group_of_3_causal": (6, 2, 256, 384, 32, None, 0),
    "group_of_3_padded_key_block": (3, 1, 256, 384, 32, None, 133),  # key block 0 wholly padded
    "group_of_7_causal": (7, 1, 256, 256, 32, None, 0),
    "group_of_7_banded": (14, 2, 384, 384, 32, 129, 0),
    "group_of_6_by_threes_banded_padded": (6, 1, 256, 384, 32, 200, 5),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SLICED_CASES))
def test_sliced_backward_matches_two_kernels_and_einsum(rng, monkeypatch, case, dtype):
    """A group whose dQ is over the budget whole, walked in slices by the one
    kernel: dQ bit for bit the two kernels' (the same sums in the same order),
    dK and dV the float32 sums of the slices' parts, so within float32
    summation order of theirs before the one rounding; and all three against
    autodiff of the einsum path."""
    h, hk, i, j, d, window, padded = SLICED_CASES[case]
    b, group = 2, h // hk
    q = (jnp.asarray(rng.standard_normal((b, h, i, d)), jnp.float32) * d**-0.5).astype(dtype)
    k = jnp.asarray(rng.standard_normal((b, hk, j, d)), jnp.float32).astype(dtype)
    v = jnp.asarray(rng.standard_normal((b, hk, j, d)), jnp.float32).astype(dtype)
    cot = jnp.asarray(rng.standard_normal((b, h, i, d)), jnp.float32)
    pad = None
    if padded:  # left padding; rows that see padding alone are dead: left out, as above
        pad = jnp.zeros((b, j), bool).at[:, :padded].set(True)
        cot = cot.at[:, :, :max(0, padded - (j - i))].set(0.0)
    flash = lambda q, k, v: flash_attention.flash_attention(q, k, v, pad_mask=pad, causal=True, window=window)
    head = i * flash_attention.LANES * 4
    by = 3 if case.startswith("group_of_6") else 1

    monkeypatch.setattr(flash_attention, "_DQ_VMEM_BUDGET_BYTES", by * head)
    assert flash_attention._resident_heads(q, k) == by < group
    sliced = _grads(flash, q, k, v, cot)
    monkeypatch.setattr(flash_attention, "_DQ_VMEM_BUDGET_BYTES", 0)
    two_call = _grads(flash, q, k, v, cot)
    np.testing.assert_array_equal(np.asarray(sliced[0], np.float32), np.asarray(two_call[0], np.float32), err_msg="dq")
    # one rounding of a float32 sum in another order: an ulp of the dtype
    ulp = 2e-6 if dtype == jnp.float32 else 2.0 ** -7
    for a, e, name in zip(sliced[1:], two_call[1:], "kv"):
        a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
        np.testing.assert_allclose(a, e, atol=ulp * np.abs(e).max(), rtol=ulp, err_msg=f"d{name}")

    einsum = _grads(
        lambda q, k, v: dot_product_attention(q, k, v, pad_mask=pad, causal=True, window=window, impl="xla"),
        q, k, v, cot)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    for a, e, name in zip(sliced, einsum, "qkv"):
        a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
        np.testing.assert_allclose(a, e, atol=tol * max(1.0, np.abs(e).max()), rtol=tol, err_msg=f"d{name}")


def _benchmark_reader(monkeypatch, metric):
    """``benchmarks/metrics/<metric>.py``'s ``read``, the repository's root on the path."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmarks import harness

    return harness.load_reader(os.path.join(root, "benchmarks"), metric)


def test_two_call_counter_has_help_text_and_a_benchmark_reader(monkeypatch):
    """``benchmarks/metrics/flash_bwd_two_call_shapes.py``: nothing from a
    program without the counter, 0 once a fused backward was traced, then the
    count of two-call ones."""
    import perceiver_io_tpu.observability as observability
    from perceiver_io_tpu.observability.exporters import HELP_TEXT

    registry = observability.MetricsRegistry()
    monkeypatch.setattr(observability, "default_registry", lambda: registry)
    read = _benchmark_reader(monkeypatch, "flash_bwd_two_call_shapes")
    assert "flash_backward_two_call_total" in HELP_TEXT
    assert read({}) is None  # the parent's program never declares it
    _traced_backward(1, 1, 1024)
    assert read({}) == 0.0
    _traced_backward(1, 1, 8192)  # 4 MiB: one kernel since the budget is 16 MiB
    assert read({}) == 0.0
    _traced_backward(1, 1, 32768 + 512)
    assert read({}) == 1.0
    assert "flash_backward_sliced_total" in HELP_TEXT


def _operator(kind):
    """``(module class, its fields, its call's arguments after the parameters)``
    at batch 4, 256 positions, bfloat16, on the kernel path."""
    from perceiver_io_tpu.models.core.modules import LatentAttention, MultiHeadAttention

    b, n = 4, 256
    if kind == "grouped_heads":  # 8 query heads on 2
        x = jnp.zeros((b, n, 512), jnp.bfloat16)
        fields = dict(num_heads=8, num_q_input_channels=512, num_kv_input_channels=512,
                      causal_attention=True, qkv_bias=False, out_bias=False, num_kv_heads=2, qk_norm=True)
        return MultiHeadAttention, fields, (x, x)
    fields = dict(num_heads=4, num_input_channels=128, q_lora_rank=48, kv_lora_rank=32,
                  qk_nope_head_dim=48, qk_rope_head_dim=16, v_head_dim=64)
    return LatentAttention, fields, (jnp.zeros((b, n, 128), jnp.bfloat16), None, None)


def _recomputed_layers_kernels(kind, policy, mesh=None):
    """The Mosaic kernels, by name, in the TPU lowering of the gradient of one
    recomputed layer (``nn.remat`` with ``policy`` around the operator) whose
    output something after it reads, as the next layer does."""
    import re
    from contextlib import nullcontext

    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    cls, fields, args = _operator(kind)
    layer = nn.remat(cls, policy=policy)(**fields, dtype=jnp.bfloat16, attention_impl="flash")
    params = layer.init(jax.random.PRNGKey(0), *(a if a is None else a[:1] for a in args))

    def loss(p, x):
        rest = tuple(a if a is None else x for a in args[1:])
        ambient = nullcontext() if mesh is None else jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
        with ambient:
            return jnp.sum(layer.apply(p, x, *rest).astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct(args[0].shape, args[0].dtype,
                             sharding=None if mesh is None else NamedSharding(mesh, P("data")))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text()
    if mesh is not None:
        assert "sdy.manual_computation" in text  # the calls stand in shard_map
    return sorted(re.findall(r'kernel_name = "([^"]*)"', text))


@pytest.mark.parametrize("over", ["one_device", "data2_model2"])
@pytest.mark.parametrize("kind", ["grouped_heads", "latent_attention"])
def test_recomputed_layer_runs_the_flash_forward_once(devices, kind, over):
    """``_remat_policy`` keeps the forward's output and log-sum-exp by name
    (``flash_attention.SAVED_NAMES``), so the backward's re-run of the layer
    holds no ``flash_fwd``; a policy that keeps nothing holds it a second
    time. The same under a mesh of four devices, where the call stands in
    ``shard_map``, and with the offloading policy."""
    from perceiver_io_tpu.models.core.modules import _remat_policy
    from perceiver_io_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = None if over == "one_device" else make_mesh(MeshConfig(data=2, model=2), devices=devices[:4])
    once = ["flash_bwd_dkv", "flash_fwd"]
    assert _recomputed_layers_kernels(kind, None, mesh) == once + ["flash_fwd"]
    assert _recomputed_layers_kernels(kind, _remat_policy(offload=False), mesh) == once
    assert _recomputed_layers_kernels(kind, _remat_policy(offload=True), mesh) == once


def test_forward_runs_per_step_reader_counts_the_forward_kernels_events_alone(monkeypatch):
    """``benchmarks/metrics/flash_fwd_runs_per_step.py``: 12 ``flash_fwd``
    events over 2 steps on one device read 6.0 (the backward's kernels and
    XLA's ``ragged-dot-*`` are not counted), over two devices the same a
    device, and a run without a trace reads nothing."""
    read = _benchmark_reader(monkeypatch, "flash_fwd_runs_per_step")
    from benchmarks.trace_reduce import DeviceTrace, Trace

    call = '= (bf16[1,20,8192,256]) custom-call(...), custom_call_target="tpu_custom_call"'

    def device(name):
        kernels = ["%flash_fwd", "%flash_fwd.1", "%flash_fwd.12", "%flash_fwd.3.remat",
                   "%flash_fwd.4", "%flash_fwd.5", "%flash_bwd_dkv.1", "%flash_bwd_dq.2",
                   "%ragged-dot-none.7", "%ragged-dot-metadata"]
        ops = [(f"{k} {call}", 0.001 * t, 0.0005) for t, k in enumerate(kernels * 2)]
        ops.append(("%fusion.9 = bf16[8] fusion(%flash_fwd.1)", 0.5, 0.001))
        return DeviceTrace(name, ops=ops, modules=[("jit_step(123)", 0.0, 0.2), ("jit_step(123)", 0.2, 0.2),
                                                   ("jit_other(7)", 0.4, 0.1)])

    ctx = lambda trace: {"trace": trace, "mix": {"trace": {"step_module": "jit_step"}}}
    assert read(ctx(Trace([device("/device:TPU:0")], [], 0.0))) == 6.0
    assert read(ctx(Trace([device("/device:TPU:0"), device("/device:TPU:1")], [], 0.0))) == 6.0
    assert read(ctx(None)) is None
    assert read(ctx(Trace([], [], 0.0))) is None  # the CPU has no device plane


# -- sliding window ----------------------------------------------------------
def _band_from_positions(i, j, window):
    """Key ``s`` is allowed for query ``t`` iff ``0 <= t + (j - i) - s < window``."""
    back = np.arange(i)[:, None] + (j - i) - np.arange(j)[None, :]
    return (back >= 0) & (back < window)


def _masked_reference(q, k, v, allowed, pad=None):
    """Softmax attention under an explicit ``(i, j)`` mask, grouped heads by
    repeating the key-value heads: nothing of the paths under test."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    allowed = jnp.asarray(allowed)[None, None]
    if pad is not None:
        allowed = allowed & ~pad[:, None, None, :]
    s = jnp.where(allowed, jnp.einsum("bhid,bhjd->bhij", q, k), -1e30)
    return jnp.einsum("bhij,bhjd->bhid", jax.nn.softmax(s, axis=-1), v)


WINDOW_CASES = {
    # (h, hk, i, j, window, padded keys): blocks are 128 wide unless a dim is 256 or 512
    "smaller_than_a_block": (2, 2, 256, 256, 40, 0),
    "equal_to_a_block": (2, 2, 384, 384, 128, 0),
    "larger_than_a_block": (2, 2, 384, 384, 200, 0),
    "larger_than_the_row": (2, 2, 256, 256, 1000, 0),
    "right_aligned_unequal": (2, 1, 128, 384, 50, 0),  # keys that no query sees
    "right_aligned_two_blocks": (2, 2, 256, 640, 300, 0),
    "with_padding": (4, 2, 256, 256, 130, 5),
    "group_of_7": (14, 2, 384, 384, 129, 0),
    "one_key": (2, 2, 256, 256, 1, 0),
}


@pytest.mark.parametrize("backward", ["one_kernel", "two_kernels"])
@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_window_forward_and_both_backward_forms_match_einsum_and_a_mask_from_positions(
        rng, monkeypatch, case, backward):
    """The windowed kernels (forward, the fused backward and the two-kernel
    backward, each on a grid over the band) against the einsum path with the
    same window and against a mask built from positions."""
    h, hk, i, j, window, padded = WINDOW_CASES[case]
    d = 32
    q = jnp.asarray(rng.standard_normal((2, h, i, d)), jnp.float32) * d**-0.5
    k = jnp.asarray(rng.standard_normal((2, hk, j, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, hk, j, d)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((2, h, i, d)), jnp.float32)
    pad = None
    if padded:
        # left padding; a query whose whole window is padding is a dead row
        # (zero here, uniform on the einsum path): left out of the comparison
        pad = jnp.zeros((2, j), bool).at[:, :padded].set(True)
        cot = cot.at[:, :, :max(0, padded - (j - i))].set(0.0)
    monkeypatch.setattr(
        flash_attention, "_DQ_VMEM_BUDGET_BYTES", 1 << 40 if backward == "one_kernel" else 0)
    paths = {
        "flash": lambda q, k, v: flash_attention.flash_attention(
            q, k, v, pad_mask=pad, causal=True, window=window),
        "einsum": lambda q, k, v: dot_product_attention(
            q, k, v, pad_mask=pad, causal=True, window=window, impl="xla"),
        "mask": lambda q, k, v: _masked_reference(q, k, v, _band_from_positions(i, j, window), pad),
    }
    found = {name: jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * cot), (0, 1, 2))(q, k, v)
             for name, fn in paths.items()}
    for name in ("flash", "einsum"):
        np.testing.assert_allclose(found[name][0], found["mask"][0], rtol=1e-4, err_msg=name)
        for a, b, leaf in zip(found[name][1], found["mask"][1], "qkv"):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=f"{name} d{leaf}")


def test_a_window_that_holds_every_key_is_the_causal_call(rng):
    q, k, v = _qkv(rng, 1, 2, 256, 384, 32)
    wide = flash_attention.flash_attention(q, k, v, causal=True, window=384)
    np.testing.assert_allclose(wide, flash_attention.flash_attention(q, k, v, causal=True), atol=1e-6)


@pytest.mark.parametrize("i,j,window,kv_blocks,q_blocks", [
    (2048, 2048, 512, 2, 2),    # blocks of 512: a q block's band touches its own kv block and the one before
    (2048, 2048, 513, 2, 2),  # its first key is the first of the block before
    (2048, 2048, 514, 3, 3),
    (16384, 16384, 4096, 9, 9),  # the cell's: 288 grid steps a head where the full grid has 1,024
    (1024, 4608, 700, 3, 2),    # right-aligned: blocks 512 over 512
    (256, 256, 1, 1, 1),
])
def test_the_band_grid_counts_the_blocks_a_band_touches(i, j, window, kv_blocks, q_blocks):
    """``_Band``: every block pair that holds an allowed query-key pair lies in
    the walked grid, and the grid's inner dimension is the most any block of
    the other side meets."""
    bi, bj = flash_attention._pick_block(i), flash_attention._pick_block(j)
    band = flash_attention._Band(bi, bj, i // bi, j // bj, j - i, window)
    assert (band.kv_blocks, band.q_blocks) == (kv_blocks, q_blocks)
    rows, cols = np.arange(i)[:, None] + (j - i), np.arange(j)[None, :]
    allowed = (cols <= rows) & (cols > rows - window)
    pairs = allowed.reshape(i // bi, bi, j // bj, bj).any(axis=(1, 3))
    for a in range(i // bi):
        meets = np.flatnonzero(pairs[a])
        assert (band.first_j(a), band.last_j(a)) == (meets[0], meets[-1])
    for b in range(j // bj):
        meets = np.flatnonzero(pairs[:, b])
        if len(meets):
            assert band.first_i(b) == meets[0] and min(band.last_i(b), i // bi - 1) == meets[-1]
        else:
            assert band.last_i(b) < band.first_i(b) or band.last_i(b) < 0


def test_window_needs_causal_and_is_refused_elsewhere(rng):
    q, k, v = _qkv(rng, 1, 2, 128, 128, 32)
    assert flash_attention.supported(q, k, v, causal=True, window=16)
    assert not flash_attention.supported(q, k, v, causal=False, window=16)
    assert not flash_attention.supported(q, k, v, causal=True, window=0)
    for call in (flash_attention.flash_attention, dot_product_attention):
        with pytest.raises(ValueError, match="window"):
            call(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="sliding window"):
        dot_product_attention(q, k, v, causal=True, window=16, impl="ring")


def test_window_counter_counts_traced_window_calls_and_is_declared_by_any_call(monkeypatch):
    import perceiver_io_tpu.observability as observability
    from perceiver_io_tpu.observability import MetricsRegistry

    registry = MetricsRegistry()
    monkeypatch.setattr(observability, "default_registry", lambda: registry)
    q = jnp.zeros((1, 2, 128, 32))
    assert "flash_window_call_total" not in registry.counters()
    jax.eval_shape(lambda: flash_attention.flash_attention(q, q, q, causal=True))
    assert registry.counters()["flash_window_call_total"] == 0.0
    jax.eval_shape(lambda: dot_product_attention(q, q, q, causal=True, window=64, impl="flash"))
    assert registry.counters()["flash_window_call_total"] == 1.0
    # the einsum path carries the window too, and is no kernel call
    jax.eval_shape(lambda: dot_product_attention(q, q, q, causal=True, window=64, impl="xla"))
    assert registry.counters()["flash_window_call_total"] == 1.0
    from perceiver_io_tpu.observability.exporters import HELP_TEXT

    assert "flash_window_call_total" in HELP_TEXT


# -- the forward's lane-dense running max and sum, at 512-row blocks ----------
WIDE_BLOCK_CASES = {
    # (h, hk, i, j, d, dv, causal, window, padded keys): q blocks of 512 rows
    "causal_two_q_blocks_offset": (1, 1, 1024, 1536, 64, 64, True, None, 0),
    "window_scaled_down": (1, 1, 1536, 1536, 128, 128, True, 600, 0),  # as 4096 of 16,384: 3 kv blocks a band
    "pad_and_dead_rows": (1, 1, 512, 1024, 64, 64, True, None, 600),  # rows 0..87 see padding alone
    "grouped_heads_128": (2, 1, 512, 1024, 128, 128, True, None, 0),
    "wide_256": (1, 1, 512, 1024, 256, 256, True, None, 0),
    "decoder_widths": (1, 1, 1024, 256, 32, 96, False, None, 0),
}


def _wide_block_inputs(rng, case):
    h, hk, i, j, d, dv, causal, window, padded = WIDE_BLOCK_CASES[case]
    q = jnp.asarray(rng.standard_normal((1, h, i, d)), jnp.float32) * d**-0.5
    k = jnp.asarray(rng.standard_normal((1, hk, j, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, hk, j, dv)), jnp.float32)
    pad = jnp.zeros((1, j), bool).at[:, :padded].set(True) if padded else None
    # a dead row (its whole window is padding) is zero here and uniform on the einsum path
    alive = np.arange(i) + (j - i) >= padded
    return q, k, v, pad, causal, window, alive


@pytest.mark.parametrize("case", sorted(WIDE_BLOCK_CASES))
def test_forward_at_512_row_blocks_matches_einsum(rng, case):
    """The forward's output at q blocks of 512 rows, with the running max and
    sum kept lane-dense, against the einsum path at the file's tolerances."""
    q, k, v, pad, causal, window, alive = _wide_block_inputs(rng, case)
    out = flash_attention.flash_attention(q, k, v, pad_mask=pad, causal=causal, window=window)
    expected = dot_product_attention(q, k, v, pad_mask=pad, causal=causal, window=window, impl="xla")
    np.testing.assert_allclose(np.asarray(out)[:, :, alive], np.asarray(expected)[:, :, alive], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", sorted(WIDE_BLOCK_CASES))
def test_forward_lse_at_512_row_blocks_is_each_rows_logsumexp(rng, case):
    """The log-sum-exp the backward reads, every one of its 128 lanes: each
    live row's log-sum-exp over the keys it sees, from the scores in float64."""
    q, k, v, pad, causal, window, alive = _wide_block_inputs(rng, case)
    pad3 = None if pad is None else pad.astype(jnp.float32)[:, None, :]
    _, lse = flash_attention._forward(q, k, v, pad3, causal, window)
    lse = np.asarray(lse)
    assert lse.shape == (*q.shape[:3], flash_attention.LANES)
    np.testing.assert_array_equal(lse, np.broadcast_to(lse[..., :1], lse.shape))
    i, j = q.shape[2], k.shape[2]
    kk = np.repeat(np.asarray(k, np.float64), q.shape[1] // k.shape[1], axis=1)
    s = np.einsum("bhid,bhjd->bhij", np.asarray(q, np.float64), kk)
    rows, cols = np.arange(i)[:, None] + (j - i), np.arange(j)[None, :]
    seen = np.ones((i, j), bool) if not causal else cols <= rows
    if window is not None:
        seen &= cols > rows - window
    if pad is not None:
        seen &= ~np.asarray(pad)[0][None, :]
    s = np.where(seen, s, -np.inf)[:, :, alive]
    top = s.max(axis=-1, keepdims=True)
    expected = (top + np.log(np.exp(s - top).sum(axis=-1, keepdims=True)))[..., 0]
    np.testing.assert_allclose(lse[:, :, alive, 0], expected, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", ["causal_two_q_blocks_offset", "window_scaled_down", "pad_and_dead_rows"])
def test_gradients_through_the_wide_block_forward_match(rng, case):
    """The backward reads the forward's log-sum-exp and output: at q blocks of
    512 rows its gradients match the einsum path's at the file's tolerances."""
    q, k, v, pad, causal, window, alive = _wide_block_inputs(rng, case)
    cot = jnp.asarray(rng.standard_normal((*q.shape[:3], v.shape[3])), jnp.float32) * alive[:, None]
    flash = _grads(lambda q, k, v: flash_attention.flash_attention(
        q, k, v, pad_mask=pad, causal=causal, window=window), q, k, v, cot)
    einsum = _grads(lambda q, k, v: dot_product_attention(
        q, k, v, pad_mask=pad, causal=causal, window=window, impl="xla"), q, k, v, cot)
    for a, e, name in zip(flash, einsum, "qkv"):
        a, e = np.asarray(a), np.asarray(e)
        np.testing.assert_allclose(a, e, atol=1e-4 * max(1.0, np.abs(e).max()), rtol=1e-4, err_msg=f"d{name}")

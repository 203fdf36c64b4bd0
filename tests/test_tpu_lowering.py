"""Lowering for the ``tpu`` platform on the CPU: what Mosaic and the SPMD
partitioner refuse, they refuse while lowering, so these catch it without a
chip. Lowering only (no libtpu compile), small shapes.

The kernels choose compiled-or-interpreted from the *lowering* platform
(``flash_attention.pallas_call_on_lowering_platform``), which is what makes
this test anything: lowered for ``tpu`` the program must hold
``tpu_custom_call``, lowered for the CPU it must not. The two refusals this pins were both live
before the chip bring-up: a pad-mask BlockSpec that broke the (8, 128) tiling
rule at batch > 1, and a Mosaic kernel under a multi-device mesh without
``shard_map``. Numerical parity of the compiled kernels is ``chip_smoke.py``'s.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.ops import flash_attention
from perceiver_io_tpu.ops import paged_attention as paged
from perceiver_io_tpu.ops.attention import dot_product_attention
from perceiver_io_tpu.ops.ragged_attention import ragged_paged_attention
from perceiver_io_tpu.parallel import (
    MeshConfig,
    create_train_state,
    make_mesh,
    make_train_step,
    shard_batch,
)
from perceiver_io_tpu.training.tasks import clm_loss_fn

pytestmark = pytest.mark.timeout(120)


def _mosaic_calls(fn, *args, platform="tpu"):
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=(platform,))
    return lowered.as_text().count("tpu_custom_call")


@pytest.mark.parametrize(
    "causal,pad",
    [(True, False), (True, True), (False, True)],
    ids=["causal", "causal_pad_b2", "noncausal_pad_b2"],
)
def test_flash_forward_and_backward_lower_for_tpu(causal, pad):
    b, h, i, j, d = 2, 2, 128, 256, 64
    q = jnp.zeros((b, h, i, d), jnp.bfloat16)
    k = v = jnp.zeros((b, h, j, d), jnp.bfloat16)
    pad_mask = jnp.zeros((b, j), bool) if pad else None

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention.flash_attention(
                    q, k, v, pad_mask=pad_mask, causal=causal
                ).astype(jnp.float32)
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    assert _mosaic_calls(fwd_bwd, q, k, v) == 2  # forward, and one backward: dQ fits VMEM here
    assert _mosaic_calls(fwd_bwd, q, k, v, platform="cpu") == 0  # interpreted


@pytest.mark.parametrize(
    "q_len,int8", [(1, False), (8, False), (1, True)],
    ids=["decode_row", "window_row", "int8"],
)
def test_ragged_kernel_lowers_for_tpu(q_len, int8):
    rows, h, d, bs, pages = 2, 2, 64, 16, 4
    tokens = (rows * pages + 1) * bs
    pool = jnp.zeros((tokens, h, d), jnp.float32)
    scale_k = scale_v = None
    if int8:
        pool, scale_k = paged.quantize_kv(pool)
        scale_v = scale_k
    q = jnp.zeros((rows, h, q_len, d), jnp.float32)
    table = jnp.zeros((rows, pages), jnp.int32)
    lengths = jnp.zeros((rows,), jnp.int32)

    def kernel(q, pool_k, pool_v, table, lengths, scale_k, scale_v):
        return ragged_paged_attention(
            q, pool_k, pool_v, table, lengths, block_size=bs,
            scale_k=scale_k, scale_v=scale_v,
        )

    args = (q, pool, pool, table, lengths, scale_k, scale_v)
    assert _mosaic_calls(kernel, *args) == 1
    assert _mosaic_calls(kernel, *args, platform="cpu") == 0


def _kernel_names(fn, *args):
    """Each Mosaic kernel's ``kernel_name`` and the last scope of its
    location, which the chip's instruction (and the profiler's event) takes
    its name from."""
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    return re.findall(r'kernel_name = "([^"]*)"', lowered.as_text()), set(
        re.findall(r"branch_0_fun/(\w+)/pallas_call", text)
    )


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
def test_each_flash_kernel_lowers_under_its_own_name(kernel):
    q = jnp.zeros((2, 2, 128, 64), jnp.bfloat16)
    k = v = jnp.zeros((2, 2, 256, 64), jnp.bfloat16)
    lse = delta = jnp.zeros((2, 2, 128, flash_attention.LANES), jnp.float32)
    call = {
        "flash_fwd": lambda q, k, v: flash_attention._forward(q, k, v, None, True),
        "flash_bwd_dq": lambda q, k, v: flash_attention._backward_dq(
            q, k, v, None, lse, delta, q, True),
        "flash_bwd_dkv": lambda q, k, v: flash_attention._backward_dkv(
            q, k, v, None, lse, delta, q, True),
    }[kernel]
    assert _kernel_names(call, q, k, v) == ([kernel], {kernel})


@pytest.mark.parametrize(
    "b,h,hk,i,j,d,pad,heads",
    [(32, 8, 8, 1024, 4608, 64, True, 1), (2, 8, 2, 512, 512, 64, False, 4),
     (2, 32, 8, 8192, 8192, 64, False, 4), (1, 20, 20, 8192, 8192, 256, False, 1),
     (1, 28, 4, 16384, 16384, 128, False, 1)],
    ids=["ar_cross_attention", "grouped_heads", "lfm2moe_cell", "glm47flash_cell", "smallthinker_cell_sliced"],
)
def test_fused_backward_lowers_as_flash_bwd_dkv_with_three_outputs(b, h, hk, i, j, d, pad, heads):
    """The one-kernel backward at the cells' shapes: one ``flash_bwd_dkv``
    with dK, dV and dQ by grid slice. Where the group is walked in slices
    (28 query heads on 4: a head a slice) dK and dV come out a slice each in
    float32; under 2 MiB of resident dQ the kernel asks for the 32 MiB it
    always did, above for what the shape takes."""
    q = do = jax.ShapeDtypeStruct((b, h, i, d), jnp.bfloat16)
    k = v = jax.ShapeDtypeStruct((b, hk, j, d), jnp.bfloat16)
    lse = delta = jax.ShapeDtypeStruct((b, h, i, flash_attention.LANES), jnp.float32)
    pad_mask = jax.ShapeDtypeStruct((b, 1, j), jnp.float32) if pad else None
    assert flash_attention._resident_heads(q, k) == heads
    slices = h // hk // heads

    def call(q, k, v, pad_mask, lse, delta, do):
        return flash_attention._backward_dkv(q, k, v, pad_mask, lse, delta, do, True, dq_heads=heads)

    args = (q, k, v, pad_mask, lse, delta, do)
    assert _kernel_names(call, *args) == (["flash_bwd_dkv"], {"flash_bwd_dkv"})
    text = jax.jit(call).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    (results,) = re.findall(r"custom_call @tpu_custom_call.*-> \((.*)\)", text)
    kv = f"tensor<{b}x{hk * slices}x{j}x{d}x{'bf16' if slices == 1 else 'f32'}>"
    dq = f"tensor<{b}x{hk * slices}x{heads * i}x{d}xbf16>"
    assert results.split(", ") == [kv, kv, dq]  # dK, dV, and dQ by key-value head (or slice of one)
    (limit,) = re.findall(r'scoped_memory_configs.*?\\22size\\22: (\d+)', text)  # ``vmem_limit_bytes``
    resident = heads * i * max(d, flash_attention.LANES) * 4
    assert (int(limit) == 32 << 20) if resident <= 8 << 20 else (48 << 20 > int(limit) > 32 << 20)
    out = jax.eval_shape(call, *args)  # the slices summed and rounded: dK, dV, dQ as their operands
    assert [(o.shape, o.dtype) for o in out] == [(k.shape, k.dtype), (v.shape, v.dtype), (q.shape, q.dtype)]


def test_ragged_kernel_lowers_under_its_own_name():
    rows, h, d, bs, pages = 2, 2, 64, 16, 4
    pool = jnp.zeros(((rows * pages + 1) * bs, h, d), jnp.float32)
    q = jnp.zeros((rows, h, 1, d), jnp.float32)
    table, lengths = jnp.zeros((rows, pages), jnp.int32), jnp.zeros((rows,), jnp.int32)

    def kernel(q, pool_k, pool_v, table, lengths):
        return ragged_paged_attention(q, pool_k, pool_v, table, lengths, block_size=bs)

    names = _kernel_names(kernel, q, pool, pool, table, lengths)
    assert names == (["ragged_paged_attention"], {"ragged_paged_attention"})


@pytest.mark.parametrize(
    "axes",
    [dict(data=4), dict(data=1, fsdp=4), dict(data=2, model=2)],
    ids=["data4", "fsdp4", "data2_model2"],
)
def test_train_step_with_pad_mask_lowers_for_tpu_on_four_devices(devices, axes):
    """The CLI's batch (pad mask included) through the flash kernel, over a
    4-device mesh: ``make_train_step`` publishes the mesh and the flash call
    shard_maps itself over it."""
    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=640, max_latents=128, num_channels=128,
        num_heads=4, num_self_attention_layers=1,
    )
    model = CausalLanguageModel(cfg, dtype=jnp.bfloat16, attention_impl="flash")
    mesh = make_mesh(MeshConfig(**axes), devices=devices[:4])

    def init():
        return model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 640), jnp.int32), 512
        )["params"]

    state, shardings = create_train_state(init, optax.adamw(1e-3), mesh)
    step = make_train_step(clm_loss_fn(model, cfg.max_latents), mesh, shardings)
    ids = np.zeros((4, 640), np.int32)
    batch = shard_batch(
        {"input_ids": ids, "labels": ids, "pad_mask": np.zeros((4, 640), bool)}, mesh
    )
    lowered = step.trace(state, batch, jax.random.PRNGKey(1)).lower(
        lowering_platforms=("tpu",)
    )
    # cross-attention + one self-attention layer, each a forward and one backward
    assert lowered.as_text().count("tpu_custom_call") == 4


def test_mosaic_kernel_without_shard_map_is_refused_at_lowering(devices):
    """Why ``dot_product_attention`` wraps the kernel: called bare on sharded
    operands it cannot be partitioned, and lowering says so."""
    mesh = Mesh(np.array(devices[:4]), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    q = jax.ShapeDtypeStruct((4, 2, 128, 64), jnp.bfloat16, sharding=sharded)
    k = jax.ShapeDtypeStruct((4, 2, 256, 64), jnp.bfloat16, sharding=sharded)

    def bare(q, k, v):
        return flash_attention.flash_attention(q, k, v, causal=True)

    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(bare).trace(q, k, k).lower(lowering_platforms=("tpu",))

    def dispatched(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return dot_product_attention(q, k, v, causal=True, impl="flash")

    lowered = jax.jit(dispatched).trace(q, k, k).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 1


def test_flash_under_a_seq_sharded_mesh_is_an_error(devices):
    mesh = make_mesh(MeshConfig(data=2, seq=2), devices=devices[:4])
    q = jnp.zeros((2, 2, 128, 64), jnp.bfloat16)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        with pytest.raises(ValueError, match="ring"):
            jax.eval_shape(
                lambda q: dot_product_attention(q, q, q, causal=True, impl="flash"), q
            )


_TENSOR = re.compile(r"tensor<((?:\d+x)+)\w+>")
_LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"')
_LOC_USE = re.compile(r"loc\((#loc\d+)\)\s*$")
_FUNC = re.compile(r"func\.func (?:public |private )?@([\w.]+)\(")
_CALL = re.compile(r"= (?:func\.)?call @([\w.]+)\(")


def test_rotary_lowers_without_a_pair_dimension_or_a_concatenate():
    """q and k are rotated as the projections' ``(b, n, h * c)`` outputs by
    shifts and a parity select. The ``(..., c/2, 2)`` view of channel pairs
    and the ``concatenate`` of rotated and pass-through channels cost the
    8k training step relayout copies of every q and k (PERF.md, PR 27): no
    value of the activations' size may end in a dimension of 2, forward or
    backward, and no ``concatenate`` of that size may sit under ``rotary``."""
    from perceiver_io_tpu.models.core.modules import MultiHeadAttention
    from perceiver_io_tpu.ops.position import RotaryEmbedding, frequency_position_encoding

    b, n, h, c = 2, 128, 2, 64
    mha = MultiHeadAttention(
        num_heads=h, num_q_input_channels=h * c, num_kv_input_channels=h * c,
        causal_attention=True, dtype=jnp.bfloat16, attention_impl="flash",
    )
    x = jnp.zeros((b, n, h * c), jnp.bfloat16)
    frq = frequency_position_encoding(jnp.broadcast_to(jnp.arange(n), (b, n)), c // 2)
    params = mha.init(jax.random.PRNGKey(0), x, x)

    def loss(params, x, frq):
        rot = RotaryEmbedding(frq, right_align=True)
        out = mha.apply(params, x, x, rot_pos_emb_q=rot, rot_pos_emb_k=rot)
        return jnp.sum(out.astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, x, frq).lower(
        lowering_platforms=("tpu",)
    )
    lines = lowered.as_text(debug_info=True).splitlines()
    scope_of = dict(m.groups() for m in map(_LOC_DEF.match, lines) if m)
    assert any("/rotary/" in scope for scope in scope_of.values())  # the pass is there, by name
    large = b * n * h * c // 2

    def shapes(text):
        return [tuple(int(d) for d in dims.split("x")[:-1]) for dims in _TENSOR.findall(text)]

    paired = {s for line in lines for s in shapes(line) if s[-1] == 2 and np.prod(s) >= large}
    assert not paired, f"activation-sized values with a trailing pair dimension: {paired}"

    # an operation is under ``rotary`` by its own location, or by that of a
    # call into the function that holds it (a jitted helper, ``jnp.roll``'s say,
    # is lowered to a function of its own whose locations start afresh)
    function, ops, calls = None, [], []
    for line in lines:
        opened = _FUNC.search(line)
        function = opened.group(1) if opened else function
        use = _LOC_USE.search(line)
        scope = scope_of.get(use.group(1), "") if use else ""
        called = _CALL.search(line)
        if called:
            calls.append((function, called.group(1), scope))
        elif "stablehlo.concatenate" in line:
            ops.append((function, scope, line))
    under = set()
    while True:
        more = {to for frm, to, scope in calls if "/rotary/" in scope or frm in under} - under
        if not more:
            break
        under |= more
    for function, scope, line in ops:
        size = np.prod(shapes(line.rsplit("->", 1)[-1])[0])
        assert not (size >= large and ("/rotary/" in scope or function in under)), line


_SHAPED = re.compile(r"tensor<([0-9x]+)x(?:bf16|f32|i32|i1)>")


def _tensor_shapes(text):
    return {tuple(int(d) for d in m.group(1).split("x")) for m in _SHAPED.finditer(text)}


def test_grouped_head_attention_lowers_for_tpu_without_repeating_keys_or_values():
    """32 query heads on 8 key-value heads, forward and backward, through the
    module: two Mosaic kernels (the group's dQ fits VMEM, so the backward is
    one), and nowhere in the program a key or value
    array at the query heads' count: ``(b, 32, n, 64)`` arrays are q, o and
    their gradients only, as many as the same module has without grouping
    has of q-shaped ones (the kernels index the shared head from their grid)."""
    from perceiver_io_tpu.models.core.modules import MultiHeadAttention

    b, n, h, hk, c = 2, 256, 32, 8, 64
    x = jnp.zeros((b, n, h * c), jnp.bfloat16)

    def lowered_text(kv_heads):
        mha = MultiHeadAttention(
            num_heads=h, num_q_input_channels=h * c, num_kv_input_channels=h * c,
            causal_attention=True, qkv_bias=False, out_bias=False, dtype=jnp.bfloat16,
            attention_impl="flash", num_kv_heads=kv_heads, qk_norm=True)
        params = mha.init(jax.random.PRNGKey(0), x, x)
        loss = lambda p, x: jnp.sum(mha.apply(p, x, x).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text()

    grouped, full = lowered_text(hk), lowered_text(None)
    assert grouped.count("tpu_custom_call") == 2
    count = lambda text, heads: len(re.findall(rf"tensor<{b}x{heads}x{n}x{c}xbf16>", text))
    assert count(grouped, hk) > 0  # k, v, dk, dv at 8 heads
    # every (b, 32, n, 64) array of the full module that was a key or a value is gone
    assert count(grouped, h) < count(full, h)
    assert f"tensor<{b}x{hk}x{h // hk}x{n}x{c}" not in grouped  # and no broadcast view of them
    assert f"tensor<{b}x{h}x{n}x{n}" not in grouped  # nor a score matrix


def test_latent_attention_at_the_cells_flash_shapes_lowers_with_a_one_kernel_backward():
    """``glm47flash-train-8k``'s attention call: 20 heads on 20, 8192 x 8192
    causal, 256-wide query-key and value heads, bfloat16. One head's float32
    dQ is 8 MiB of the one-kernel backward's 16, so the backward lowers as one
    ``flash_bwd_dkv`` beside ``flash_fwd``, through the
    module's own path (``LatentAttention``, ``attention_impl`` ``flash``), with
    no score matrix and no einsum fallback."""
    from perceiver_io_tpu.models.core.modules import LatentAttention
    from perceiver_io_tpu.ops.position import RotaryEmbedding

    b, n, c, h = 1, 8192, 2048, 20
    q = jax.ShapeDtypeStruct((b, h, n, 256), jnp.bfloat16)
    assert flash_attention.supported(q, q, q, causal=True) and flash_attention._resident_heads(q, q) == 1
    op = LatentAttention(
        num_heads=h, num_input_channels=c, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, dtype=jnp.bfloat16, attention_impl="flash")
    u = jax.ShapeDtypeStruct((b, n, c), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((b, n, 64), jnp.float32)
    rot = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(RotaryEmbedding(jnp.zeros((1, 2, 2)))), (tables, tables))
    params = jax.eval_shape(
        lambda: op.init(jax.random.PRNGKey(0), jnp.zeros((1, 128, c), jnp.bfloat16), None, None))
    loss = lambda p, u, rot: jnp.sum(op.apply(p, u, None, rot).astype(jnp.float32))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, u, rot).lower(
        lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert sorted(re.findall(r'kernel_name = "([^"]*)"', text)) == ["flash_bwd_dkv", "flash_fwd"]
    assert f"tensor<{b}x{h}x{n}x256xbf16>" in text  # q, k, v and o at 20 plain heads
    assert f"x{n}x{n}x" not in text  # no score matrix


def test_expert_layer_lowers_for_tpu_without_a_dispatch_array_over_all_experts():
    """The expert layer, forward and backward: tokens are sorted by held
    expert and multiplied by ``ragged_dot``; no ``(tokens, 64, ...)`` one-hot
    dispatch or combine array over the router's width, and nothing of the
    size of ``tokens x experts x channels``."""
    from perceiver_io_tpu.models.core.hybrid import SparseExperts

    tokens, c, f, width, held, k = 512, 128, 64, 64, 8, 4
    layer = SparseExperts(num_channels=c, hidden_channels=f, router_width=width,
                          num_experts=held, top_k=k, dtype=jnp.bfloat16)
    u = jnp.zeros((2, tokens // 2, c), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), u)
    loss = lambda p, u: jnp.sum(layer.apply(p, u)[0].astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, u).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "ragged_dot" in text
    for shape in _tensor_shapes(text):
        if len(shape) >= 3 and shape[0] in (tokens, tokens * k):
            assert width not in shape[1:] and held not in shape[1:], shape
        size = 1
        for d in shape:
            size *= d
        assert size <= max(tokens * k * c, held * c * f), shape
    # the router's scores are the only array over the router's width
    assert (tokens, width) in _tensor_shapes(text)


def test_expert_layer_at_the_cells_shapes_lowers_with_a_bounded_and_a_worst_case_branch():
    """``lfm2moe-train-8k``'s expert layer (16,384 tokens, 4 of 64 experts a
    token, 8 held, 2048 x 1536), forward and backward from shapes alone: the
    layer chooses its rows by ``lax.cond`` (one in the forward, one in the
    backward, whose branches take the operands and keep no residuals for
    each other), and both branches lower for the TPU. On the bounded rows
    (16,384) nothing has 65,536 rows but vectors (the permutation, its
    inverse, the pairs' weights and their gradient) and one array: the
    dispatch's backward gathers every pair's place from the 16,384 gradient
    rows, ``(4, 16384, 2048)``, which the chip ran faster than a scatter-add
    (PERF.md, PR 31). On every pair's rows the buffers are ``(65536, ...)``."""
    from perceiver_io_tpu.models.core import hybrid

    b, n, c, f, width, held, k = 2, 8192, 2048, 1536, 64, 8, 4
    pairs, rows = b * n * k, hybrid.expected_rows(b * n, k, held, width)
    assert (pairs, rows) == (65536, 16384)
    layer = hybrid.SparseExperts(num_channels=c, hidden_channels=f, router_width=width,
                                 num_experts=held, top_k=k, dtype=jnp.bfloat16)
    u = jax.ShapeDtypeStruct((b, n, c), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), u)
    loss = lambda p, u: jnp.sum(layer.apply(p, u)[0].astype(jnp.float32) ** 2)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, u).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("stablehlo.case") == 2
    assert {(pairs, c), (rows, c), (pairs, f), (rows, f)} <= _tensor_shapes(text)

    def forward_and_backward_on(rows):
        def run(tokens, weights, gate, up, down, order, inverse, sizes, g):
            out, pull = jax.vjp(
                lambda *a: hybrid.sorted_rows_output(*a, order, inverse, sizes, rows=rows),
                tokens, weights, gate, up, down)
            return out, pull(g)
        shaped = jax.ShapeDtypeStruct
        args = (shaped((b * n, c), jnp.bfloat16), shaped((b * n, k), jnp.float32),
                *(shaped(s, jnp.float32) for s in ((held, c, f), (held, c, f), (held, f, c))),
                shaped((pairs,), jnp.int32), shaped((pairs,), jnp.int32), shaped((held,), jnp.int32),
                shaped((b * n, c), jnp.bfloat16))
        shapes = _tensor_shapes(jax.jit(run).trace(*args).lower(lowering_platforms=("tpu",)).as_text())
        # of every pair's size, vectors and index columns aside
        return {s for s in shapes if math.prod(s) >= pairs * min(c, f) and math.prod(s) % pairs == 0}

    assert forward_and_backward_on(rows) == {(k, b * n, c)}
    assert {(pairs, c), (pairs, f)} <= forward_and_backward_on(pairs)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_dkv_with_dq"])
@pytest.mark.parametrize("pad", [False, True], ids=["nopad", "pad_b2"])
def test_each_windowed_flash_kernel_lowers_through_mosaic_on_a_grid_over_the_band(kernel, pad):
    """The three kernels (and the fused backward) under a window: Mosaic takes
    the band's index maps (a first block from the q block, a clamp), the
    kernel keeps its name, and the grid's inner dimension counts the band's
    blocks (``_Band``)."""
    b, h, hk, n, d, window = 2, 4, 2, 2048, 64, 200
    q = do = jax.ShapeDtypeStruct((b, h, n, d), jnp.bfloat16)
    k = v = jax.ShapeDtypeStruct((b, hk, n, d), jnp.bfloat16)
    lse = delta = jax.ShapeDtypeStruct((b, h, n, flash_attention.LANES), jnp.float32)
    pad_mask = jax.ShapeDtypeStruct((b, 1, n), jnp.float32) if pad else None
    call = {
        "flash_fwd": lambda q, k, v, pad_mask, lse, delta, do: flash_attention._forward(
            q, k, v, pad_mask, True, window),
        "flash_bwd_dq": lambda q, k, v, pad_mask, lse, delta, do: flash_attention._backward_dq(
            q, k, v, pad_mask, lse, delta, do, True, window),
        "flash_bwd_dkv": lambda q, k, v, pad_mask, lse, delta, do: flash_attention._backward_dkv(
            q, k, v, pad_mask, lse, delta, do, True, window),
        "flash_bwd_dkv_with_dq": lambda q, k, v, pad_mask, lse, delta, do: flash_attention._backward_dkv(
            q, k, v, pad_mask, lse, delta, do, True, window, dq_heads=h // hk),
    }[kernel]
    args = (q, k, v, pad_mask, lse, delta, do)
    name = kernel.replace("_with_dq", "")
    assert _kernel_names(call, *args) == ([name], {name})
    text = jax.jit(call).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    # 2048 rows in blocks of 512: a band of 200 keys meets 2 of a row's 4 blocks
    bi = flash_attention._pick_block(n)
    band = flash_attention._Band(bi, bi, n // bi, n // bi, 0, window)
    assert (band.kv_blocks, band.q_blocks) == (2, 2)
    assert jax.jit(call).trace(*args).lower(lowering_platforms=("cpu",)).as_text().count(
        "tpu_custom_call") == 0
    assert text.count("tpu_custom_call") >= 1


def test_window_attention_at_the_cells_shapes_lowers_on_the_band_with_a_sliced_one_kernel_backward():
    """``smallthinker-train-16k``'s window layers: 28 query heads on 4 of 128,
    16,384 x 16,384 causal under a window of 4,096, bfloat16, through the
    module's own path. A group's float32 dQ is 56 MiB against the one-kernel
    backward's 16 and a head's 8, so the backward is that kernel over the 7
    heads of a group a slice each, dK and dV a slice in float32; each
    kernel's grid walks 9 blocks a band where the row has 32; no score matrix, no key or value at
    the query heads' count, no einsum fallback."""
    from perceiver_io_tpu.models.core.modules import MultiHeadAttention
    from perceiver_io_tpu.ops.position import RotaryEmbedding

    b, n, c, h, hk, d, window = 1, 16384, 2560, 28, 4, 128, 4096
    q = jax.ShapeDtypeStruct((b, h, n, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, hk, n, d), jnp.bfloat16)
    assert flash_attention.supported(q, k, k, causal=True, window=window)
    assert flash_attention._resident_heads(q, k) == 1
    band = flash_attention._Band(512, 512, 32, 32, 0, window)
    assert (band.kv_blocks, band.q_blocks) == (9, 9)
    op = MultiHeadAttention(
        num_heads=h, num_q_input_channels=c, num_kv_input_channels=c, num_qk_channels=h * d,
        causal_attention=True, qkv_bias=False, out_bias=False, dtype=jnp.bfloat16,
        attention_impl="flash", num_kv_heads=hk, window=window)
    u = jax.ShapeDtypeStruct((b, n, c), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((b, n, d), jnp.float32)
    rot = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(RotaryEmbedding(jnp.zeros((1, 2, 2)))), (tables, tables))
    x = jnp.zeros((1, 128, c), jnp.bfloat16)
    params = jax.eval_shape(lambda: op.init(jax.random.PRNGKey(0), x, x))
    loss = lambda p, u, rot: jnp.sum(
        op.apply(p, u, u, rot_pos_emb_q=rot, rot_pos_emb_k=rot).astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, u, rot).lower(
        lowering_platforms=("tpu",)).as_text()
    assert sorted(re.findall(r'kernel_name = "([^"]*)"', text)) == ["flash_bwd_dkv", "flash_fwd"]
    assert f"tensor<{b}x{h}x{n}x{d}xf32>" in text  # dK and dV by slice
    assert f"tensor<{b}x{h}x{n}x{d}xbf16>" in text and f"tensor<{b}x{hk}x{n}x{d}xbf16>" in text
    assert f"x{n}x{n}x" not in text and f"tensor<{b}x{hk}x{h // hk}x{n}x{d}xbf16" not in text
    assert params["params"]["q_proj"]["kernel"].shape == (c, h * d)
    assert params["params"]["k_proj"]["kernel"].shape == (c, hk * d)
    assert params["params"]["o_proj"]["kernel"].shape == (h * d, c)


def test_windowed_flash_call_lowers_under_a_mesh_with_heads_over_model(devices):
    """A window call through ``dot_product_attention`` on ``data=2 x model=2``:
    the call shard_maps itself (batch over ``data``, the 2 key-value heads and
    their 14 query heads over ``model``) and every shard's kernels carry the
    window: forward and a one-kernel backward (7 heads x 256 rows of dQ fit)."""
    mesh = make_mesh(MeshConfig(data=2, model=2), devices=devices[:4])
    q = jax.ShapeDtypeStruct((2, 14, 256, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 2, 256, 64), jnp.bfloat16)

    def loss(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            o = dot_product_attention(q, k, v, causal=True, window=100, impl="flash")
        return jnp.sum(o.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, k, k).lower(
        lowering_platforms=("tpu",)).as_text()
    assert sorted(re.findall(r'kernel_name = "([^"]*)"', text)) == ["flash_bwd_dkv", "flash_fwd"]
    assert "tensor<1x7x256x64xbf16>" in text  # a shard's query heads


def test_a_head_whose_dq_alone_is_over_the_budget_still_lowers_as_two_kernels():
    """Past the rule (one 256-wide head on 16,896 rows: 16.5 MiB of float32
    dQ) the backward is the two kernels it always was, and both lower for the
    TPU under their own names."""
    q = jax.ShapeDtypeStruct((1, 2, 16384 + 512, 256), jnp.bfloat16)
    assert flash_attention.supported(q, q, q, causal=True) and flash_attention._resident_heads(q, q) == 0
    loss = lambda q, k, v: jnp.sum(flash_attention.flash_attention(q, k, v, causal=True).astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, q, q).lower(lowering_platforms=("tpu",)).as_text()
    assert sorted(re.findall(r'kernel_name = "([^"]*)"', text)) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert "scoped_memory_configs" not in text  # no ``vmem_limit_bytes``: each under the 16 MiB a kernel gets without asking


@pytest.mark.parametrize(
    "b,h,hk,i,j,d,dv,causal,window,pad",
    [(32, 8, 8, 1024, 4608, 64, 64, True, None, True), (32, 8, 8, 1024, 1024, 64, 64, True, None, False),
     (32, 8, 8, 256, 256, 32, 160, False, None, False), (32, 8, 8, 256, 2048, 32, 160, False, None, True),
     (32, 8, 8, 2048, 256, 32, 96, False, None, False), (2, 32, 8, 8192, 8192, 64, 64, True, None, False),
     (1, 20, 20, 8192, 8192, 256, 256, True, None, False),
     (1, 28, 4, 16384, 16384, 128, 128, True, None, False),
     (1, 28, 4, 16384, 16384, 128, 128, True, 4096, False)],
    ids=["ar_cross_attention", "ar_self_attention", "mlm_self_attention", "mlm_encoder", "mlm_decoder",
         "lfm2moe_cell", "glm47flash_cell", "smallthinker_global", "smallthinker_window"],
)
def test_forward_at_the_cells_shapes_lowers_as_flash_fwd_on_its_grid(b, h, hk, i, j, d, dv, causal, window, pad):
    """Each forward the five cells run, lowered for the TPU through Mosaic:
    one kernel named ``flash_fwd`` on the grid it has always had (every q
    block, and every kv block or a band's), two products a grid step."""
    q = jax.ShapeDtypeStruct((b, h, i, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, hk, j, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, hk, j, dv), jnp.bfloat16)
    pad_mask = jax.ShapeDtypeStruct((b, 1, j), jnp.float32) if pad else None
    call = lambda q, k, v, pad_mask: flash_attention._forward(q, k, v, pad_mask, causal, window)
    assert _kernel_names(call, q, k, v, pad_mask) == (["flash_fwd"], {"flash_fwd"})
    bi, bj = flash_attention._pick_block(i), flash_attention._pick_block(j)
    steps = j // bj if window is None else flash_attention._Band(bi, bj, i // bi, j // bj, j - i, window).kv_blocks
    jaxpr = str(jax.make_jaxpr(call)(q, k, v, pad_mask))
    assert set(re.findall(r"grid=\(([^)]*)\)", jaxpr)) == {f"{b}, {h}, {i // bi}, {steps}"}
    assert jaxpr.count("dot_general") == 2 * 2  # the Mosaic branch and the interpreter's

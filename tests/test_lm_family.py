"""The decoder-only family (``models/text/lm.py``, ``models/core/hybrid.py``)
against the benchmark's plain reference (``benchmarks/reference/lfm2_moe.py``,
which imports nothing of the program) at a small size on the CPU: logits,
loss and every leaf's gradient; the share test (the shares' expert outputs
add up to the uncut layer); no dropped pair at the worst imbalance; the short
convolution against ``jnp.convolve``; grouped heads on every attention path;
a two-step ``fit`` through the CLI."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.adapters import lm as adapter  # noqa: E402
from benchmarks.reference import blocks  # noqa: E402
from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from perceiver_io_tpu.models.core import hybrid  # noqa: E402
from perceiver_io_tpu.models.core.hybrid import ShortConv, SparseExperts, causal_depthwise_conv  # noqa: E402
from perceiver_io_tpu.models.text.lm import DecoderLM, DecoderLMConfig  # noqa: E402
from perceiver_io_tpu.ops.attention import dot_product_attention  # noqa: E402
from perceiver_io_tpu.ops.position import RotaryEmbedding, frequency_position_encoding, positions  # noqa: E402
from perceiver_io_tpu.training.tasks import lm_loss_fn  # noqa: E402

#: hidden 64, 4 heads on 2 key-value heads, 8 experts 2 a token, published
#: layers 1..3 of ``conv, conv, full_attention, conv``: one dense layer
SMALL = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3, "norm_eps": 1e-5,
    "norm_topk_prob": True, "num_experts": 8, "router_width": 8, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 64,
    "rope_parameters": {"rope_theta": 1000000}, "max_position_embeddings": 256,
    "layer_types": ["conv", "conv", "full_attention", "conv"], "first_layer": 1,
    "num_layers": 3, "num_dense_layers": 1,
}
SEED = 3


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _batch(rows=2, n=128):
    x = jax.random.randint(jax.random.PRNGKey(0), (rows, n + 1), 0, SMALL["vocab_size"])
    return {"input_ids": x[:, :-1], "labels": x[:, 1:], "pad_mask": jnp.zeros((rows, n), bool)}


def _program(dtype=jnp.float32, **model):
    cfg = adapter.model_config(SMALL, model)
    return DecoderLM(cfg, dtype=dtype, attention_impl="xla")


def _reference_loss(p, batch):
    total, count = ref.train_nll(p, SMALL, batch)
    return total / count


def test_logits_loss_and_every_gradient_match_the_reference_in_float32():
    batch = _batch()
    p_ref = ref.init_params(jax.random.PRNGKey(SEED), SMALL)
    assert float(jnp.abs(p_ref["layer.1.moe.bias"]).max()) > 0.005  # the selection bias is there
    tree = adapter.common.seeded_tree(ref, SMALL, adapter.path_of, SEED)
    model = _program()
    np.testing.assert_allclose(
        model.apply({"params": tree}, batch["input_ids"]),
        ref.logits(p_ref, SMALL, batch["input_ids"]), atol=1e-5)
    loss_r, grads_r = jax.value_and_grad(_reference_loss)(p_ref, batch)
    (loss_p, stats), grads_p = jax.value_and_grad(lm_loss_fn(model), has_aux=True)(tree, batch, None)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5
    grads_p = adapter.common.leaves_by_name(grads_p, sorted(p_ref), adapter.path_of)
    for name, g in grads_r.items():
        np.testing.assert_allclose(grads_p[name], g, atol=1e-5 * max(1.0, float(jnp.abs(g).max())),
                                   err_msg=name)
    assert float(jnp.abs(grads_r["layer.1.moe.bias"]).max()) == 0.0  # it takes no gradient
    # two expert layers, every expert held: every pair is computed
    assert float(stats["moe_assignments_held"]) == 2 * 2 * 128 * 2
    assert float(stats["moe_expert_load_max_over_mean"]) >= 1.0
    assert float(stats["moe_layers_bounded"]) == 2.0  # a whole layer's bound is its worst case


def test_bfloat16_program_stays_near_the_float32_reference():
    """bfloat16 keeps 8 bits of mantissa: products round at 2 ** -9 relative,
    and a score that rounds past another flips a token's expert, so the band
    is a few per cent of the logits' range, not 1e-5."""
    batch = _batch()
    p_ref = ref.init_params(jax.random.PRNGKey(SEED), SMALL)
    tree = adapter.common.seeded_tree(ref, SMALL, adapter.path_of, SEED)
    want = ref.logits(p_ref, SMALL, batch["input_ids"])
    got = _program(jnp.bfloat16).apply({"params": tree}, batch["input_ids"]).astype(jnp.float32)
    assert float(jnp.abs(got - want).max()) < 0.05 * float(jnp.abs(want).max())
    loss = lm_loss_fn(_program(jnp.bfloat16))(tree, batch, None)[0]
    assert abs(float(loss) - float(_reference_loss(p_ref, batch))) < 2e-2


def _expert_layer_params(key, c=64, f=48, e=8):
    shapes = {"moe.router.w": (c, e), "moe.bias": (e,), "moe.gate": (e, c, f),
              "moe.up": (e, c, f), "moe.down": (e, f, c)}
    p = blocks.normal_params(key, shapes, 0.3)
    return {**p, "moe.bias": 5.0 * p["moe.bias"]}


def _held(p, offset, count):
    take = slice(offset, offset + count)
    return {"router": p["moe.router.w"], "expert_bias": p["moe.bias"], "gate": p["moe.gate"][take],
            "up": p["moe.up"][take], "down": p["moe.down"][take]}


def _layer(offset, count, top_k=2):
    return SparseExperts(num_channels=64, hidden_channels=48, router_width=8, num_experts=count,
                         expert_offset=offset, top_k=top_k)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """8 experts in shares of 2: each share routes over all 8 and computes
    its own experts' part; the four parts sum to the whole layer (nothing is
    counted twice: the layer has no shared expert)."""
    p = _expert_layer_params(jax.random.PRNGKey(1))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 96, 64))
    whole = ref.experts(u, p, "moe", SMALL)
    parts, pairs = [], 0.0
    for share in range(4):
        out, stats = _layer(2 * share, 2).apply({"params": _held(p, 2 * share, 2)}, u)
        parts.append(out)
        pairs += float(stats[0])
        alone = ref.experts(u, _cut(p, 2 * share, 2), "moe",
                            {**SMALL, "num_experts": 2, "expert_offset": 2 * share})
        np.testing.assert_allclose(out, alone, atol=1e-5, rtol=1e-5)
    assert pairs == 2 * 96 * 2  # every pair is computed by exactly one share
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5, rtol=1e-5)
    full, _ = _layer(0, 8).apply({"params": _held(p, 0, 8)}, u)
    np.testing.assert_allclose(full, whole, atol=1e-5, rtol=1e-5)


def _cut(p, offset, count):
    take = slice(offset, offset + count)
    return {**p, "moe.gate": p["moe.gate"][take], "moe.up": p["moe.up"][take],
            "moe.down": p["moe.down"][take]}


@pytest.mark.parametrize("row_tile", [512, 32], ids=["one_path", "cond"])
@pytest.mark.parametrize("held_offset,expect_pairs", [(3, 192), (5, 0)])
def test_no_pair_is_dropped_when_every_token_chooses_one_held_expert(
        monkeypatch, held_offset, expect_pairs, row_tile):
    """A bias that sends every token to expert 3 (and, second, to 0): the one
    held expert gets every token, or none; both match the reference, values
    and gradients. 384 pairs, one expert of 8 held: with a row tile of 32
    the layer's bound is 96 rows, so it chooses by ``lax.cond``; 192 held
    pairs are over it, the worst-case branch runs and reports 0; none is
    under it. With the tile of 512 the bound is the worst case: one path."""
    monkeypatch.setattr(hybrid, "_ROW_TILE", row_tile)
    chooses = hybrid.expected_rows(192, 2, 1, 8) < 384
    assert chooses == (row_tile == 32)
    p = _expert_layer_params(jax.random.PRNGKey(4))
    p["moe.bias"] = jnp.zeros(8).at[3].set(100.0).at[0].set(50.0)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 96, 64))
    cfg = {**SMALL, "num_experts": 1, "expert_offset": held_offset}
    layer, held = _layer(held_offset, 1), _held(p, held_offset, 1)
    assert ("cond" in str(jax.make_jaxpr(lambda q, x: layer.apply({"params": q}, x))(held, u))) == chooses
    out, stats = layer.apply({"params": held}, u)
    assert float(stats[0]) == expect_pairs
    assert float(stats[2]) == (0.0 if chooses and expect_pairs > 96 else 1.0)
    np.testing.assert_allclose(out, ref.experts(u, _cut(p, held_offset, 1), "moe", cfg), atol=1e-5, rtol=1e-5)
    g_prog = jax.grad(lambda q, x: (layer.apply({"params": q}, x)[0] ** 2).sum(), argnums=(0, 1))(held, u)
    g_ref = jax.grad(lambda q, x: (ref.experts(x, q, "moe", cfg) ** 2).sum(), argnums=(0, 1))(
        _cut(p, held_offset, 1), u)
    np.testing.assert_allclose(g_prog[1], g_ref[1], atol=1e-4, rtol=1e-4)
    for ours, theirs in (("gate", "moe.gate"), ("down", "moe.down"), ("router", "moe.router.w")):
        np.testing.assert_allclose(g_prog[0][ours], g_ref[0][theirs], atol=1e-4, rtol=1e-4, err_msg=ours)


def _routed(key, t=192, c=64, f=48, width=8, held=3, top_k=2):
    """Tokens, their routing over ``width`` experts and the weights of the
    ``held`` experts from 2 on, for :func:`hybrid.held_experts_output`."""
    ks = jax.random.split(key, 5)
    tokens = jax.random.normal(ks[0], (t, c))
    router = 0.3 * jax.random.normal(ks[1], (c, width))
    gate, up = (0.3 * jax.random.normal(k, (held, c, f)) for k in ks[2:4])
    down = 0.3 * jax.random.normal(ks[4], (held, f, c))
    return tokens, router, gate, up, down, top_k


def _loss_on_rows(rows):
    def loss(tokens, router, gate, up, down):
        indices, weights = hybrid.route(tokens, router, None, 2, True, 1.0)
        out, sizes = hybrid.held_experts_output(tokens, indices, weights, gate, up, down, 2, rows)
        return (out ** 2).sum(), (out, sizes)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)


@pytest.mark.parametrize("rows", ["exactly_held", "held_rounded_up", "twice_held", "every_pair"])
def test_any_row_count_that_holds_the_held_pairs_gives_the_worst_case_result(rows):
    """One implementation for every ``rows``: on exactly the held pairs (no
    row to spare: a pair past ``rows`` must not read the last held row), on
    them rounded up to 8, on twice that and on all ``t * top_k`` the output
    and the gradients (tokens, the router through the weights, gate, up,
    down) are the worst-case buffer's."""
    *args, top_k = _routed(jax.random.PRNGKey(11))
    (_, (want_out, sizes)), want = _loss_on_rows(None)(*args)
    held = int(sizes.sum())
    every = args[0].shape[0] * top_k
    assert 0 < held < every // 2
    rows = {"exactly_held": held, "held_rounded_up": -(-held // 8) * 8, "twice_held": 2 * held,
            "every_pair": every}[rows]
    (_, (out, got_sizes)), got = _loss_on_rows(rows)(*args)
    np.testing.assert_array_equal(got_sizes, sizes)
    np.testing.assert_allclose(out, want_out, atol=1e-5, rtol=1e-5)
    for a, b, name in zip(got, want, ("tokens", "router", "gate", "up", "down")):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


def test_the_row_bound_is_twice_the_uniform_share_in_whole_tiles():
    assert hybrid.expected_rows(16384, 4, 8, 64) == 16384  # the cell: a quarter of 65,536
    assert hybrid.expected_rows(16384, 4, 64, 64) >= 65536  # a whole layer: its worst case, no cond
    assert hybrid.expected_rows(1000, 4, 3, 64) == 512 and hybrid.expected_rows(3000, 4, 3, 64) == 1536


@pytest.mark.parametrize("row_tile", [512, 32], ids=["one_path", "bounded_rows"])
def test_rows_a_grouped_product_leaves_unwritten_are_never_read(monkeypatch, row_tile):
    """The TPU's kernel for ``ragged_dot`` writes only the rows of its groups;
    what lies past them is whatever the memory held (the first chip run of
    PR 28 read gradients of 1e5 and NaN from there). Poison those rows,
    forward and backward, and the layer's output and gradients stay put: on
    the worst-case buffer (384 rows), and with a row tile of 32 on the
    bounded one (288 rows, twice the 3 held experts' uniform share)."""
    monkeypatch.setattr(hybrid, "_ROW_TILE", row_tile)
    clean = hybrid.grouped_matmul

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        out = clean(lhs, rhs, sizes)
        return jnp.where((jnp.arange(lhs.shape[0]) < sizes.sum())[:, None], out, jnp.nan)

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        inside = (jnp.arange(lhs.shape[0]) < sizes.sum())[:, None]
        d_lhs, d_rhs = jax.vjp(lambda a, b: clean(a, b, sizes), lhs, rhs)[1](jnp.where(inside, g, 0.0))
        return jnp.where(inside, d_lhs, 1e30), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    p = _expert_layer_params(jax.random.PRNGKey(1))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 96, 64))
    layer, held = _layer(2, 3), _held(p, 2, 3)
    loss = lambda q, x: (layer.apply({"params": q}, x)[0] ** 2).sum()
    want = jax.value_and_grad(loss, argnums=(0, 1))(held, u)
    monkeypatch.setattr(hybrid, "grouped_matmul", poisoned)
    got = jax.value_and_grad(loss, argnums=(0, 1))(held, u)
    stats = layer.apply({"params": held}, u)[1]
    assert float(stats[0]) < min(384, hybrid.expected_rows(192, 2, 3, 8))  # rows past the held pairs, in either buffer
    assert float(stats[2]) == 1.0
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_short_convolution_is_a_causal_convolution_by_channel():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 5))
    filt = jax.random.normal(jax.random.PRNGKey(7), (3, 5))
    got = causal_depthwise_conv(x, filt)
    for b in range(2):
        for ch in range(5):
            want = jnp.convolve(x[b, :, ch], filt[::-1, ch])[:40]
            np.testing.assert_allclose(got[b, :, ch], want, atol=1e-5)
    # the module against the reference, and padding never reaches a real position
    p = blocks.normal_params(jax.random.PRNGKey(8), {"conv.in.w": (5, 15), "conv.filter": (3, 5),
                                                     "conv.out.w": (5, 5)}, 0.5)
    params = {"in_proj": {"kernel": p["conv.in.w"]}, "filter": p["conv.filter"],
              "out_proj": {"kernel": p["conv.out.w"]}}
    module = ShortConv(5, 3)
    np.testing.assert_allclose(module.apply({"params": params}, x), ref.short_conv(x, p, "conv"), atol=1e-5)
    pad = jnp.zeros((2, 40), bool).at[:, :7].set(True)
    np.testing.assert_allclose(module.apply({"params": params}, x, pad)[:, 7:],
                               module.apply({"params": params}, x[:, 7:]), atol=1e-5)


def test_adjacent_pair_rotary_on_reordered_channels_is_the_published_rotary():
    """The adapter's reordering: channel ``i`` and ``i + d / 2`` of the
    reference sit at ``2i`` and ``2i + 1`` of the program."""
    d, n = 16, 12
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 2, n, d))
    order = adapter._pair_order(d)
    rot = RotaryEmbedding(frequency_position_encoding(positions(1, n), d, 1e6))
    np.testing.assert_allclose(rot.rotate(x[..., order]), ref.rotary(x, 1e6)[..., order], atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("causal", [True, False])
def test_grouped_heads_match_repeated_key_value_heads(impl, causal):
    b, h, hk, n, d = 2, 4, 2, 256, 32
    q = 0.3 * jax.random.normal(jax.random.PRNGKey(10), (b, h, n, d))
    k = jax.random.normal(jax.random.PRNGKey(11), (b, hk, n, d))
    v = jax.random.normal(jax.random.PRNGKey(12), (b, hk, n, d))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * jnp.cos(jnp.arange(d))).sum()

    grouped = lambda q, k, v: dot_product_attention(q, k, v, causal=causal, impl=impl)
    repeated = lambda q, k, v: dot_product_attention(
        q, jnp.repeat(k, h // hk, 1), jnp.repeat(v, h // hk, 1), causal=causal, impl="xla")
    np.testing.assert_allclose(grouped(q, k, v), repeated(q, k, v), atol=2e-5)
    for got, want in zip(jax.grad(loss(grouped), (0, 1, 2))(q, k, v),
                         jax.grad(loss(repeated), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("held", [8, 2], ids=["whole_layers", "cond_inside_the_layer"])
def test_recomputation_by_layer_gives_the_same_loss_and_gradients(monkeypatch, held):
    """With 2 of the 8 experts held and a row tile of 32 the expert layers
    choose their rows by ``lax.cond`` (256 of 512), inside the rematerialised
    layer."""
    batch = _batch()
    tree = adapter.common.seeded_tree(ref, SMALL, adapter.path_of, SEED)
    model = {}
    if held < 8:
        monkeypatch.setattr(hybrid, "_ROW_TILE", 32)
        model = {"num_experts": held, "expert_offset": 2}
        stacked = lambda path: path[-2].key == "moe" and path[-1].key in ("gate", "up", "down")
        tree = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf[2:2 + held] if stacked(path) else leaf, tree)
    loss = lambda **kw: (lambda p: lm_loss_fn(_program(**model, **kw))(p, batch, None)[0])
    assert ("cond" in str(jax.make_jaxpr(loss(activation_checkpointing=True))(tree))) == (held < 8)
    plain = jax.value_and_grad(loss())(tree)
    remat = jax.value_and_grad(loss(activation_checkpointing=True))(tree)
    assert float(plain[0]) == float(remat[0])
    for a, b in zip(jax.tree_util.tree_leaves(plain[1]), jax.tree_util.tree_leaves(remat[1])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_config_round_trips_and_refuses_unknown_layers():
    from perceiver_io_tpu.models import model_for_config
    from perceiver_io_tpu.models.core.config import config_from_dict, config_to_dict

    cfg = DecoderLMConfig(layer_types=("conv", "full_attention"), num_dense_layers=1)
    back = config_from_dict(DecoderLMConfig, config_to_dict(cfg))
    assert back == cfg and back.num_layers == 2 and back.has_experts
    assert isinstance(model_for_config(back), DecoderLM)
    with pytest.raises(ValueError, match="layer_types"):
        DecoderLMConfig(layer_types=("conv", "sliding_attention"))
    with pytest.raises(ValueError, match="not among the router"):
        SparseExperts(64, 48, router_width=8, num_experts=4, expert_offset=6).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 64)))


def _fit_argv(tmp_path, remat):
    return [
        "fit", "--data=synthetic", f"--data.dataset_dir={tmp_path}/data", "--data.max_seq_len=64",
        "--data.batch_size=8", "--data.num_train_docs=16", "--data.num_valid_docs=8",
        "--data.doc_chars=512", "--model.num_channels=32", "--model.num_heads=2",
        "--model.num_kv_heads=1", "--model.layer_types=conv,full_attention,conv",
        "--model.num_dense_layers=1", "--model.mlp_channels=48", "--model.expert_channels=24",
        "--model.router_width=8", "--model.num_experts=8", "--model.experts_per_token=2",
        f"--model.activation_checkpointing={remat}", "--trainer.max_steps=2",
        "--trainer.log_every_n_steps=1", "--trainer.val_check_interval=100",
        f"--trainer.default_root_dir={tmp_path}/logs_{remat}", "--trainer.enable_checkpointing=false",
        "--trainer.enable_tensorboard=false",
    ]


def test_two_step_fit_through_the_cli_with_and_without_recomputation(tmp_path):
    from perceiver_io_tpu.observability import default_registry
    from perceiver_io_tpu.scripts.text import lm as lm_script

    losses = {}
    for remat in ("false", "true"):
        state = lm_script.main(_fit_argv(tmp_path, remat))
        assert int(state.step) == 2
        gauges = default_registry().snapshot()["gauges"]
        losses[remat] = gauges["trainer_loss"] if "trainer_loss" in gauges else None
        # 8 rows of 64 tokens, 2 a token, two expert layers, every expert held
        assert gauges["trainer_moe_assignments_held"] == 2 * 8 * 64 * 2
        assert gauges["trainer_moe_expert_load_max_over_mean"] >= 1.0
        assert gauges["trainer_moe_layers_bounded"] == 2.0
    with open(os.path.join(tmp_path, "logs_true", "metrics.jsonl")) as f:
        assert "train/moe_assignments_held" in f.read()


def test_bounded_layers_gauge_has_help_text_and_a_benchmark_reader(monkeypatch):
    """``benchmarks/metrics/moe_bounded_layers.py``: nothing from a program
    that never set the gauge (the parent), then the gauge's value."""
    import perceiver_io_tpu.observability as observability
    from benchmarks import harness
    from perceiver_io_tpu.observability.exporters import HELP_TEXT

    registry = observability.MetricsRegistry()
    monkeypatch.setattr(observability, "default_registry", lambda: registry)
    read = harness.load_reader(os.path.join(ROOT, "benchmarks"), "moe_bounded_layers")
    assert "trainer_moe_layers_bounded" in HELP_TEXT
    assert read({}) is None
    registry.set_gauge("trainer_moe_layers_bounded", 4.0)
    assert read({}) == 4.0


def test_serve_refuses_the_family_loudly(tmp_path):
    from perceiver_io_tpu.scripts.text import lm as lm_script
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    cfg = DecoderLMConfig(vocab_size=32, num_channels=32, num_heads=2, num_kv_heads=1,
                          layer_types=("conv",), num_dense_layers=1, mlp_channels=32)
    params = DecoderLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    save_pretrained(str(tmp_path / "ckpt"), params, cfg)
    with pytest.raises(SystemExit, match="serve does not take the lm family yet"):
        lm_script.main(["serve", f"--ckpt={tmp_path}/ckpt"])

"""The decoder-only family's window layers and what came with them
(docs/lm.md): a head width of its own, q/k norm and rotary by operator kind,
``window_attention`` beside ``full_attention``, a router that scores by a
softmax over the chosen logits and may read another tensor than the experts,
``relu`` experts; the defaults' parameter trees as they were; a two-step
``fit`` through the CLI with flags alone."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.models.core import hybrid
from perceiver_io_tpu.models.core.hybrid import SparseExperts
from perceiver_io_tpu.models.text.lm import ATTENTION_SCOPES, DecoderLM, DecoderLMConfig


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _config(**changes):
    settings = dict(
        vocab_size=64, max_seq_len=256, num_channels=40, num_heads=14, num_kv_heads=2, head_dim=8,
        qk_norm=False, layer_types=("full_attention", "window_attention"), sliding_window=16,
        rotary_layer_types=("window_attention",), num_dense_layers=0, expert_channels=24,
        router_width=8, num_experts=8, experts_per_token=3, use_expert_bias=False,
        router_score="softmax_topk", expert_activation="relu", router_input="operator",
        tie_word_embeddings=False, norm_eps=1e-6)
    return DecoderLMConfig(**{**settings, **changes})


def _init(cfg, n=32):
    model = DecoderLM(cfg, attention_impl="xla")
    return model, model.init(jax.random.PRNGKey(0), jnp.zeros((1, n), jnp.int32))["params"]


@pytest.mark.parametrize("changes,message", [
    (dict(sliding_window=0), "sliding_window"),
    (dict(rotary_layer_types=("window",)), "layer_types"),
    (dict(router_input="attention"), "router_input"),
    (dict(head_dim=7), "even number"),
    (dict(head_dim=0, num_channels=40, num_heads=14), "divisible"),
])
def test_config_refuses_what_it_cannot_build(changes, message):
    with pytest.raises(ValueError, match=message):
        _config(**changes)


def test_config_round_trips_with_the_new_fields():
    from perceiver_io_tpu.models.core.config import config_from_dict, config_to_dict

    cfg = _config()
    back = config_from_dict(DecoderLMConfig, config_to_dict(cfg))
    assert back == cfg and back.attention_head_dim == 8
    assert DecoderLMConfig().attention_head_dim == 512 // 8
    assert set(ATTENTION_SCOPES) == {"full_attention", "window_attention", "sparse_attention"}


def test_head_width_of_its_own_and_no_qk_norm_shape_the_tree():
    _, params = _init(_config())
    for layer in ("layers_0", "layers_1"):
        attn = params[layer]["attention"]
        assert sorted(attn) == ["k_proj", "o_proj", "q_proj", "v_proj"]  # no q_norm, k_norm
        assert attn["q_proj"]["kernel"].shape == (40, 14 * 8)
        assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (40, 2 * 8)
        assert attn["o_proj"]["kernel"].shape == (14 * 8, 40)
        assert sorted(params[layer]["moe"]) == ["down", "gate", "router", "up"]
    assert params["head"]["kernel"].shape == (40, 64)


def test_the_defaults_build_the_tree_they_always_built():
    """A ``full_attention`` model that sets none of the new fields: heads of
    ``num_channels / num_heads``, q and k normed, an expert bias: the leaves
    of ``lfm2-24b-a2b-ep8``'s layers."""
    cfg = DecoderLMConfig(vocab_size=64, num_channels=32, num_heads=4, num_kv_heads=2,
                          layer_types=("full_attention",), num_dense_layers=0, expert_channels=16)
    _, params = _init(cfg)
    attn = params["layers_0"]["attention"]
    assert sorted(attn) == ["k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]
    assert attn["q_proj"]["kernel"].shape == (32, 32) and attn["k_proj"]["kernel"].shape == (32, 16)
    assert attn["q_norm"]["scale"].shape == (8,)
    assert sorted(params["layers_0"]["moe"]) == ["down", "expert_bias", "gate", "router", "up"]


def _last_logits(cfg, ids):
    model, params = _init(cfg, n=ids.shape[1])
    return model.apply({"params": params}, ids)[0, -1]


def test_a_kind_left_out_of_rotary_layer_types_has_no_position_signal():
    """One global layer, every earlier token permuted: the last position,
    which sees them all, reads the same without rotary and another thing with."""
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 64)
    perm = jnp.concatenate([jax.random.permutation(jax.random.PRNGKey(2), 31), jnp.array([31])])
    plain = _config(layer_types=("full_attention",), rotary_layer_types=(), init_scale=0.2)
    np.testing.assert_allclose(_last_logits(plain, ids[:, perm]), _last_logits(plain, ids), atol=1e-5)
    turned = _config(layer_types=("full_attention",), rotary_layer_types=("full_attention",), init_scale=0.2)
    assert float(jnp.abs(_last_logits(turned, ids[:, perm]) - _last_logits(turned, ids)).max()) > 1e-3


def test_a_window_layer_sees_the_window_and_nothing_before_it():
    """One window layer of 16: the last position's logits do not move with a
    token 16 back, and do with one 15 back; a global layer's move with both."""
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0, 64)
    for kind, moves_far in (("window_attention", False), ("full_attention", True)):
        cfg = _config(layer_types=(kind,), rotary_layer_types=(), init_scale=0.2)
        base = _last_logits(cfg, ids)
        near = _last_logits(cfg, ids.at[0, 47 - 15].set((ids[0, 47 - 15] + 1) % 64))
        far = _last_logits(cfg, ids.at[0, 47 - 16].set((ids[0, 47 - 16] + 1) % 64))
        assert float(jnp.abs(near - base).max()) > 1e-4
        assert (float(jnp.abs(far - base).max()) > 1e-4) == moves_far


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_window_layers_through_the_kernels_are_the_einsum_paths(impl):
    """Head 32 in rows of 256 (the kernels' least), a group of 7, a window that
    cuts across blocks: logits and every gradient on either path."""
    from perceiver_io_tpu.training.tasks import lm_loss_fn

    cfg = _config(head_dim=32, sliding_window=100, init_scale=0.1)
    x = jax.random.randint(jax.random.PRNGKey(0), (2, 257), 0, 64)
    batch = {"input_ids": x[:, :-1], "labels": x[:, 1:], "pad_mask": jnp.zeros((2, 256), bool)}
    _, params = _init(cfg, n=256)
    want = jax.value_and_grad(lm_loss_fn(DecoderLM(cfg, attention_impl="xla")), has_aux=True)(
        params, batch, None)
    got = jax.value_and_grad(lm_loss_fn(DecoderLM(cfg, attention_impl=impl)), has_aux=True)(
        params, batch, None)
    assert float(got[0][0]) == pytest.approx(float(want[0][0]), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))


def test_softmax_over_the_chosen_logits_weighs_and_sums_to_one():
    tokens = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    logits = tokens @ router
    idx, w = hybrid.route(tokens, router, None, 3, True, 1.0, "softmax_topk")
    top, want_idx = jax.lax.top_k(logits, 3)
    assert (idx == want_idx).all()
    np.testing.assert_allclose(w, jax.nn.softmax(top, axis=-1), atol=1e-6)
    np.testing.assert_allclose(w.sum(axis=-1), 1.0, atol=1e-6)
    # normalise has nothing left to do; a scaling factor scales
    _, unnormalised = hybrid.route(tokens, router, None, 3, False, 2.0, "softmax_topk")
    np.testing.assert_allclose(unnormalised, 2.0 * w, atol=1e-6)
    # a bias only chooses: the weights are still a softmax over the chosen logits
    bias = jnp.zeros((8,)).at[5].set(100.0)
    idx_b, w_b = hybrid.route(tokens, router, bias, 3, True, 1.0, "softmax_topk")
    assert (idx_b[:, 0] == 5).all()
    np.testing.assert_allclose(
        w_b, jax.nn.softmax(jnp.take_along_axis(logits, idx_b, axis=-1), axis=-1), atol=1e-6)
    # the sigmoid form is the one it was
    idx_s, w_s = hybrid.route(tokens, router, None, 3, True, 1.0)
    scores = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(scores, idx_s, axis=-1)
    np.testing.assert_allclose(w_s, picked / (picked.sum(axis=-1, keepdims=True) + 1e-6), atol=1e-6)
    with pytest.raises(ValueError, match="router score"):
        hybrid.route(tokens, router, None, 3, True, 1.0, "softmax")


def _layer(**kw):
    return SparseExperts(num_channels=16, hidden_channels=12, router_width=4, num_experts=4, top_k=2,
                         use_expert_bias=False, **kw)


@pytest.mark.parametrize("row_tile", [512, 32], ids=["one_path", "cond"])
def test_router_reads_its_second_input_and_experts_gate_with_relu(monkeypatch, row_tile):
    """By hand: weights from ``seen``, outputs from ``x``; ``relu`` between
    gate and up; the row bound's ``cond`` carries the activation through both
    branches and the backward."""
    monkeypatch.setattr(hybrid, "_ROW_TILE", row_tile)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 16))
    seen = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16))
    layer = _layer(router_score="softmax_topk", activation="relu", init_scale=0.3)
    params = layer.init(jax.random.PRNGKey(2), x)["params"]

    def by_hand(p, x, seen, act):
        logits = seen @ p["router"]
        top, idx = jax.lax.top_k(logits, 2)
        w = jax.nn.softmax(top, axis=-1)
        out = jnp.zeros_like(x)
        for e in range(4):
            w_e = jnp.where(idx == e, w, 0.0).sum(axis=-1)
            out = out + w_e[..., None] * ((act(x @ p["gate"][e]) * (x @ p["up"][e])) @ p["down"][e])
        return out

    out, stats = layer.apply({"params": params}, x, seen)
    np.testing.assert_allclose(out, by_hand(params, x, seen, jax.nn.relu), atol=1e-5)
    assert float(stats[0]) == 2 * 64 * 2
    own, _ = layer.apply({"params": params}, x)
    np.testing.assert_allclose(own, by_hand(params, x, x, jax.nn.relu), atol=1e-5)
    assert float(jnp.abs(own - out).max()) > 1e-2
    silu, _ = _layer(router_score="softmax_topk", init_scale=0.3).apply({"params": params}, x, seen)
    np.testing.assert_allclose(silu, by_hand(params, x, seen, jax.nn.silu), atol=1e-5)
    loss = lambda p, x, seen: jnp.sum(layer.apply({"params": p}, x, seen)[0] ** 2)
    want = jax.grad(lambda p, x, seen: jnp.sum(by_hand(p, x, seen, jax.nn.relu) ** 2), (0, 1, 2))(
        params, x, seen)
    got = jax.grad(loss, (0, 1, 2))(params, x, seen)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4 * max(1.0, float(jnp.abs(b).max())))
    with pytest.raises(ValueError, match="activation"):
        _layer(activation="gelu").init(jax.random.PRNGKey(0), x)


def test_second_input_is_sharded_with_the_tokens_under_a_mesh(devices):
    """Four devices over the batch: every shard routes its own tokens on its
    own rows of the router's input."""
    from jax.sharding import Mesh

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 16))
    seen = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 16))
    layer = _layer(router_score="softmax_topk", activation="relu", init_scale=0.3)
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    want, want_stats = layer.apply({"params": params}, x, seen)
    mesh = Mesh(np.array(devices[:4]).reshape(4, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        got, stats = jax.jit(lambda p, x, seen: layer.apply({"params": p}, x, seen))(params, x, seen)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(stats[0]) == float(want_stats[0])


def test_two_step_fit_through_the_cli_with_flags_alone(tmp_path):
    """``lm fit`` builds and trains the window-and-global stack from flags:
    no environment variable, no side script."""
    from perceiver_io_tpu.observability import default_registry
    from perceiver_io_tpu.scripts.text import lm as lm_script

    argv = [
        "fit", "--data=synthetic", f"--data.dataset_dir={tmp_path}/data", "--data.max_seq_len=64",
        "--data.batch_size=8", "--data.num_train_docs=16", "--data.num_valid_docs=8",
        "--data.doc_chars=512", "--model.num_channels=40", "--model.num_heads=14",
        "--model.num_kv_heads=2", "--model.head_dim=8", "--model.qk_norm=false",
        "--model.layer_types=full_attention,window_attention,window_attention",
        "--model.rotary_layer_types=window_attention", "--model.sliding_window=16",
        "--model.num_dense_layers=0", "--model.expert_channels=24", "--model.router_width=8",
        "--model.num_experts=8", "--model.experts_per_token=3", "--model.use_expert_bias=false",
        "--model.router_score=softmax_topk", "--model.expert_activation=relu",
        "--model.router_input=operator", "--model.tie_word_embeddings=false",
        "--model.activation_checkpointing=true", "--trainer.max_steps=2",
        "--trainer.log_every_n_steps=1", "--trainer.val_check_interval=100",
        f"--trainer.default_root_dir={tmp_path}/logs", "--trainer.enable_checkpointing=false",
        "--trainer.enable_tensorboard=false",
    ]
    state = lm_script.main(argv)
    assert int(state.step) == 2
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["trainer_moe_assignments_held"] == 3 * 8 * 64 * 3  # three layers, 3 a token
    assert gauges["trainer_moe_layers_bounded"] == 3.0
    params = state.params
    assert "q_norm" not in params["layers_1"]["attention"] and "expert_bias" not in params["layers_1"]["moe"]
    assert params["layers_1"]["attention"]["q_proj"]["kernel"].shape == (40, 112)
    assert os.path.exists(os.path.join(tmp_path, "logs", "metrics.jsonl"))

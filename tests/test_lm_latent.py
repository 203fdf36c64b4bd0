"""The ``lm`` family's latent attention, shared expert, untied head and
prediction module (``models/core/modules.py::LatentAttention``,
``models/text/lm.py``, docs/lm.md) on the CPU: the operator against a naive
einsum of its equations, the config's checks and defaults, the partition
rules for the new leaves, and a two-step ``fit`` through the CLI with flags
alone. Program against the benchmark's plain reference, the share test and the
second loss's shift are ``tests/benchmarks/test_bench_glm4_moe_lite.py``'s."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from perceiver_io_tpu.models.core.modules import LatentAttention
from perceiver_io_tpu.models.text.lm import DecoderLM, DecoderLMConfig
from perceiver_io_tpu.ops.position import RotaryEmbedding, frequency_position_encoding, positions

H, C, RQ, RKV, DN, DR, DV = 4, 40, 24, 16, 24, 8, 32


def _operator(impl="xla"):
    return LatentAttention(
        num_heads=H, num_input_channels=C, q_lora_rank=RQ, kv_lora_rank=RKV, qk_nope_head_dim=DN,
        qk_rope_head_dim=DR, v_head_dim=DV, init_scale=0.3, attention_impl=impl)


def _rms(x, gain, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _naive(p, u, angles):
    """The equations of docs/lm.md one by one, a head's channels in the
    program's order ``[rotary | no-position]``, adjacent-pair rotary."""
    b, n, _ = u.shape

    def rope(x):  # (..., n, DR) by position: pairs (2i, 2i + 1)
        x1, x2 = x[..., 0::2], x[..., 1::2]
        cos, sin = jnp.cos(angles[0, :, 0::2]), jnp.sin(angles[0, :, 0::2])
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)

    cq = _rms(u @ p["q_a_proj"]["kernel"], p["q_a_norm"]["scale"])
    q = (cq @ p["q_b_proj"]["kernel"]).reshape(b, n, H, DR + DN).transpose(0, 2, 1, 3)
    a = u @ p["kv_a_proj"]["kernel"]
    ckv, k_rot = _rms(a[..., :RKV], p["kv_a_norm"]["scale"]), a[..., RKV:]
    kv = (ckv @ p["kv_b_proj"]["kernel"]).reshape(b, n, H, DN + DV).transpose(0, 2, 1, 3)
    out = []
    for head in range(H):
        q_h = jnp.concatenate([rope(q[:, head, :, :DR]), q[:, head, :, DR:]], axis=-1)
        k_h = jnp.concatenate([rope(k_rot), kv[:, head, :, :DN]], axis=-1)  # the same rotary key
        scores = jnp.einsum("bid,bjd->bij", q_h, k_h) / np.sqrt(DR + DN)
        scores = jnp.where(jnp.tril(jnp.ones((n, n), bool)), scores, -jnp.inf)
        out.append(jnp.einsum("bij,bjd->bid", jax.nn.softmax(scores, axis=-1), kv[:, head, :, DN:]))
    return jnp.concatenate(out, axis=-1) @ p["o_proj"]["kernel"]


def test_latent_attention_is_the_naive_einsum_of_its_equations():
    b, n = 2, 48
    u = jax.random.normal(jax.random.PRNGKey(0), (b, n, C))
    angles = frequency_position_encoding(positions(b, n), DR, 1e6)
    rot = RotaryEmbedding(angles)
    with jax.default_matmul_precision("highest"):
        op = _operator()
        params = op.init(jax.random.PRNGKey(1), u, None, rot)["params"]
        assert {k: v["kernel"].shape for k, v in params.items() if "kernel" in v} == {
            "q_a_proj": (C, RQ), "q_b_proj": (RQ, H * (DN + DR)), "kv_a_proj": (C, RKV + DR),
            "kv_b_proj": (RKV, H * (DN + DV)), "o_proj": (H * DV, C)}
        assert params["q_a_norm"]["scale"].shape == (RQ,) and params["kv_a_norm"]["scale"].shape == (RKV,)
        got = op.apply({"params": params}, u, None, rot)
        np.testing.assert_allclose(got, _naive(params, u, angles), atol=2e-5, rtol=1e-4)
        # causal: a later position changes no earlier output; rotary: positions matter
        moved = op.apply({"params": params}, u.at[:, -1].add(1.0), None, rot)
        np.testing.assert_allclose(moved[:, :-1], got[:, :-1], atol=1e-6)
        assert float(jnp.abs(op.apply({"params": params}, u, None, None) - got).max()) > 1e-3


def test_latent_attention_takes_the_kernel_path_where_the_shape_allows(monkeypatch):
    """``flash`` runs the Pallas kernels (interpreted on the CPU) at 256-wide
    query-key and value heads and agrees with the einsum path; ``auto`` on a
    TPU backend would take it without counting a fallback."""
    from perceiver_io_tpu.observability import default_registry
    from perceiver_io_tpu.ops import attention

    b, n = 1, 128
    u = jax.random.normal(jax.random.PRNGKey(0), (b, n, C))
    rot = RotaryEmbedding(frequency_position_encoding(positions(b, n), DR, 1e6))
    params = _operator().init(jax.random.PRNGKey(1), u, None, rot)["params"]
    want = _operator().apply({"params": params}, u, None, rot)
    np.testing.assert_allclose(
        _operator("flash").apply({"params": params}, u, None, rot), want, atol=2e-3, rtol=2e-3)
    before = default_registry().snapshot().get("counters", {}).get("attention_einsum_fallback_total", 0.0)
    monkeypatch.setattr(attention, "_flash_eligible", lambda rate: True)  # what a TPU backend answers
    np.testing.assert_allclose(
        _operator("auto").apply({"params": params}, u, None, rot), want, atol=2e-3, rtol=2e-3)
    after = default_registry().snapshot().get("counters", {}).get("attention_einsum_fallback_total", 0.0)
    assert after == before


LATENT = dict(
    vocab_size=64, max_seq_len=256, num_channels=C, num_heads=H, layer_types=("latent_attention",) * 2,
    num_dense_layers=1, mlp_channels=96, expert_channels=48, router_width=8, num_experts=8,
    experts_per_token=2, num_shared_experts=1, routed_scaling_factor=1.8, q_lora_rank=RQ,
    kv_lora_rank=RKV, qk_nope_head_dim=DN, qk_rope_head_dim=DR, v_head_dim=DV,
    tie_word_embeddings=False, num_nextn_predict_layers=1)


def test_config_checks_hold_only_for_the_operators_that_need_them():
    DecoderLMConfig(**LATENT)  # 40 channels on 4 heads... and 2048 on 20 is no whole number
    DecoderLMConfig(**{**LATENT, "num_channels": 42, "num_heads": 5})
    with pytest.raises(ValueError, match="divisible"):
        DecoderLMConfig(num_channels=42, num_heads=5, layer_types=("conv", "full_attention"))
    with pytest.raises(ValueError, match="latent_attention needs"):
        DecoderLMConfig(layer_types=("latent_attention",))
    with pytest.raises(ValueError, match="qk_rope_head_dim even"):
        DecoderLMConfig(**{**LATENT, "qk_rope_head_dim": 7})
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        DecoderLMConfig(**{**LATENT, "num_nextn_predict_layers": 2})
    with pytest.raises(ValueError, match="known"):
        DecoderLMConfig(layer_types=("sliding_attention",))


def test_defaults_leave_the_models_the_family_already_built_as_they_were():
    """No shared expert, a tied head, no prediction module and no latent
    width unless asked for: the parameter tree of a model built from the
    defaults has none of the new leaves, and ``next_ids`` is refused."""
    cfg = DecoderLMConfig()
    new = {f.name: f.default for f in dataclasses.fields(cfg)}
    assert (new["num_shared_experts"], new["tie_word_embeddings"], new["num_nextn_predict_layers"]) == (0, True, 0)
    assert all(new[k] == 0 for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                                      "qk_rope_head_dim", "v_head_dim"))
    ids = jnp.zeros((1, 16), jnp.int32)
    tree = jax.eval_shape(lambda: DecoderLM(cfg).init(jax.random.PRNGKey(0), ids))["params"]
    assert set(tree) == {"embed", "out_norm"} | {f"layers_{i}" for i in range(4)}
    assert set(tree["layers_1"]) == {"operator_norm", "conv", "ffn_norm", "moe"}
    with pytest.raises(ValueError, match="next_ids"):
        DecoderLM(cfg).init(jax.random.PRNGKey(0), ids, next_ids=ids)


def test_untied_head_module_and_shared_expert_are_leaves_of_their_own():
    cfg = DecoderLMConfig(**LATENT)
    ids = jnp.zeros((1, 16), jnp.int32)
    model = DecoderLM(cfg)
    tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, next_ids=ids))["params"]
    assert tree["head"]["kernel"].shape == (C, 64) and "output_adapter" not in tree
    assert set(tree["layers_1"]) == {"operator_norm", "attention", "ffn_norm", "moe", "shared_expert"}
    assert "shared_expert" not in tree["layers_0"] and "mlp" in tree["layers_0"]
    assert set(tree["mtp"]) == {"embed_norm", "hidden_norm", "eh_proj", "layer", "out_norm"}
    assert tree["mtp"]["eh_proj"]["kernel"].shape == (2 * C, C)
    assert set(tree["mtp"]["layer"]) == set(tree["layers_1"])
    params = model.init(jax.random.PRNGKey(0), ids, next_ids=ids)["params"]
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    alone = model.apply({"params": params}, x)
    (main, second), stats = model.apply({"params": params}, x, next_ids=x, return_stats=True)
    assert main.shape == second.shape == (2, 32, 64)
    np.testing.assert_allclose(main, alone, atol=1e-6)
    assert float(stats["moe_layers_bounded"]) == 2.0  # the expert layer and the module's
    # the module reads the next tokens: other next tokens, other second logits, same first
    (same, other), _ = model.apply({"params": params}, x, next_ids=(x + 1) % 64, return_stats=True)
    np.testing.assert_allclose(same, main, atol=1e-6)
    assert float(jnp.abs(other - second).max()) > 1e-4


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
def test_recomputation_by_layer_covers_the_modules_layer(remat):
    """With ``activation_checkpointing`` the module's layer is a ``remat`` too
    (its forward runs again in the backward pass: a ``rematted_computation`` under
    ``mtp``), and loss and gradients are what they are without."""
    from perceiver_io_tpu.training.tasks import lm_loss_fn

    cfg = DecoderLMConfig(**{**LATENT, "activation_checkpointing": remat})
    model = DecoderLM(cfg)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 64)
    batch = {"input_ids": x[:, :-1], "labels": x[:, 1:], "pad_mask": jnp.zeros((2, 32), bool)}
    params = DecoderLM(DecoderLMConfig(**LATENT)).init(
        jax.random.PRNGKey(0), x[:1, :-1], next_ids=x[:1, 1:])["params"]
    grad = jax.jit(jax.value_and_grad(lambda p: lm_loss_fn(model)(p, batch, None)[0]))
    text = grad.lower(params).as_text(debug_info=True)
    assert ("mtp/checkpoint/rematted_computation/layer" in text) == remat
    loss, grads = grad(params)
    plain = DecoderLM(DecoderLMConfig(**LATENT))
    want, want_grads = jax.value_and_grad(lambda p: lm_loss_fn(plain)(p, batch, None)[0])(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, atol=1e-6)


#: a convolution layer and grouped-query attention (4 heads of 32 on 2), the flash kernels' least shapes
GROUPED = dict(
    vocab_size=64, max_seq_len=256, num_channels=128, num_heads=4, num_kv_heads=2,
    layer_types=("conv", "full_attention"), num_dense_layers=1, mlp_channels=96, expert_channels=48,
    router_width=8, num_experts=8, experts_per_token=2)


@pytest.mark.parametrize("base", [GROUPED, LATENT], ids=["full_attention", "latent_attention"])
def test_recomputation_on_the_kernel_path_keeps_the_forwards_outputs_and_the_gradients(base):
    """On the flash path (interpreted here) a recomputed layer keeps the
    forward kernel's output and log-sum-exp: the gradient's trace holds as
    many ``flash_fwd`` calls with ``activation_checkpointing`` as without (one
    an attention layer, the prediction module's too), and loss and every
    gradient leaf are what they are without."""
    from perceiver_io_tpu.training.tasks import lm_loss_fn

    x = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 64)
    batch = {"input_ids": x[:, :-1], "labels": x[:, 1:], "pad_mask": jnp.zeros((2, 128), bool)}
    more = {"next_ids": x[:1, 1:]} if base.get("num_nextn_predict_layers") else {}
    params = DecoderLM(DecoderLMConfig(**base)).init(jax.random.PRNGKey(0), x[:1, :-1], **more)["params"]
    attentions = sum(t != "conv" for t in base["layer_types"]) + base.get("num_nextn_predict_layers", 0)

    def run(remat):
        model = DecoderLM(DecoderLMConfig(**{**base, "activation_checkpointing": remat}), attention_impl="flash")
        grad = jax.value_and_grad(lambda p: lm_loss_fn(model)(p, batch, None)[0])
        # a kernel is traced for the TPU and for the interpreter: two a call
        assert str(jax.make_jaxpr(grad)(params)).count("name=flash_fwd") == 2 * attentions
        return jax.jit(grad)(params)

    (want, want_grads), (loss, grads) = run(False), run(True)
    assert float(loss) == float(want)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_partition_rules_for_the_new_leaves(devices):
    """Tensor parallelism splits the latent up-projections by head (their
    columns), ``o_proj`` by row, the shared expert as a gated MLP; the low-rank
    down-projections, the latent norms and the module's projection are left to
    FSDP; the stacked experts' first dimension is never split."""
    from perceiver_io_tpu.parallel import MeshConfig, make_mesh
    from perceiver_io_tpu.parallel.partition import infer_param_specs

    cfg = DecoderLMConfig(**{**LATENT, "num_channels": 64, "q_lora_rank": 32, "kv_lora_rank": 32})
    ids = jnp.zeros((1, 16), jnp.int32)
    shapes = jax.eval_shape(lambda: DecoderLM(cfg).init(jax.random.PRNGKey(0), ids, next_ids=ids))["params"]
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, model=2), devices=devices)
    specs = infer_param_specs(shapes, mesh, min_fsdp_size=0)
    for layer in (specs["layers_1"], specs["mtp"]["layer"]):
        attn = layer["attention"]
        assert attn["q_b_proj"]["kernel"] == P("fsdp", "model")
        assert attn["kv_b_proj"]["kernel"] == P("fsdp", "model")
        assert attn["o_proj"]["kernel"] == P("model", "fsdp")
        assert "model" not in attn["q_a_proj"]["kernel"] and "model" not in attn["kv_a_proj"]["kernel"]
        assert attn["q_a_norm"]["scale"] == P("fsdp") and "model" not in attn["kv_a_norm"]["scale"]
        assert layer["shared_expert"]["gate"]["kernel"] == P("fsdp", "model")
        assert layer["shared_expert"]["up"]["kernel"] == P("fsdp", "model")
        assert layer["shared_expert"]["down"]["kernel"] == P("model", "fsdp")
        assert layer["moe"]["gate"][0] is None and layer["moe"]["down"][0] is None
    assert "model" not in specs["mtp"]["eh_proj"]["kernel"] and "model" not in specs["head"]["kernel"]


def test_two_step_fit_through_the_cli_with_flags_alone(tmp_path):
    """``lm fit`` trains the architecture from ``--model.*`` flags: both loss
    terms are gauges, their weighted sum the loss, and the module's expert
    layer counts among the bounded layers."""
    from perceiver_io_tpu.observability import default_registry
    from perceiver_io_tpu.scripts.text import lm as lm_script

    argv = [
        "fit", "--data=synthetic", f"--data.dataset_dir={tmp_path}/data", "--data.max_seq_len=64",
        "--data.batch_size=8", "--data.num_train_docs=16", "--data.num_valid_docs=8",
        "--data.doc_chars=512", f"--model.num_channels={C}",
        f"--model.num_heads={H}", "--model.layer_types=latent_attention,latent_attention",
        "--model.num_dense_layers=1", "--model.mlp_channels=48", "--model.expert_channels=24",
        "--model.router_width=8", "--model.num_experts=4", "--model.expert_offset=2",
        "--model.experts_per_token=2", "--model.num_shared_experts=1",
        "--model.routed_scaling_factor=1.8", f"--model.q_lora_rank={RQ}", f"--model.kv_lora_rank={RKV}",
        f"--model.qk_nope_head_dim={DN}", f"--model.qk_rope_head_dim={DR}", f"--model.v_head_dim={DV}",
        "--model.tie_word_embeddings=false", "--model.num_nextn_predict_layers=1",
        "--model.mtp_loss_weight=0.5", "--model.activation_checkpointing=true",
        "--trainer.max_steps=2", "--trainer.log_every_n_steps=1", "--trainer.val_check_interval=100",
        f"--trainer.default_root_dir={tmp_path}/logs", "--trainer.enable_checkpointing=false",
        "--trainer.enable_tensorboard=false",
    ]
    state = lm_script.main(argv)
    assert int(state.step) == 2
    assert state.params["mtp"]["eh_proj"]["kernel"].shape == (2 * C, C)
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["trainer_loss"] == pytest.approx(
        gauges["trainer_lm_loss"] + 0.5 * gauges["trainer_mtp_loss"], rel=1e-5)
    assert gauges["trainer_moe_layers_bounded"] == 2.0
    with open(os.path.join(tmp_path, "logs", "metrics.jsonl")) as f:
        logged = f.read()
    assert "train/lm_loss" in logged and "train/mtp_loss" in logged

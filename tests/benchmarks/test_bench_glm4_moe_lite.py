"""The benchmark's side of the GLM-4.7-Flash cell: the adapter's layout both
ways over every leaf, program against reference at a small size (logits of
both heads, loss, every leaf's gradient), a toy cell through the harness and
the ``train`` driver on the CPU, the planted faults and the control, the
shares of an expert layer with its shared expert, the second loss's shift,
the real configuration's count, the roofline's count against XLA's, and the
new readers on a hand-built trace."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, scopes, trace_reduce
from benchmarks.adapters import lm_latent as adapter
from benchmarks.reference import blocks
from benchmarks.reference import glm4_moe_lite as ref
from benchmarks.rooflines import glm4_moe_lite as glm_work
from benchmarks.rooflines import grouped, work
from conftest import ROOT, build_toy_root

FILES = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "glm-4.7-flash-ep8.json")

#: hidden 40 on 4 heads (no whole number of channels a head: the widths are
#: the latent ones), experts 2..5 of 8 held, one dense layer, two expert
#: layers and the prediction module
TOY = {
    "name": "toy-glm", "reference": "glm4_moe_lite", "program": "lm_latent",
    "hidden_size": 40, "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 32, "n_routed_experts": 4, "router_width": 8,
    "expert_offset": 2, "num_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True, "first_k_dense_replace": 1,
    "num_layers": 3, "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
    "tie_word_embeddings": False, "vocab_size": 262, "max_position_embeddings": 512,
}
#: the toy cell's limits, set as the real ones are: about three times what
#: sound runs of the toy program (bfloat16) read on the CPU over three seeds
#: (loss 1.2e-5, first gradient 0.0019, change 0.0021) and below what the
#: control and every planted fault read (the test below)
TOY_LIMITS = {"loss1": 4e-5, "loss2": 4e-5, "loss3": 4e-5, "grad_leaf": 0.006, "delta_leaf": 0.006}
SEED = 3


def _batch(rows=2, n=128, vocab=64):
    x = jax.random.randint(jax.random.PRNGKey(0), (rows, n + 1), 0, vocab)
    return {"input_ids": x[:, :-1], "labels": x[:, 1:], "pad_mask": jnp.zeros((rows, n), bool)}


def _program(config, dtype=jnp.float32):
    from perceiver_io_tpu.models.text.lm import DecoderLM

    return DecoderLM(adapter.model_config(config), dtype=dtype, attention_impl="xla")


def test_adapter_lays_every_leaf_out_and_reads_it_back():
    names = sorted(ref.param_shapes(TOY))
    flat = jax.jit(lambda key: ref.init_params(key, TOY))(jax.random.PRNGKey(5))
    tree = adapter.common.seeded_tree(ref, TOY, adapter.path_of, 5)
    ids = jnp.zeros((1, 8), jnp.int32)
    init = jax.eval_shape(
        lambda: _program(TOY).init(jax.random.PRNGKey(0), ids, next_ids=ids))["params"]
    assert jax.tree_util.tree_structure(init) == jax.tree_util.tree_structure(tree)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(init), jax.tree_util.tree_leaves(tree)))
    back = adapter.common.leaves_by_name(tree, names, adapter.path_of)
    assert sorted(back) == names and len(names) == len(jax.tree_util.tree_leaves(tree))
    for name in names:
        assert (back[name] == flat[name]).all(), name
    # the columns of q_b and the rotary columns of kv_a really are reordered
    attn = tree["mtp"]["layer"]["attention"]
    assert not (attn["q_b_proj"]["kernel"] == flat["mtp.layer.attn.q_b.w"]).all()
    assert not (attn["kv_a_proj"]["kernel"] == flat["mtp.layer.attn.kv_a.w"]).all()
    assert (attn["kv_a_proj"]["kernel"][:, :16] == flat["mtp.layer.attn.kv_a.w"][:, :16]).all()
    model = adapter.model_config(TOY)
    assert model.layer_types == ("latent_attention",) * 3 and model.num_dense_layers == 1
    assert (model.expert_offset, model.num_experts, model.num_shared_experts) == (2, 4, 1)
    assert not model.tie_word_embeddings and model.num_nextn_predict_layers == 1


def test_both_heads_the_loss_and_every_gradient_match_the_reference_in_float32():
    small = {**TOY, "vocab_size": 64}
    batch = _batch()
    with jax.default_matmul_precision("highest"):
        p_ref = ref.init_params(jax.random.PRNGKey(SEED), small)
        tree = adapter.common.seeded_tree(ref, small, adapter.path_of, SEED)
        model = _program(small)
        main, second = model.apply({"params": tree}, batch["input_ids"], next_ids=batch["labels"])
        np.testing.assert_allclose(main, ref.logits(p_ref, small, batch["input_ids"]), atol=1e-5)
        np.testing.assert_allclose(
            second, ref.mtp_logits(p_ref, small, batch["input_ids"], batch["labels"]), atol=1e-5)
        from perceiver_io_tpu.training.tasks import lm_loss_fn

        def reference_loss(p):
            total, count = ref.train_nll(p, small, batch)
            return total / count

        loss_r, grads_r = jax.value_and_grad(reference_loss)(p_ref)
        (loss_p, metrics), grads_p = jax.value_and_grad(lm_loss_fn(model), has_aux=True)(tree, batch, None)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5
    assert float(loss_p) == pytest.approx(
        float(metrics["lm_loss"]) + 0.3 * float(metrics["mtp_loss"]), abs=1e-6)
    grads_p = adapter.common.leaves_by_name(grads_p, sorted(p_ref), adapter.path_of)
    for name, g in grads_r.items():
        np.testing.assert_allclose(grads_p[name], g, atol=1e-5 * max(1.0, float(jnp.abs(g).max())),
                                   err_msg=name)
    assert float(jnp.abs(grads_r["layer.1.moe.bias"]).max()) == 0.0  # it only chooses
    assert float(jnp.abs(grads_r["mtp.eh.w"]).max()) > 0.0
    assert float(metrics["moe_layers_bounded"]) == 3.0  # two layers and the module's


def test_reference_in_blocks_slices_and_chunks_is_the_reference_at_once(monkeypatch):
    """The loops that make the real size fit (blocks of queries in a head,
    slices of the dense feed-forward's hidden channels, chunks of positions in
    the losses) and the recomputation change no number: small blocks against
    one block, with and without ``RECOMPUTE``, as compiled loops and unrolled."""
    small = {**TOY, "vocab_size": 64}
    batch = _batch()
    p = ref.init_params(jax.random.PRNGKey(SEED), small)
    loss = lambda q: ref.train_nll(q, small, batch)[0]
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(loss)(p)
        monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
        monkeypatch.setattr(ref, "DENSE_SLICE", 32)
        monkeypatch.setattr(ref, "LOSS_CHUNK", 32)
        for recompute, unroll in ((True, False), (False, False), (False, True)):
            monkeypatch.setattr(ref, "RECOMPUTE", recompute)
            monkeypatch.setattr(ref, "UNROLL_LOOPS", unroll)
            got, grads = jax.value_and_grad(loss)(p)
            assert float(got) == pytest.approx(float(want), rel=1e-6)
            for name, g in want_grads.items():
                np.testing.assert_allclose(grads[name], g, atol=2e-6 * max(1.0, float(jnp.abs(g).max())),
                                           err_msg=name)


def test_reference_loss_in_blocks_of_rows_is_the_whole_batchs():
    """``loss_and_grads`` sums blocks' totals over the batch's label count:
    with packed rows the second term's scaling is the same in every block."""
    from benchmarks.reference import training

    small = {**TOY, "vocab_size": 64}
    batch = {k: np.asarray(v) for k, v in _batch(rows=4).items()}
    p = ref.init_params(jax.random.PRNGKey(SEED), small)
    block = training.make_block(ref, small)
    whole, _ = training.loss_and_grads(block, p, batch, None, 4)
    halves, _ = training.loss_and_grads(block, p, batch, None, 2)
    assert float(whole) == pytest.approx(float(halves), rel=1e-6)


def test_second_loss_is_shifted_by_one_more_and_a_rows_last_position_has_no_label():
    """Packed row: the module's label at ``i`` is ``labels[i + 1]`` and the last
    position has none: the second term is the mean over the first ``n - 1``
    positions' logits against the labels from the second on."""
    small = {**TOY, "vocab_size": 64}
    batch = _batch(rows=1, n=32)
    p = ref.init_params(jax.random.PRNGKey(SEED), small)
    tree = adapter.common.seeded_tree(ref, small, adapter.path_of, SEED)
    from perceiver_io_tpu.training.tasks import lm_loss_fn

    loss_fn = lm_loss_fn(_program(small))
    _, first = loss_fn(tree, batch, None)
    logits = ref.mtp_logits(p, small, batch["input_ids"], batch["labels"])
    want, count = blocks.token_nll(logits[:, :-1], batch["labels"][:, 1:])
    assert int(count) == 31
    assert float(first["mtp_loss"]) == pytest.approx(float(want) / 31, rel=1e-4)
    # a padded position's label is ignored, and so is the module's label there
    # (the reference pads no attention, so the program is held to its own logits)
    padded = {**batch, "pad_mask": batch["pad_mask"].at[0, :4].set(True)}
    _, metrics = loss_fn(tree, padded, None)
    main, second = _program(small).apply(
        {"params": tree}, batch["input_ids"], pad_mask=padded["pad_mask"], next_ids=batch["labels"])
    want_main, main_count = blocks.token_nll(main[:, 4:], batch["labels"][:, 4:])
    want_second, second_count = blocks.token_nll(second[:, 4:-1], batch["labels"][:, 5:])
    assert (int(main_count), int(second_count)) == (28, 27)
    assert float(metrics["lm_loss"]) == pytest.approx(float(want_main) / 28, rel=1e-4)
    assert float(metrics["mtp_loss"]) == pytest.approx(float(want_second) / 27, rel=1e-4)
    total, count = ref.train_nll(p, small, padded)  # the reference counts the same labels
    assert int(count) == 28 and np.isfinite(float(total))


def _expert_layer_params(key, c=40, f=48, e=8):
    shapes = {"moe.router.w": (c, e), "moe.bias": (e,), "moe.gate": (e, c, f), "moe.up": (e, c, f),
              "moe.down": (e, f, c), "shared.gate.w": (c, f), "shared.up.w": (c, f),
              "shared.down.w": (f, c), "op_norm.g": (c,), "ffn_norm.g": (c,)}
    p = blocks.normal_params(key, shapes, 0.3)
    return {**p, "moe.bias": 5.0 * p["moe.bias"]}


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """8 experts in shares of 2 through the program's ``DecoderLayer``'s
    feed-forward: each share's output is its routed part plus the shared
    expert, which every chip computes alike; the four routed parts and the
    shared expert counted once are the uncut reference's whole layer."""
    from perceiver_io_tpu.models.core.hybrid import GatedMLP, SparseExperts

    cfg = {**TOY, "n_routed_experts": 8, "expert_offset": 0}
    p = _expert_layer_params(jax.random.PRNGKey(1))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 96, 40))
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(u, p, "moe", cfg) + ref.shared_expert(u, p, "shared")
        shared = GatedMLP(40, 48).apply({"params": {
            k: {"kernel": p[f"shared.{k}.w"]} for k in ("gate", "up", "down")}}, u)
        np.testing.assert_allclose(shared, ref.shared_expert(u, p, "shared"), atol=1e-5, rtol=1e-5)
        routed, pairs = [], 0.0
        for share in range(4):
            take = slice(2 * share, 2 * share + 2)
            held = {"router": p["moe.router.w"], "expert_bias": p["moe.bias"],
                    "gate": p["moe.gate"][take], "up": p["moe.up"][take], "down": p["moe.down"][take]}
            layer = SparseExperts(num_channels=40, hidden_channels=48, router_width=8, num_experts=2,
                                  expert_offset=2 * share, top_k=2, routed_scaling_factor=1.8)
            out, stats = layer.apply({"params": held}, u)
            routed.append(out)
            pairs += float(stats[0])
            cut = {**p, "moe.gate": p["moe.gate"][take], "moe.up": p["moe.up"][take],
                   "moe.down": p["moe.down"][take]}
            alone = ref.experts(u, cut, "moe", {**cfg, "n_routed_experts": 2, "expert_offset": 2 * share})
            np.testing.assert_allclose(out, alone, atol=1e-5, rtol=1e-5)
    assert pairs == 2 * 96 * 2  # every pair is computed by exactly one share
    np.testing.assert_allclose(sum(routed) + shared, whole, atol=2e-5, rtol=1e-5)
    # counted in every share, the shared expert would be there four times
    assert float(jnp.abs(sum(routed) + 4 * shared - whole).max()) > 0.1


def build_toy_glm_root(tmp_path) -> tuple:
    """The toy benchmark with a further cell, ``toy-glm-train``, and the expert
    layers' and this architecture's metrics listed for it."""
    root, files = build_toy_root(tmp_path)
    with open(os.path.join(root, "cfg", "toy-glm.json"), "w") as f:
        json.dump(TOY, f)
    mix = {"driver": "train", "feed": {"task": "clm", "batch": 8, "seq_len": 128, "corpus_tokens": 20000},
           "fit": {"trainer": {"max_steps": 100000, "enable_tensorboard": False},
                   "model": {"activation_checkpointing": True}},
           "warmup_steps": 1, "trace_steps": 2, "reference_rows": 2,
           "trace": {"step_module": "jit_step"}, "limits": TOY_LIMITS}
    with open(os.path.join(files, "traffic", "mixes", "toy-fit-glm.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-glm", "source": "toy", "reduced": [],
                             "file": "cfg/toy-glm.json", "why": "toy"})
    bench["workloads"].append({"name": "toy-glm-train", "config": "toy-glm",
                               "traffic": "toy-fit-glm", "chips": 1, "why": "toy"})
    bench["end_to_end"][0]["workloads"].append("toy-glm-train")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)["per_layer"]
    for metric in real:
        if "glm47flash-train-8k" in metric.get("workloads", ()):
            bench["per_layer"].append({**metric, "workloads": ["toy-glm-train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, files


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_toy_cell_runs_through_the_driver_and_agrees_with_the_reference(tmp_path, trace):
    """``drivers/train.py`` end to end: the program's checked steps (loss of
    both terms, first gradient by leaf, each leaf's change) against
    ``reference_readings`` through ``compare``, experts 2..5 of 8 held."""
    from perceiver_io_tpu.observability import default_registry

    root, files = build_toy_glm_root(tmp_path)
    result = harness.run_cell(root, "toy-glm-train", 2**31 + 33, 0.3, trace,
                              files_dir=files, need_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["window"]["compiles_in_window"] == 0
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["trainer_loss"] == pytest.approx(
        gauges["trainer_lm_loss"] + 0.3 * gauges["trainer_mtp_loss"], rel=1e-5)
    if trace:
        # the CPU has no device plane: the trace's readers leave their metrics
        # out; the program's gauges are there
        assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
        assert 0.0 <= result["metrics"]["moe_bounded_layers"]["value"] <= 3.0
        assert result["metrics"]["einsum_fallbacks"]["value"] == 0
        for name in ("latent_proj_device_ms", "latent_glue_device_ms", "shared_expert_device_ms",
                     "mtp_device_ms", "expert_matmul_device_ms"):
            assert name not in result["metrics"]
    else:
        assert result["metrics"]["train_tokens_per_s"]["value"] > 0


FAULTS = {"expert_left_out": {"_skip_experts": (0,)}, "shared_expert_left_out": {"_skip_shared": True},
          "rotary_key_not_rotated": {"_unrotated_key": True}, "second_loss_left_out": {"_skip_mtp_loss": True}}


@pytest.fixture(scope="module")
def exact_readings():
    from benchmarks.drivers import train
    from benchmarks.traffic.train_batches import TrainBatches

    opt = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01,
           "schedule": "constant", "warmup_steps": 0, "training_steps": 10, "min_fraction": 0.0}
    batches = TrainBatches({"task": "clm", "batch": 4, "seq_len": 128, "corpus_tokens": 20000}, 11)
    check = [batches.next_batch() for _ in range(train.CHECK_STEPS)]
    readings = lambda config, rows=2, **more: train.reference_readings(
        ref, config, opt, 0, 11, check, rows, **more)
    return readings, readings(TOY)


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["float8_control", "other_blocks"])
def test_each_planted_fault_and_the_control_read_outside_the_sound_band(exact_readings, fault):
    """The calibration's faults and its control at the toy size: each reads
    above the toy cell's limits by one reading at least; the same reference
    in other blocks of rows reads the same."""
    from benchmarks.drivers import train

    readings, exact = exact_readings
    if fault == "other_blocks":
        assert train.compare(readings(TOY, rows=4), exact)["grad_leaf"] < 1e-4
        return
    if fault == "float8_control":
        found = train.compare(readings(TOY, precision="fp8"), exact)
    else:
        found = train.compare(readings({**TOY, **FAULTS[fault]}), exact)
    over = [n for n, limit in TOY_LIMITS.items() if not found[n] <= limit]
    assert over, found
    if fault not in ("float8_control", "rotary_key_not_rotated"):
        # a part left out is no rounding: the first gradient is far off (the
        # unrotated key reads 0.021 at this size, 128 positions and 8 rotary channels)
        assert found["grad_leaf"] > 10 * TOY_LIMITS["grad_leaf"], found


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


ROOF = {**TOY, "hidden_size": 128, "intermediate_size": 384, "moe_intermediate_size": 96,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
        "v_head_dim": 32, "n_routed_experts": 8, "expert_offset": 0, "vocab_size": 512}


@pytest.mark.parametrize("training", [False, True], ids=["forward", "train"])
def test_roofline_count_against_xla(training, monkeypatch):
    """The reference computes every held expert for every token; the roofline
    counts the rows routed. With every expert held and 2 of 8 a token XLA's
    count of the routed experts' products is 4 times ours, so the comparison
    adds that difference, known from shapes, to ours. Both heads, the
    module's projection and layer and the shared experts are in both."""
    monkeypatch.setattr(ref, "RECOMPUTE", False)  # XLA would count the recomputation
    monkeypatch.setattr(ref, "UNROLL_LOOPS", True)  # and a loop's body once
    ids = jnp.zeros((2, 256), jnp.int32)
    p = jax.eval_shape(lambda: ref.init_params(jax.random.PRNGKey(0), ROOF))
    step_work = glm_work.train_step_work(ROOF, 2, 256)
    tokens, layers = 2 * 256, glm_work.expert_layers(ROOF)
    assert layers == 3 and glm_work.expected_rows(ROOF, tokens) == tokens * 2 * 8 / 8
    assert len(step_work["attentions"]) == 4
    dense_rows = tokens * ROOF["n_routed_experts"] - glm_work.expected_rows(ROOF, tokens)
    extra = layers * grouped.grouped_flops(grouped.expert_products(ROOF, dense_rows), training)

    def fn(q, x):
        return ref.logits(q, ROOF, x).sum() + ref.mtp_logits(q, ROOF, x, x).sum()

    # the two logits functions each run the main layers: take one stack off XLA's
    main_only = lambda q, x: ref.hidden(q, ROOF, x).sum()
    if training:
        ours = work.train_step_flops(step_work, count_masked=True) + extra
        xla = _xla_flops(jax.grad(fn), p, ids) - _xla_flops(jax.grad(main_only), p, ids)
    else:
        ours = work.forward_flops(step_work, count_masked=True) + extra
        xla = _xla_flops(fn, p, ids) - _xla_flops(main_only, p, ids)
    assert abs(ours - xla) / xla < 0.15, (ours, xla)


def test_real_configuration_counts_what_the_issue_counted():
    with open(CONFIG) as f:
        config = json.load(f)
    shapes = ref.param_shapes(config)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == config["parameters"]["total"] == 706_518_848  # ISSUE 33's table
    groups = config["parameters"]["by_group"]
    assert groups["layer.1.attn"] == 21_759_232 and groups["layer.0.mlp"] == 3 * 2048 * 10240
    assert groups["layer.1.experts"] == 75_497_472 and groups["layer.1.shared"] == 9_437_184
    assert groups["emb.tok"] + groups["head.w"] + groups["out_norm.g"] == 79_300_608
    module = sum(v for k, v in groups.items() if k.startswith("mtp."))
    assert module == 115_223_872 and sum(groups.values()) == total
    assert ref.held_layers(config) == [True, False, False, False, False]
    # every number of the catalog's row is in the file under its own key; the
    # cut keys differ from what was published and are listed
    assert config["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    for key, value in config["published"].items():
        assert (config[key] != value) == (key in config["reduced"]), key
    widths = {"hidden_size": 2048, "intermediate_size": 10240, "moe_intermediate_size": 1536,
              "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
              "v_head_dim": 256, "num_attention_heads": 20, "num_experts_per_tok": 4,
              "router_width": 64, "routed_scaling_factor": 1.8, "n_shared_experts": 1}
    assert {k: config[k] for k in widths} == widths
    assert config["num_experts"] == config["n_routed_experts"] == 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    model = adapter.model_config(config, {"activation_checkpointing": True})
    assert model.num_heads * model.v_head_dim == 5120 and model.num_channels % model.num_heads
    step = glm_work.train_step_work(config, 1, 8192)
    assert 31.5e12 < work.train_step_flops(step) < 32e12  # the issue's 31.7 TFLOP at one row
    assert 17.2e12 < 3 * work.matmul_forward_flops(step["matmuls"]) < 17.5e12
    call = dict(b=1, h=20, i=8192, j=8192, dk=256, dv=256, causal=True)
    assert step["attentions"] == [call] * 6
    assert glm_work.expert_layers(config) == 5 and glm_work.expected_rows(config, 8192) == 4096
    with open(os.path.join(ROOT, "benchmarks", "traffic", "mixes", "fit-8k-b1.json")) as f:
        mix = json.load(f)
    assert (mix["feed"]["batch"], mix["feed"]["seq_len"], mix["reference_rows"]) == (1, 8192, 1)
    assert mix["fit"]["optimizer"]["lr"] == 1e-6 and mix["fit"]["lr_scheduler"]["warmup_steps"] == 0
    assert mix["fit"]["model"] == {"activation_checkpointing": True}


MODEL = "jit(step)/jvp(DecoderLM)/layers_1/checkpoint"
BACK = "jit(step)/transpose(jvp(DecoderLM))/layers_1/checkpoint"
MODULE = "jit(step)/jvp(DecoderLM)/mtp"
TABLE = {
    "fusion.1": f"{MODEL}/attention/latent_q/q_a_proj/dot_general",
    "fusion.2": f"{BACK}/attention/latent_kv/kv_a_norm/mul",
    "fusion.3": f"{MODEL}/attention/latent_assemble/rotary/mul",
    "copy.4": f"{BACK}/attention/latent_assemble/transpose",
    "fusion.5": f"{MODEL}/attention/o_proj/dot_general",
    "fusion.6": f"{MODEL}/shared_expert/gate/dot_general",
    "fusion.7": f"{MODEL}/moe/experts/mul",
    "fusion.8": f"{MODULE}/eh_proj/dot_general",
    "fusion.9": f"{MODULE}/layer/checkpoint/attention/latent_q/q_b_proj/dot_general",
    "fusion.10": f"{MODULE}/layer/checkpoint/shared_expert/down/dot_general",
    "fusion.11": "jit(step)/jvp(mtp)/loss/reduce_sum",
    "fusion.12": "",
    "ragged-dot-none.13": "ragged-dot-none",
}
FUSED = {"fusion.12": ["", f"{BACK}/attention/latent_assemble/concatenate"]}
MS = {"fusion.1": 1.0, "fusion.2": 2.0, "fusion.3": 4.0, "copy.4": 8.0, "fusion.5": 16.0,
      "fusion.6": 32.0, "fusion.7": 64.0, "fusion.8": 128.0, "fusion.9": 256.0, "fusion.10": 512.0,
      "fusion.11": 1024.0, "fusion.12": 2048.0, "ragged-dot-none.13": 4096.0}
EXPECTED = {
    "latent_proj_device_ms": 1.0 + 2.0 + 256.0,
    "latent_glue_device_ms": 4.0 + 8.0 + 2048.0,
    "shared_expert_device_ms": 32.0 + 512.0,
    "mtp_device_ms": 128.0 + 256.0 + 512.0 + 1024.0,
    "expert_matmul_device_ms": 64.0 + 4096.0,
}


def _trace(steps=2, devices=1):
    out = []
    for d in range(devices):
        device, t = trace_reduce.DeviceTrace(f"/device:TPU:{d}"), 0.0
        for _ in range(steps):
            start = t
            for instruction, ms in MS.items():
                device.ops.append((f"%{instruction} = bf16[8,128] op(%x)", t, ms * 1e-3))
                t += ms * 1e-3
            device.modules.append(("jit_step(1)", start, t - start))
        out.append(device)
    return trace_reduce.Trace(out, [], 0.0)


def _ctx(trace):
    return {"trace": trace, "cell": {"name": "toy"}, "mix": {"trace": {"step_module": "jit_step"}}}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_phase_reader_sums_its_scopes_and_reads_nothing_from_the_parent(metric, monkeypatch):
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, FUSED))
    read = harness.load_reader(FILES, metric)
    assert read(_ctx(_trace())) == pytest.approx(EXPECTED[metric])
    assert read(_ctx(_trace(devices=4))) == pytest.approx(EXPECTED[metric])
    assert read(_ctx(None)) is None and read(_ctx(_trace(steps=0))) is None
    # the parent's program has the tables and none of these scopes: nothing under them
    bare = {k: v.replace("latent_q/", "").replace("latent_kv/", "").replace("latent_assemble/", "")
            .replace("shared_expert", "mlp").replace("mtp", "layers_2") for k, v in TABLE.items()}
    monkeypatch.setattr(scopes, "tables", lambda cell: (bare, {}))
    if metric != "expert_matmul_device_ms":
        assert read(_ctx(_trace())) == 0.0
    monkeypatch.setattr(scopes, "tables", lambda cell: None)
    assert read(_ctx(_trace())) is None


def test_the_program_names_the_scopes_the_readers_ask_for():
    """The compiled step's ``op_name``s carry ``latent_q``, ``latent_kv``,
    ``latent_assemble``, ``shared_expert`` and ``mtp`` (the module, its
    embedding, its head's product and its loss), and ``shared_expert`` stands
    outside ``experts``."""
    from perceiver_io_tpu.training.tasks import lm_loss_fn

    small = {**TOY, "vocab_size": 64}
    model = _program(small)
    tree = jax.eval_shape(lambda: adapter.common.seeded_tree(ref, small, adapter.path_of, 1))
    batch = jax.eval_shape(_batch)
    text = jax.jit(jax.grad(lambda p, b: lm_loss_fn(model)(p, b, None)[0])).lower(tree, batch).as_text(
        debug_info=True)
    import re

    names = set(re.findall(r'"(jit\([^"]*)"', text))
    found = {s for name in names for s in scopes.scopes_of(name)}
    assert {"latent_q", "latent_kv", "latent_assemble", "shared_expert", "mtp", "experts",
            "router", "loss"} <= found
    with_shared = [scopes.scopes_of(n) for n in names if "shared_expert" in scopes.scopes_of(n)]
    assert with_shared and not any("experts" in s or "moe" in s for s in with_shared)
    under = [scopes.scopes_of(n) for n in names if "mtp" in scopes.scopes_of(n)]
    for part in ("embed", "eh_proj", "layer", "head", "loss"):
        assert any(part in s for s in under), part

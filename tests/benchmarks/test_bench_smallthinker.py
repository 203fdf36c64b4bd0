"""The benchmark's side of the SmallThinker cell: the adapter's layout both
ways over every leaf, program against reference at a small size (logits, loss,
every leaf's gradient; the einsum path and the kernels), the reference in
blocks against the reference at once, a toy cell through the harness and the
``train`` driver on the CPU, the planted faults and the control, the shares of
an expert layer whose router reads another tensor, the real configuration's
count, the band's pairs against a direct count of the mask, and the new
readers on a hand-built trace."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, scopes, trace_reduce
from benchmarks.adapters import lm_window as adapter
from benchmarks.reference import blocks
from benchmarks.reference import smallthinker as ref
from benchmarks.rooflines import smallthinker as st_work
from benchmarks.rooflines import work
from conftest import ROOT, build_toy_root

FILES = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "smallthinker-21b-a3b-ep8.json")

#: hidden 40 under 14 query heads on 2 of 8 channels (a group of 7, and 112
#: query channels on 40 inputs: the head width is its own), a window of 24 in
#: rows of 128, published layers 1..4 of a period of four (window, window,
#: window, global: the period's order is the layouts', not the code's),
#: experts 2..5 of 8 held, 3 a token
TOY = {
    "name": "toy-smallthinker", "reference": "smallthinker", "program": "lm_window",
    "hidden_size": 40, "head_dim": 8, "num_attention_heads": 14, "num_key_value_heads": 2,
    "moe_ffn_hidden_size": 24, "moe_intermediate_size": 24, "moe_num_primary_experts": 4,
    "num_experts": 4, "router_width": 8, "expert_offset": 2, "moe_num_active_primary_experts": 3,
    "num_experts_per_tok": 3, "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_size": 24, "first_layer": 1, "num_layers": 4, "rms_norm_eps": 1e-6,
    "rope_theta": 1500000, "tie_word_embeddings": False, "vocab_size": 262,
    "max_position_embeddings": 512,
}
#: the toy cell's limits, set as the real ones are, between two readings on the
#: CPU: sound runs of the toy program (bfloat16) over three seeds read loss
#: 1.3e-5, first gradient 0.0032-0.0104, change 0.0017-0.0032; the weakest
#: planted fault (rotary on the global layer, 128 positions and 8 channels a
#: head) reads 0.0204 and 0.0226 there, the float8 control 0.0324 and 1.4e-4 of
#: the loss (the test below)
TOY_LIMITS = {"loss1": 4e-5, "loss2": 4e-5, "loss3": 4e-5, "grad_leaf": 0.018, "delta_leaf": 0.008}
SEED = 3


def _batch(rows=2, n=128, vocab=64):
    x = jax.random.randint(jax.random.PRNGKey(0), (rows, n + 1), 0, vocab)
    return {"input_ids": x[:, :-1], "labels": x[:, 1:], "pad_mask": jnp.zeros((rows, n), bool)}


def _program(config, dtype=jnp.float32, impl="xla"):
    from perceiver_io_tpu.models.text.lm import DecoderLM

    return DecoderLM(adapter.model_config(config), dtype=dtype, attention_impl=impl)


def test_adapter_lays_every_leaf_out_and_reads_it_back():
    names = sorted(ref.param_shapes(TOY))
    flat = jax.jit(lambda key: ref.init_params(key, TOY))(jax.random.PRNGKey(5))
    tree = adapter.common.seeded_tree(ref, TOY, adapter.path_of, 5)
    ids = jnp.zeros((1, 8), jnp.int32)
    init = jax.eval_shape(lambda: _program(TOY).init(jax.random.PRNGKey(0), ids))["params"]
    assert jax.tree_util.tree_structure(init) == jax.tree_util.tree_structure(tree)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(init), jax.tree_util.tree_leaves(tree)))
    laid = adapter.common.leaves_by_name(tree, names, adapter.path_of)
    assert sorted(laid) == names and len(names) == len(jax.tree_util.tree_leaves(tree))
    back = adapter.reference_order(laid, TOY)
    for name in names:
        assert (back[name] == flat[name]).all(), name
        # a leaf's norm, which is what the driver reads, is the same in either order
        assert float(jnp.linalg.norm(laid[name])) == pytest.approx(float(jnp.linalg.norm(flat[name])), rel=1e-6)
    # the columns of q and k really are reordered, in every head alike, and nothing else is
    moved = [n for n in names if not (laid[n] == flat[n]).all()]
    assert moved == sorted(f"layer.{i}.attn.{x}.w" for i in range(4) for x in ("q", "k"))
    q = laid["layer.0.attn.q.w"]
    assert (q[:, 0:8:2] == flat["layer.0.attn.q.w"][:, 0:4]).all()
    assert (q[:, 1] == flat["layer.0.attn.q.w"][:, 4]).all() and (q[:, 9] == flat["layer.0.attn.q.w"][:, 12]).all()
    model = adapter.model_config(TOY)
    assert model.layer_types == ("window_attention",) * 3 + ("full_attention",)
    assert model.rotary_layer_types == ("window_attention",) and model.sliding_window == 24
    assert (model.head_dim, model.num_heads, model.num_kv_heads, model.qk_norm) == (8, 14, 2, False)
    assert (model.expert_offset, model.num_experts, model.experts_per_token) == (2, 4, 3)
    assert (model.router_score, model.expert_activation, model.router_input) == (
        "softmax_topk", "relu", "operator")
    assert not model.tie_word_embeddings and not model.use_expert_bias
    with pytest.raises(ValueError, match="rotates some"):
        adapter.layer_kinds({**TOY, "rope_layout": [0, 1, 0, 1, 0, 1, 1, 1]})


#: head 8 on the einsum path; head 32 in rows of 256 through the kernels
#: (interpreted on the CPU), whose blocks a window of 100 cuts across
KERNEL_TOY = {**TOY, "head_dim": 32, "num_attention_heads": 14, "sliding_window_size": 100}


@pytest.mark.parametrize("impl,config,n", [("xla", TOY, 128), ("flash", KERNEL_TOY, 256)])
def test_logits_loss_and_every_gradient_match_the_reference_in_float32(impl, config, n):
    small = {**config, "vocab_size": 64}
    batch = _batch(n=n)
    with jax.default_matmul_precision("highest"):
        p_ref = ref.init_params(jax.random.PRNGKey(SEED), small)
        tree = adapter.common.seeded_tree(ref, small, adapter.path_of, SEED)
        model = _program(small, impl=impl)
        logits = model.apply({"params": tree}, batch["input_ids"])
        np.testing.assert_allclose(logits, ref.logits(p_ref, small, batch["input_ids"]), atol=2e-5)
        from perceiver_io_tpu.training.tasks import lm_loss_fn

        def reference_loss(p):
            total, count = ref.train_nll(p, small, batch)
            return total / count

        loss_r, grads_r = jax.value_and_grad(reference_loss)(p_ref)
        (loss_p, metrics), grads_p = jax.value_and_grad(lm_loss_fn(model), has_aux=True)(tree, batch, None)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5
    names = sorted(p_ref)
    grads_p = adapter.reference_order(
        adapter.common.leaves_by_name(grads_p, names, adapter.path_of), small)
    for name, g in grads_r.items():
        np.testing.assert_allclose(grads_p[name], g, atol=2e-5 * max(1.0, float(jnp.abs(g).max())),
                                   err_msg=name)
    assert float(jnp.abs(grads_r["layer.1.moe.router.w"]).max()) > 0.0  # the weights' softmax
    assert float(metrics["moe_layers_bounded"]) == 4.0


def test_reference_in_blocks_and_chunks_is_the_reference_at_once(monkeypatch):
    """The loops that make the real size fit (blocks of queries in a
    key-value head, chunks of positions in the loss) and the recomputation
    change no number."""
    small = {**TOY, "vocab_size": 64}
    batch = _batch()
    p = ref.init_params(jax.random.PRNGKey(SEED), small)
    loss = lambda q: ref.train_nll(q, small, batch)[0]
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(loss)(p)
        monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
        monkeypatch.setattr(ref, "LOSS_CHUNK", 32)
        for recompute in (True, False):
            monkeypatch.setattr(ref, "RECOMPUTE", recompute)
            got, grads = jax.value_and_grad(loss)(p)
            assert float(got) == pytest.approx(float(want), rel=1e-6)
            for name, g in want_grads.items():
                np.testing.assert_allclose(grads[name], g, atol=2e-6 * max(1.0, float(jnp.abs(g).max())),
                                           err_msg=name)


def test_reference_mask_is_the_band_and_its_global_layer_has_no_position():
    """The mask built from positions, and what the layouts decide: a window
    layer's output at ``t`` does not move with a key ``window`` back, a
    global layer's does; a global layer's scores know no position (the keys
    permuted with their values give the same last row), a rotated one's do."""
    allowed = np.asarray(ref.allowed_keys(jnp.arange(4, 8), 10, 3))
    want = np.zeros((4, 10), bool)
    for r, t in enumerate(range(4, 8)):
        want[r, t - 2:t + 1] = True
    assert (allowed == want).all()
    assert (np.asarray(ref.allowed_keys(jnp.arange(3), 5, None)) == np.tril(np.ones((3, 5), bool))).all()
    assert ref.held_layers(TOY) == [(True, True)] * 3 + [(False, False)]
    p = blocks.layer_params(ref.init_params(jax.random.PRNGKey(1), TOY), "layer.0")
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 40))
    moved = u.at[0, 10].add(1.0)  # position 10 is 30 back from 40, outside a window of 24
    with jax.default_matmul_precision("highest"):
        out = lambda x, rotated, windowed: ref.attention(x, p, "attn", TOY, rotated, windowed)
        assert float(jnp.abs(out(u, True, True)[0, 40] - out(moved, True, True)[0, 40]).max()) == 0.0
        assert float(jnp.abs(out(u, True, True)[0, 30] - out(moved, True, True)[0, 30]).max()) > 1e-4
        assert float(jnp.abs(out(u, False, False)[0, 40] - out(moved, False, False)[0, 40]).max()) > 1e-4
        perm = jnp.concatenate([jnp.arange(62)[::-1], jnp.arange(62, 64)])  # the last row sees all
        plain = float(jnp.abs(out(u[:, perm], False, False)[0, -1] - out(u, False, False)[0, -1]).max())
        turned = float(jnp.abs(out(u[:, perm], True, False)[0, -1] - out(u, True, False)[0, -1]).max())
        assert plain < 1e-6 and turned > 2e-5  # weights of 0.02: the outputs are of the order of 1e-3


def _expert_layer_params(key, c=40, f=24, e=8):
    shapes = {"moe.router.w": (c, e), "moe.gate": (e, c, f), "moe.up": (e, c, f), "moe.down": (e, f, c)}
    return blocks.normal_params(key, shapes, 0.3)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """8 experts in shares of 1 through the program's ``SparseExperts`` with
    the router on another tensor than the experts', a softmax over the chosen
    and ``relu``: the eight parts are the uncut reference's whole layer, and
    every pair is computed by exactly one share."""
    from perceiver_io_tpu.models.core.hybrid import SparseExperts

    cfg = {**TOY, "moe_num_primary_experts": 8, "expert_offset": 0}
    p = _expert_layer_params(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 96, 40))
    seen = jax.random.normal(jax.random.PRNGKey(3), (2, 96, 40))
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(x, seen, p, "moe", cfg)
        parts, pairs = [], 0.0
        for share in range(8):
            take = slice(share, share + 1)
            held = {"router": p["moe.router.w"], "gate": p["moe.gate"][take], "up": p["moe.up"][take],
                    "down": p["moe.down"][take]}
            layer = SparseExperts(
                num_channels=40, hidden_channels=24, router_width=8, num_experts=1,
                expert_offset=share, top_k=3, use_expert_bias=False, router_score="softmax_topk",
                activation="relu")
            out, stats = layer.apply({"params": held}, x, seen)
            parts.append(out)
            pairs += float(stats[0])
            cut = {**p, "moe.gate": p["moe.gate"][take], "moe.up": p["moe.up"][take],
                   "moe.down": p["moe.down"][take]}
            alone = ref.experts(x, seen, cut, "moe", {**cfg, "moe_num_primary_experts": 1, "expert_offset": share})
            np.testing.assert_allclose(out, alone, atol=1e-5, rtol=1e-5)
        assert pairs == 2 * 96 * 3
        np.testing.assert_allclose(sum(parts), whole, atol=2e-5, rtol=1e-5)
        # routed on the experts' own input the layer is another one
        other = ref.experts(x, x, p, "moe", cfg)
        assert float(jnp.abs(other - whole).max()) > 0.05


def build_toy_smallthinker_root(tmp_path) -> tuple:
    """The toy benchmark with a further cell, ``toy-smallthinker-train``, and
    this cell's metrics listed for it."""
    root, files = build_toy_root(tmp_path)
    with open(os.path.join(root, "cfg", "toy-smallthinker.json"), "w") as f:
        json.dump(TOY, f)
    mix = {"driver": "train", "feed": {"task": "clm", "batch": 8, "seq_len": 128, "corpus_tokens": 20000},
           "fit": {"trainer": {"max_steps": 100000, "enable_tensorboard": False},
                   "model": {"activation_checkpointing": True}},
           "warmup_steps": 1, "trace_steps": 2, "reference_rows": 2,
           "trace": {"step_module": "jit_step"}, "limits": TOY_LIMITS}
    with open(os.path.join(files, "traffic", "mixes", "toy-fit-smallthinker.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-smallthinker", "source": "toy", "reduced": [],
                             "file": "cfg/toy-smallthinker.json", "why": "toy"})
    bench["workloads"].append({"name": "toy-smallthinker-train", "config": "toy-smallthinker",
                               "traffic": "toy-fit-smallthinker", "chips": 1, "why": "toy"})
    bench["end_to_end"][0]["workloads"].append("toy-smallthinker-train")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)["per_layer"]
    for metric in real:
        if "smallthinker-train-16k" in metric.get("workloads", ()):
            bench["per_layer"].append({**metric, "workloads": ["toy-smallthinker-train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, files


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_toy_cell_runs_through_the_driver_and_agrees_with_the_reference(tmp_path, trace):
    """``drivers/train.py`` end to end: the program's checked steps (loss,
    first gradient by leaf, each leaf's change) against ``reference_readings``
    through ``compare``, experts 2..5 of 8 held, recomputation by layer."""
    root, files = build_toy_smallthinker_root(tmp_path)
    result = harness.run_cell(root, "toy-smallthinker-train", 2**31 + 35, 0.3, trace,
                              files_dir=files, need_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["window"]["compiles_in_window"] == 0
    if trace:
        # the CPU has no device plane: the trace's readers leave their metrics
        # out; the program's gauges and counters are there
        assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
        assert 0.0 <= result["metrics"]["moe_bounded_layers"]["value"] <= 4.0
        assert result["metrics"]["einsum_fallbacks"]["value"] == 0
        # the einsum path runs here: no kernel, so no windowed kernel call either
        assert result["metrics"]["window_attention_calls"]["value"] >= 0.0
        for name in ("window_attention_device_ms", "global_attention_device_ms",
                     "window_flash_roofline", "expert_matmul_device_ms"):
            assert name not in result["metrics"]
    else:
        assert result["metrics"]["train_tokens_per_s"]["value"] > 0


FAULTS = {
    "window_ignored": {"_window_ignored": True},
    "global_layer_rotated": {"_global_rotated": True},
    "router_reads_ffn_input": {"_router_reads_ffn_input": True},
    "silu_for_relu": {"_silu": True},
    "expert_left_out": {"_skip_experts": (0,)},
}


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


def _mask_pairs(seq_len: int, window: int) -> int:
    t = np.arange(seq_len)[:, None] - np.arange(seq_len)[None, :]
    return int(((t >= 0) & (t < window)).sum())


@pytest.mark.parametrize("seq_len,window", [(128, 24), (128, 128), (64, 100), (512, 1), (4096, 1000)])
def test_the_window_calls_pairs_are_the_bands_by_a_direct_count_of_the_mask(seq_len, window):
    """``rooflines/work.py`` knows causal and full calls; a window call is
    handed to it as the two whose pairs add up to the band's exactly."""
    config = {**TOY, "sliding_window_size": window, "first_layer": 1, "num_layers": 1}
    calls = st_work.window_calls(config, 1, seq_len)
    assert len(calls) == (2 if seq_len > window else 1)
    assert sum(work.attention_pairs(a) for a in calls) == _mask_pairs(seq_len, window)
    flops = sum(work.attention_forward_flops(a) for a in calls)
    assert flops == 2 * 14 * _mask_pairs(seq_len, window) * (8 + 8)
    assert st_work.global_calls(config, 1, seq_len) == []
    whole = st_work.train_step_work({**TOY, "sliding_window_size": window}, 2, seq_len)
    assert len(whole["attentions"]) == 1 + 3 * len(calls)
    assert whole["attentions"][0] == dict(b=2, h=14, i=seq_len, j=seq_len, dk=8, dv=8, causal=True)


def test_real_configuration_counts_what_the_issue_counted():
    with open(CONFIG) as f:
        config = json.load(f)
    shapes = ref.param_shapes(config)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == config["parameters"]["total"] == 370_547_200  # ISSUE 35's count
    groups = config["parameters"]["by_group"]
    assert sum(groups.values()) == total
    assert groups["layer.0.attn"] == 20_971_520 and groups["layer.0.router"] == 163_840
    assert groups["layer.0.experts"] == 8 * 5_898_240
    assert groups["emb.tok"] + groups["head.w"] == 97_239_040 and groups["out_norm.g"] == 2_560
    layer = sum(v for k, v in groups.items() if k.startswith("layer.0."))
    assert layer == 68_326_400
    # the program's own tree, counted without building it
    model = adapter.model_config(config, {"activation_checkpointing": True})
    from perceiver_io_tpu.models.text.lm import DecoderLM

    ids = jnp.zeros((1, 8), jnp.int32)
    tree = jax.eval_shape(lambda: DecoderLM(model, dtype=jnp.bfloat16).init(jax.random.PRNGKey(0), ids))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree["params"])) == total
    assert ref.held_layers(config) == [(False, False)] + [(True, True)] * 3
    assert model.layer_types == ("full_attention",) + ("window_attention",) * 3
    assert model.rotary_layer_types == ("window_attention",)
    # every number of the catalog's row is in the file under its own key; the
    # cut keys differ from what was published and are listed
    assert config["reduced"] == ["num_layers", "moe_num_primary_experts", "vocab_size"]
    published = {"head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
                 "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
                 "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
                 "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
                 "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1500000,
                 "sliding_window_size": 4096, "tie_word_embeddings": False,
                 "model_name": "smallthinker_21b_instruct"}
    assert {k: config[k] for k in published} == published
    assert config["rope_layout"] == config["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert config["published"] == {"num_hidden_layers": 52, "moe_num_primary_experts": 64,
                                   "vocab_size": 151936}
    assert (config["moe_num_primary_experts"], config["num_experts"], config["router_width"]) == (8, 8, 64)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["moe_intermediate_size"] == config["moe_ffn_hidden_size"]
    assert config["num_experts_per_tok"] == config["moe_num_active_primary_experts"]
    for key in ("assumed", "deployment", "precision", "other_names"):
        assert config[key]
    # the initialisation the file states is the one the reference draws
    assert (config["init_scale"], config["embed_init_scale"]) == (0.02, 1.0)
    assert config["residual_init_scale"] == pytest.approx(0.02 / 104 ** 0.5)
    drawn = jax.eval_shape(lambda: ref.init_params(jax.random.PRNGKey(0), config))
    assert set(drawn) == set(shapes)
    tiny = {**TOY, "init_scale": 0.5, "embed_init_scale": 2.0, "residual_init_scale": 0.01}
    spread = {n: float(jnp.std(v)) for n, v in ref.init_params(jax.random.PRNGKey(0), tiny).items()}
    assert spread["emb.tok"] == pytest.approx(2.0, rel=0.05) and spread["layer.0.attn.q.w"] == pytest.approx(0.5, rel=0.1)
    assert spread["layer.0.attn.o.w"] == pytest.approx(0.01, rel=0.1)
    assert spread["layer.3.moe.down"] == pytest.approx(0.01, rel=0.1) and spread["head.w"] == pytest.approx(0.5, rel=0.05)
    step = st_work.train_step_work(config, 1, 16384)
    assert 30.2e12 < work.train_step_flops(step) < 30.6e12  # the issue's 30.4 TFLOP a row
    assert 14.7e12 < 3 * work.matmul_forward_flops(step["matmuls"]) < 14.9e12
    pairs = [sum(work.attention_pairs(a) for a in calls) for calls in (
        st_work.global_calls(config, 1, 16384), st_work.window_calls(config, 1, 16384))]
    assert pairs == [16384 * 16385 // 2, 3 * _mask_pairs(16384, 4096)]
    assert st_work.expert_layers(config) == 4 and st_work.expected_rows(config, 16384) == 12288
    from perceiver_io_tpu.models.core.hybrid import expected_rows

    assert expected_rows(16384, 6, 8, 64) == 24576
    with open(os.path.join(ROOT, "benchmarks", "traffic", "mixes", "fit-16k-b1.json")) as f:
        mix = json.load(f)
    assert mix["feed"] == {"task": "clm", "batch": 1, "seq_len": 16384, "corpus_tokens": 8388608,
                           "markov_fanout": 8}
    assert (mix["warmup_steps"], mix["trace_steps"], mix["reference_rows"]) == (2, 6, 1)
    assert mix["fit"]["optimizer"]["lr"] == 1e-6 and mix["fit"]["lr_scheduler"]["warmup_steps"] == 0
    assert mix["fit"]["model"] == {"activation_checkpointing": True}
    assert mix["fit"]["trainer"]["enable_tensorboard"] is False


LAYER = "jit(step)/jvp(DecoderLM)/layers_{}/checkpoint/{}/attention/attention.attend/cond/branch_0_fun"
BACK = "jit(step)/transpose(jvp(DecoderLM))/layers_{}/checkpoint/{}/attention/attention.attend/cond/branch_0_fun"
TABLE = {
    "flash_fwd.1": LAYER.format(0, "global_attention") + "/flash_fwd/pallas_call",
    "flash_bwd_dkv.2": BACK.format(0, "global_attention") + "/flash_bwd_dkv/pallas_call",
    "flash_bwd_dq.3": BACK.format(0, "global_attention") + "/flash_bwd_dq/pallas_call",
    "flash_fwd.4": LAYER.format(1, "window_attention") + "/flash_fwd/pallas_call",
    "flash_bwd_dkv.5": BACK.format(1, "window_attention") + "/flash_bwd_dkv/pallas_call",
    "flash_bwd_dq.remat.6": BACK.format(1, "window_attention") + "/flash_bwd_dq/pallas_call",
    "copy.7": LAYER.format(1, "window_attention") + "/flash_fwd/pallas_call",  # a layout copy: no kernel
    "fusion.8": "jit(step)/jvp(DecoderLM)/layers_1/checkpoint/window_attention/attention/q_proj/dot_general",
}
MS = {"flash_fwd.1": 1.0, "flash_bwd_dkv.2": 2.0, "flash_bwd_dq.3": 4.0, "flash_fwd.4": 8.0,
      "flash_bwd_dkv.5": 16.0, "flash_bwd_dq.remat.6": 32.0, "copy.7": 64.0, "fusion.8": 128.0}


def _event(instruction: str) -> str:
    if instruction.startswith("flash_"):
        return f'%{instruction} = bf16[1,28,128,128] custom-call(%x), custom_call_target="tpu_custom_call"'
    return f"%{instruction} = bf16[8,128] op(%x)"


def _trace(steps=2, devices=1):
    out = []
    for d in range(devices):
        device, t = trace_reduce.DeviceTrace(f"/device:TPU:{d}"), 0.0
        for _ in range(steps):
            start = t
            for instruction, ms in MS.items():
                device.ops.append((_event(instruction), t, ms * 1e-3))
                t += ms * 1e-3
            device.modules.append(("jit_step(1)", start, t - start))
        out.append(device)
    return trace_reduce.Trace(out, [], 0.0)


def _ctx(trace, config=None):
    config = config or {**TOY, "first_layer": 0, "num_layers": 2, "sliding_window_size": 64}
    return {"trace": trace, "cell": {"name": "toy"}, "mix": {"trace": {"step_module": "jit_step"}},
            "config": config, "window": {"batch": 1, "seq_len": 128},
            "peak": {"flops_per_s_bf16": 1e9, "bytes_per_s": 1e12}}


EXPECTED = {"window_attention_device_ms": 8.0 + 16.0 + 32.0, "global_attention_device_ms": 1.0 + 2.0 + 4.0}


@pytest.mark.parametrize("metric", sorted(EXPECTED) + ["window_flash_roofline"])
def test_kind_reader_sums_the_flash_kernels_under_its_scope(metric, monkeypatch):
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, {}))
    read = harness.load_reader(FILES, metric)
    if metric == "window_flash_roofline":
        # one window layer of 14 heads of 8 at 128 positions under a window of 64:
        # 64 * 65 / 2 + 64 * 64 pairs, 7 products of 2 * pairs * 8 a head, at 1 GFLOP/s
        pairs = 64 * 65 // 2 + 64 * 64
        least_ms = 1e3 * 2 * 14 * pairs * 8 * 7 / 1e9
        want = 100.0 * least_ms / EXPECTED["window_attention_device_ms"]
    else:
        want = EXPECTED[metric]
    assert read(_ctx(_trace())) == pytest.approx(want)
    assert read(_ctx(_trace(devices=4))) == pytest.approx(want)
    assert read(_ctx(None)) is None and read(_ctx(_trace(steps=0))) is None
    # a program with the tables and without the scopes (the parent's): nothing to read
    bare = {k: v.replace("window_attention/", "").replace("global_attention/", "") for k, v in TABLE.items()}
    monkeypatch.setattr(scopes, "tables", lambda cell: (bare, {}))
    assert read(_ctx(_trace())) is None
    monkeypatch.setattr(scopes, "tables", lambda cell: None)
    assert read(_ctx(_trace())) is None


def test_roofline_reader_reads_nothing_for_a_configuration_without_window_calls(monkeypatch):
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, {}))
    read = harness.load_reader(FILES, "window_flash_roofline")
    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-24b-a2b-ep8.json")) as f:
        assert read(_ctx(_trace(), json.load(f))) is None


def test_window_calls_reader_reads_the_programs_counter(monkeypatch):
    import perceiver_io_tpu.observability as observability
    from perceiver_io_tpu.observability import MetricsRegistry
    from perceiver_io_tpu.ops.flash_attention import flash_attention

    registry = MetricsRegistry()
    monkeypatch.setattr(observability, "default_registry", lambda: registry)
    read = harness.load_reader(FILES, "window_attention_calls")
    assert read({}) is None  # a program that never declared it
    q = jnp.zeros((1, 2, 128, 32))
    jax.eval_shape(lambda: flash_attention(q, q, q, causal=True))
    assert read({}) == 0.0  # declared by the first flash call, whatever it carries
    jax.eval_shape(lambda: flash_attention(q, q, q, causal=True, window=16))
    jax.eval_shape(lambda: flash_attention(q, q, q, causal=True, window=200))
    assert read({}) == 2.0


def test_the_program_names_the_scopes_the_readers_ask_for():
    """The compiled step's ``op_name``s carry ``window_attention`` and
    ``global_attention`` around each kind's attention module, kernels
    included, and the expert layer's phases as they were."""
    import re

    from perceiver_io_tpu.training.tasks import lm_loss_fn

    small = {**KERNEL_TOY, "vocab_size": 64}
    model = _program(small, impl="flash")
    tree = jax.eval_shape(lambda: adapter.common.seeded_tree(ref, small, adapter.path_of, 1))
    batch = jax.eval_shape(lambda: _batch(n=256))
    text = jax.jit(jax.grad(lambda p, b: lm_loss_fn(model)(p, b, None)[0])).lower(tree, batch).as_text(
        debug_info=True)
    names = set(re.findall(r'"(jit\([^"]*)"', text))
    by_scope = {}
    for name in names:
        for s in scopes.scopes_of(name):
            by_scope.setdefault(s, []).append(name)
    assert {"window_attention", "global_attention", "router", "dispatch", "experts", "combine",
            "loss"} <= set(by_scope)
    for kind, layers in (("window_attention", ("layers_0", "layers_1", "layers_2")),
                         ("global_attention", ("layers_3",))):
        kernels = {k for k in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
                   if any(f"/{k}/" in n for n in by_scope[kind])}
        assert "flash_fwd" in kernels and "flash_bwd_dkv" in kernels, kind
        assert {s for n in by_scope[kind] for s in scopes.scopes_of(n) if s.startswith("layers_")} == set(layers)
    # rotary runs under the window layers alone
    rotary = {s for n in by_scope.get("rotary", []) for s in scopes.scopes_of(n) if s.startswith("layers_")}
    assert rotary == {"layers_0", "layers_1", "layers_2"}

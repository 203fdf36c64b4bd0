"""The benchmark's side of the Keye-VL-2.0 cell: the adapter's layout both
ways over every leaf, program against reference at a small size (logits, both
losses, every leaf's gradient, and which loss reaches which leaves; the
einsum path and the kernels), the reference in blocks against the reference
at once, the planted faults, the shares of an expert layer, the real
configuration's count, the selection's pairs against the roofline's, the new
readers on a hand-built trace, and the scopes they read in the program."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, scopes, trace_reduce
from benchmarks.adapters import lm_sparse as adapter
from benchmarks.reference import blocks
from benchmarks.reference import keye_vl2 as ref
from benchmarks.rooflines import keye_vl2 as kv_work
from benchmarks.rooflines import work
from conftest import ROOT

FILES = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "keye-vl-2.0-30b-a3b-ep8.json")

#: hidden 64 under 4 query heads on 2 of 16, an indexer of 2 heads of 16
#: keeping 8 keys a query, 2 layers, experts 2..5 of 8 held, 3 a token
TOY = {
    "name": "toy-keye", "reference": "keye_vl2", "program": "lm_sparse",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 16, "indexer_num_kv_heads": 1, "topk": 8,
                  "q_chunk_size": 512, "kv_chunk_size": 512},
    "moe_intermediate_size": 24, "num_experts": 4, "router_width": 8, "expert_offset": 2,
    "num_experts_per_tok": 3, "norm_topk_prob": True, "num_layers": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 10000000, "tie_word_embeddings": False, "vocab_size": 64,
    "max_position_embeddings": 512, "init_scale": 0.1, "embed_init_scale": 1.0,
}
#: heads of 32 in rows of 256 through the kernels (interpreted), 40 keys a query
KERNEL_TOY = {**TOY, "head_dim": 32, "sa_config": {**TOY["sa_config"], "topk": 40}}
SEED = 3


def _batch(rows=2, n=64, vocab=64):
    x = jax.random.randint(jax.random.PRNGKey(0), (rows, n + 1), 0, vocab)
    return {"input_ids": x[:, :-1], "labels": x[:, 1:], "pad_mask": jnp.zeros((rows, n), bool)}


def _program(config, dtype=jnp.float32, impl="xla"):
    from perceiver_io_tpu.models.text.lm import DecoderLM

    return DecoderLM(adapter.model_config(config), dtype=dtype, attention_impl=impl)


def test_adapter_lays_every_leaf_out_and_reads_it_back():
    names = sorted(ref.param_shapes(TOY))
    flat = jax.jit(lambda key: ref.init_params(key, TOY))(jax.random.PRNGKey(5))
    tree = adapter.common.seeded_tree(ref, TOY, adapter.path_of, 5)
    ids = jnp.zeros((1, 8), jnp.int32)  # no longer than the top-k: no selection to trace
    init = jax.eval_shape(lambda: _program(TOY).init(jax.random.PRNGKey(0), ids))["params"]
    assert jax.tree_util.tree_structure(init) == jax.tree_util.tree_structure(tree)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(init), jax.tree_util.tree_leaves(tree)))
    laid = adapter.common.leaves_by_name(tree, names, adapter.path_of)
    assert sorted(laid) == names and len(names) == len(jax.tree_util.tree_leaves(tree))
    back = adapter.reference_order(laid, TOY)
    for name in names:
        assert (back[name] == flat[name]).all(), name
    # the rotated heads' columns are reordered, the attention's by 16 and the
    # indexer's by 16 (its key's norm with them), and nothing else
    moved = {n for n in names if not (laid[n] == flat[n]).all()}
    want = {f"layer.{i}.{x}" for i in range(2) for x in (
        "attn.q.w", "attn.k.w", "attn.q_norm.g", "attn.k_norm.g",
        "idx.q.w", "idx.k.w", "idx.k_norm.g", "idx.k_norm.b")}
    assert moved == want
    q = laid["layer.0.idx.q.w"]
    assert (q[:, 0:16:2] == flat["layer.0.idx.q.w"][:, 0:8]).all()
    assert (q[:, 17] == flat["layer.0.idx.q.w"][:, 24]).all()
    model = adapter.model_config(TOY)
    assert model.layer_types == ("sparse_attention",) * 2 and model.rotary_layer_types == ("sparse_attention",)
    assert (model.index_n_heads, model.index_head_dim, model.index_topk) == (2, 16, 8)
    assert (model.head_dim, model.num_heads, model.num_kv_heads, model.qk_norm) == (16, 4, 2, True)
    assert (model.router_score, model.expert_activation, model.router_input) == ("softmax_topk", "silu", "ffn")
    assert not model.tie_word_embeddings and not model.use_expert_bias and model.num_dense_layers == 0


def _grads_by_name(grads, config):
    names = sorted(ref.param_shapes(config))
    return adapter.reference_order(adapter.common.leaves_by_name(grads, names, adapter.path_of), config)


@pytest.mark.parametrize("impl,config,n", [("xla", TOY, 64), ("flash", KERNEL_TOY, 256)])
def test_logits_losses_and_every_gradient_match_the_reference_in_float32(impl, config, n):
    """The sparse path (8 of up to 64 keys; 40 of up to 256 through the
    kernels): logits, the LM loss, the indexer loss and every leaf's gradient
    of each against the reference; the indexer's leaves take gradient from
    the indexer loss alone, every other leaf from the LM loss alone."""
    from perceiver_io_tpu.training.tasks import lm_loss_fn, masked_cross_entropy

    batch = _batch(n=n)
    model = _program(config, impl=impl)

    def program(params):
        out, stats = model.apply({"params": params}, batch["input_ids"], return_stats=True)
        return jnp.stack([masked_cross_entropy(out, batch["labels"]), stats["indexer_loss"]]), out

    def reference(p):
        lm, index = ref.losses(p, config, batch)
        return jnp.stack([lm, index]), ref.logits(p, config, batch["input_ids"])

    def both(f):  # the values, and each loss's gradient stacked by leaf, in one program
        each = lambda p, i: jax.grad(lambda q: f(q)[0][i])(p)
        return jax.jit(lambda p: (f(p), jax.tree_util.tree_map(
            lambda a, b: jnp.stack([a, b]), each(p, 0), each(p, 1))))
    with jax.default_matmul_precision("highest"):
        p_ref = ref.init_params(jax.random.PRNGKey(SEED), config)
        tree = adapter.common.seeded_tree(ref, config, adapter.path_of, SEED)
        (losses_p, logits_p), grads_p = both(program)(tree)
        (losses_r, logits_r), grads_r = both(reference)(p_ref)
        loss, _ = jax.jit(lambda t: lm_loss_fn(model)(t, batch, None))(tree)
    np.testing.assert_allclose(logits_p, logits_r, atol=2e-5)
    np.testing.assert_allclose(losses_p, losses_r, atol=1e-5)
    assert float(loss) == pytest.approx(float(losses_r.sum()), abs=1e-5) and float(losses_r[1]) > 0.0
    grads_p = _grads_by_name(grads_p, config)
    for name, g in grads_r.items():
        np.testing.assert_allclose(grads_p[name], g, atol=2e-5 * max(1.0, float(jnp.abs(g).max())),
                                   err_msg=name)
        from_lm, from_index = (float(jnp.abs(x).max()) for x in g)
        if ".idx." in name:
            assert from_lm == 0.0 and from_index > 0.0, name
        else:
            assert from_index == 0.0, name
    assert float(jnp.abs(grads_r["layer.1.attn.q.w"][0]).max()) > 0.0


def test_a_row_no_longer_than_the_top_k_runs_the_causal_path_and_is_not_counted(monkeypatch):
    """At 64 positions and a top-64 every earlier key is selected: no
    selection is made, the attention is the causal path's, the selection
    counter stays at 0 (declared), and the indexer loss is still there."""
    import perceiver_io_tpu.observability as observability
    from perceiver_io_tpu.observability import MetricsRegistry

    registry = MetricsRegistry()
    monkeypatch.setattr(observability, "default_registry", lambda: registry)
    whole = {**TOY, "sa_config": {**TOY["sa_config"], "topk": 64}}
    batch = _batch(n=64)
    with jax.default_matmul_precision("highest"):
        tree = adapter.common.seeded_tree(ref, whole, adapter.path_of, SEED)
        out, stats = jax.jit(lambda t: _program(whole).apply({"params": t}, batch["input_ids"],
                                                             return_stats=True))(tree)
        p_ref = ref.init_params(jax.random.PRNGKey(SEED), whole)
        dense = jax.jit(lambda p: ref.logits(p, {**whole, "_dense_attention": True}, batch["input_ids"]))(p_ref)
        np.testing.assert_allclose(out, dense, atol=2e-5)
    assert registry.counters()["sparse_attention_call_total"] == 0.0
    assert float(stats["indexer_loss"]) > 0.0
    jax.eval_shape(lambda: _program(TOY).apply({"params": tree}, batch["input_ids"]))
    assert registry.counters()["sparse_attention_call_total"] == 2.0  # two sparse layers, once a trace


def test_reference_in_blocks_and_chunks_is_the_reference_at_once(monkeypatch):
    """The loops that make the real size fit (blocks of queries, chunks of
    positions in the loss) and the recomputation change no number."""
    batch = _batch(n=64)
    p = ref.init_params(jax.random.PRNGKey(SEED), TOY)
    loss = lambda q: ref.train_nll(q, TOY, batch)[0]
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(loss))(p)
        monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
        monkeypatch.setattr(ref, "LOSS_CHUNK", 16)
        monkeypatch.setattr(ref, "RECOMPUTE", False)
        got, grads = jax.jit(jax.value_and_grad(loss))(p)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for name, g in want_grads.items():
        np.testing.assert_allclose(grads[name], g, atol=2e-6 * max(1.0, float(jnp.abs(g).max())), err_msg=name)


FAULTS = {
    "dense_attention": {"_dense_attention": True},
    "top_half": {"_topk": 4},
    "no_indexer_loss": {"_no_indexer_loss": True},
    "selection_shift": {"_selection_shift": True},
    "expert_left_out": {"_skip_experts": (0,)},
}


@pytest.fixture(scope="module")
def exact_losses():
    batch = _batch(n=64)
    p = ref.init_params(jax.random.PRNGKey(SEED), TOY)
    losses = lambda config: jax.jit(lambda q: jnp.stack(ref.losses(q, config, batch)))(p)
    with jax.default_matmul_precision("highest"):
        return losses, np.asarray(losses(TOY))


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_moves_what_the_cell_compares(exact_losses, fault):
    """The calibration's planted faults at the toy size: each moves the
    reference's LM loss (the selection's, the expert's) or its indexer loss
    (the loss left out reads 0, so the indexer's leaves get no gradient)."""
    losses, exact = exact_losses
    with jax.default_matmul_precision("highest"):
        found = np.asarray(losses({**TOY, **FAULTS[fault]}))
    if fault == "no_indexer_loss":
        assert found[1] == 0.0 and found[0] == exact[0] and exact[1] > 0.0
    else:
        assert abs(found[0] - exact[0]) > 1e-4, (found, exact)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """8 experts in shares of 2 through the program's ``SparseExperts`` with
    a softmax over the chosen and ``silu``, the router on the layer's own
    input: the four parts are the uncut reference's whole layer, and every
    pair is computed by exactly one share."""
    from perceiver_io_tpu.models.core.hybrid import SparseExperts

    cfg = {**TOY, "num_experts": 8, "expert_offset": 0}
    shapes = {"moe.router.w": (64, 8), "moe.gate": (8, 64, 24), "moe.up": (8, 64, 24), "moe.down": (8, 24, 64)}
    p = blocks.normal_params(jax.random.PRNGKey(1), shapes, 0.3)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 96, 64))
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(x, p, "moe", cfg)
        parts, pairs = [], 0.0
        for share in range(4):
            take = slice(2 * share, 2 * share + 2)
            held = {"router": p["moe.router.w"], "gate": p["moe.gate"][take], "up": p["moe.up"][take],
                    "down": p["moe.down"][take]}
            layer = SparseExperts(
                num_channels=64, hidden_channels=24, router_width=8, num_experts=2,
                expert_offset=2 * share, top_k=3, use_expert_bias=False, router_score="softmax_topk",
                activation="silu")
            out, stats = layer.apply({"params": held}, x)
            parts.append(out)
            pairs += float(stats[0])
        assert pairs == 2 * 96 * 3
        np.testing.assert_allclose(sum(parts), whole, atol=2e-5, rtol=1e-5)


def _selected_pairs(seq_len: int, topk: int) -> int:
    return int(np.minimum(np.arange(seq_len) + 1, topk).sum())


@pytest.mark.parametrize("seq_len,topk", [(64, 8), (256, 40), (128, 128), (64, 100), (16384, 2048)])
def test_the_sparse_calls_pairs_are_the_selections(seq_len, topk):
    """``rooflines/work.py`` knows causal and full calls; a selection is handed
    to it as the two whose pairs add up to the selection's exactly: the count
    of an actual selection's bits where it can be made here, the formula's at
    the cell's shape."""
    config = {**TOY, "sa_config": {**TOY["sa_config"], "topk": topk}, "num_layers": 1}
    calls = kv_work.sparse_calls(config, 1, seq_len)
    assert len(calls) == (2 if seq_len > topk else 1)
    pairs = sum(work.attention_pairs(a) for a in calls)
    assert pairs == _selected_pairs(seq_len, topk)
    if seq_len <= 256:
        from perceiver_io_tpu.ops import sparse_attention as sa

        keys = jax.random.split(jax.random.PRNGKey(seq_len), 3)
        q_i = jax.random.normal(keys[0], (1, seq_len, 2, 16))
        k_i, w = jax.random.normal(keys[1], (1, seq_len, 16)), jax.random.normal(keys[2], (1, seq_len, 2))
        assert int(sa.unpack_all(sa.select(q_i, k_i, w, topk)).sum()) == pairs
    assert sum(work.attention_forward_flops(a) for a in calls) == 2 * 4 * pairs * (16 + 16)


def test_real_configuration_counts_its_parameters_and_work():
    with open(CONFIG) as f:
        config = json.load(f)
    shapes = ref.param_shapes(config)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == config["parameters"]["total"] == 465_391_104  # 465.4M held on a chip
    groups = config["parameters"]["by_group"]
    assert sum(groups.values()) == total
    assert groups["layer.0.attn"] == 18_874_368 and groups["layer.0.qk_norms"] == 256
    assert groups["layer.0.indexer"] == 2_261_120 and groups["layer.0.router"] == 262_144
    assert groups["layer.0.experts"] == 16 * 4_718_592
    assert sum(v for k, v in groups.items() if k.startswith("layer.0.")) == 96_899_456
    assert groups["emb.tok"] + groups["head.w"] == 77_791_232 and groups["out_norm.g"] == 2_048
    assert config["parameters"]["state_bytes_at_16_a_parameter"] == 16 * total
    # the program's own tree, counted without building it
    model = adapter.model_config(config, {"activation_checkpointing": True})
    from perceiver_io_tpu.models.text.lm import DecoderLM

    ids = jnp.zeros((1, 512), jnp.int32)
    tree = jax.eval_shape(lambda: DecoderLM(model, dtype=jnp.bfloat16).init(jax.random.PRNGKey(0), ids))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree["params"])) == total
    assert model.layer_types == ("sparse_attention",) * 4 and model.index_topk == 2048
    # every number of the catalog's row is in the file under its own key; the
    # cut keys differ from what was published and are listed
    assert config["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    }
    assert {k: config[k] for k in published} == published
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert (config["num_experts"], config["router_width"], config["num_layers"]) == (16, 128, 4)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key in ("assumed", "deployment", "precision", "other_names"):
        assert config[key]
    assert (config["init_scale"], config["embed_init_scale"]) == (0.02, 1.0)
    assert config["residual_init_scale"] == pytest.approx(0.02 / 96 ** 0.5)
    # a step's required work at 16,384 positions: 7.85 TFLOP forward
    step = kv_work.train_step_work(config, 1, 16384)
    forward = work.forward_flops(step)
    assert 7.7e12 < forward < 8.0e12
    indexer = sum(work.attention_forward_flops(a) for a in kv_work.indexer_calls(config, 1, 16384))
    assert indexer == pytest.approx(1.10e12, rel=0.01)
    attention = sum(work.attention_forward_flops(a) for a in step["attentions"])
    assert attention == 4 * 2 * 32 * _selected_pairs(16384, 2048) * 256
    assert kv_work.expert_layers(config) == 4 and kv_work.expected_rows(config, 16384) == 16384
    with open(os.path.join(ROOT, "benchmarks", "traffic", "mixes", "fit-16k-b1-sparse.json")) as f:
        mix = json.load(f)
    assert mix["feed"] == {"task": "clm", "batch": 1, "seq_len": 16384, "corpus_tokens": 8388608,
                           "markov_fanout": 8}
    assert (mix["warmup_steps"], mix["trace_steps"], mix["reference_rows"]) == (2, 6, 1)
    assert mix["fit"]["optimizer"]["lr"] == 1e-6 and mix["fit"]["lr_scheduler"]["warmup_steps"] == 0
    assert mix["fit"]["model"] == {"activation_checkpointing": True}
    assert mix["fit"]["trainer"]["enable_tensorboard"] is False


LAYER = "jit(step)/jvp(DecoderLM)/layers_{}/checkpoint/{}"
BACK = "jit(step)/transpose(jvp(DecoderLM))/layers_{}/checkpoint/{}"
TABLE = {
    "flash_fwd.1": LAYER.format(0, "sparse_attention/flash_fwd/pallas_call"),
    "flash_bwd_dkv.2": BACK.format(0, "sparse_attention/flash_bwd_dkv/pallas_call"),
    "fusion.3": LAYER.format(0, "indexer/selection/while/body/reduce_sum"),
    "fusion.4": BACK.format(0, "indexer/indexer_loss/while/body/dot_general"),
    "fusion.5": LAYER.format(1, "indexer/indexer/wq/dot_general"),
    "copy.6": LAYER.format(1, "sparse_attention/flash_fwd/pallas_call"),  # a layout copy: no kernel
    "fusion.7": LAYER.format(1, "sparse_attention/attention/q_proj/dot_general"),
    # a loop's own event over its body's operations: their time again, not counted
    "while.8": LAYER.format(0, "indexer/selection/while"),
}
MS = {"flash_fwd.1": 1.0, "flash_bwd_dkv.2": 2.0, "fusion.3": 4.0, "fusion.4": 8.0, "fusion.5": 16.0,
      "copy.6": 32.0, "fusion.7": 64.0, "while.8": 128.0}


def _event(instruction: str) -> str:
    if instruction.startswith("flash_"):
        return f'%{instruction} = bf16[1,32,512,128] custom-call(%x), custom_call_target="tpu_custom_call"'
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
    return {"trace": trace, "cell": {"name": "toy"}, "mix": {"trace": {"step_module": "jit_step"}},
            "config": config or {**TOY, "num_layers": 1}, "window": {"batch": 1, "seq_len": 64},
            "peak": {"flops_per_s_bf16": 1e9, "bytes_per_s": 1e12}}


EXPECTED = {"sparse_attention_device_ms": 1.0 + 2.0, "indexer_device_ms": 4.0 + 8.0 + 16.0}


@pytest.mark.parametrize("metric", sorted(EXPECTED) + ["sparse_attention_roofline"])
def test_readers_sum_their_scope_a_step(metric, monkeypatch):
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, {}))
    read = harness.load_reader(FILES, metric)
    if metric == "sparse_attention_roofline":
        # one layer of 4 heads of 16 at 64 positions keeping 8 keys a query:
        # 8 * 9 / 2 + 56 * 8 pairs, 7 products of 2 * pairs * 16 a head, at 1 GFLOP/s
        least_ms = 1e3 * 2 * 4 * _selected_pairs(64, 8) * 16 * 7 / 1e9
        want = 100.0 * least_ms / EXPECTED["sparse_attention_device_ms"]
    else:
        want = EXPECTED[metric]
    assert read(_ctx(_trace())) == pytest.approx(want)
    assert read(_ctx(_trace(devices=4))) == pytest.approx(want)
    assert read(_ctx(None)) is None and read(_ctx(_trace(steps=0))) is None
    # a program with the tables and without the scopes (the parent's): nothing to read
    bare = {k: v.replace("sparse_attention/", "").replace("indexer/", "") for k, v in TABLE.items()}
    monkeypatch.setattr(scopes, "tables", lambda cell: (bare, {}))
    assert read(_ctx(_trace())) is None
    monkeypatch.setattr(scopes, "tables", lambda cell: None)
    assert read(_ctx(_trace())) is None


def test_roofline_reader_reads_nothing_for_a_configuration_without_sparse_calls(monkeypatch):
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, {}))
    read = harness.load_reader(FILES, "sparse_attention_roofline")
    with open(os.path.join(ROOT, "benchmarks", "configs", "smallthinker-21b-a3b-ep8.json")) as f:
        assert read(_ctx(_trace(), json.load(f))) is None


def test_sparse_calls_reader_reads_the_programs_counter(monkeypatch):
    import perceiver_io_tpu.observability as observability
    from perceiver_io_tpu.observability import MetricsRegistry
    from perceiver_io_tpu.ops.attention import dot_product_attention

    registry = MetricsRegistry()
    monkeypatch.setattr(observability, "default_registry", lambda: registry)
    read = harness.load_reader(FILES, "sparse_attention_calls")
    assert read({}) is None  # a program that never declared it
    q = jnp.zeros((1, 2, 128, 32))
    jax.eval_shape(lambda: dot_product_attention(q, q, q, causal=True, impl="xla"))
    assert read({}) == 0.0  # declared by every attention call


def test_the_program_names_the_scopes_the_readers_ask_for():
    """The compiled step's ``op_name``s carry ``sparse_attention`` around each
    layer's attention, kernels included, and ``indexer`` around its indexer,
    selection and loss; the expert layer's phases as they were."""
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
    assert {"sparse_attention", "indexer", "selection", "indexer_loss", "router", "experts", "loss"} <= set(by_scope)
    kernels = {k for k in ("flash_fwd", "flash_bwd_dkv") if any(f"/{k}/" in n for n in by_scope["sparse_attention"])}
    assert kernels == {"flash_fwd", "flash_bwd_dkv"}
    assert not any("flash_" in n for n in by_scope["indexer"])
    layers = lambda scope: {s.split(".")[0] for n in by_scope[scope] for s in scopes.scopes_of(n)
                            if s.startswith("layers_")}
    assert layers("sparse_attention") == layers("indexer") == {"layers_0", "layers_1"}

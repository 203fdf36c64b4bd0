"""The benchmark's side of the LFM2 mixture-of-experts cell: the adapter's
layout both ways over every leaf, a toy cell through the harness and the
``train`` driver on the CPU (reference against program), the roofline's
count against XLA's, and the new readers on hand-built traces."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, phases, scopes, trace_reduce
from benchmarks.adapters import lm as adapter
from benchmarks.reference import lfm2_moe as ref
from benchmarks.rooflines import grouped, work
from benchmarks.rooflines import lfm2_moe as lfm2_work
from conftest import ROOT, build_toy_lfm2_root
from conftest import TOY_LFM2 as TOY
from conftest import TOY_LFM2_LIMITS as TOY_LIMITS

FILES = os.path.join(ROOT, "benchmarks")


def test_adapter_lays_every_leaf_out_and_reads_it_back():
    names = sorted(ref.param_shapes(TOY))
    flat = jax.jit(lambda key: ref.init_params(key, TOY))(jax.random.PRNGKey(5))
    tree = adapter.common.seeded_tree(ref, TOY, adapter.path_of, 5)
    model = adapter.model_config(TOY)
    from perceiver_io_tpu.models.text.lm import DecoderLM

    init = jax.eval_shape(
        lambda: DecoderLM(model).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert jax.tree_util.tree_structure(init) == jax.tree_util.tree_structure(tree)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(init), jax.tree_util.tree_leaves(tree)))
    back = adapter.common.leaves_by_name(tree, names, adapter.path_of)
    assert sorted(back) == names and len(names) == len(jax.tree_util.tree_leaves(tree))
    for name in names:
        assert (back[name] == flat[name]).all(), name
    # the q and k columns really are reordered in the program's tree
    q = tree["layers_1"]["attention"]["q_proj"]["kernel"]
    assert not (q == flat["layer.1.attn.q.w"]).all()
    assert model.layer_types == ("conv", "full_attention", "conv") and model.expert_offset == 2


@pytest.fixture
def toy_lfm2_root(tmp_path):
    return build_toy_lfm2_root(tmp_path)


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_toy_cell_runs_through_the_driver_and_agrees_with_the_reference(toy_lfm2_root, trace):
    """``drivers/train.py`` end to end: the program's checked steps (loss,
    first gradient by leaf, each leaf's change) against ``reference_readings``
    through ``compare``, experts 2..5 of 8 held."""
    root, files = toy_lfm2_root
    result = harness.run_cell(root, "toy-lfm2-train", 2**31 + 77, 0.3, trace,
                              files_dir=files, need_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["window"]["compiles_in_window"] == 0
    if trace:
        # the CPU has no device plane: the trace's readers leave their metrics
        # out; the program's gauge is there
        assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
        assert "expert_matmul_device_ms" not in result["metrics"]
        assert result["metrics"]["einsum_fallbacks"]["value"] == 0
    else:
        assert result["metrics"]["train_tokens_per_s"]["value"] > 0


def test_a_left_out_expert_and_a_lower_precision_read_outside_the_sound_band():
    """The planted fault and the control of the calibration, at the toy
    size: both read far above what the sound program does."""
    from benchmarks.drivers import train
    from benchmarks.traffic.train_batches import TrainBatches

    opt = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01,
           "schedule": "constant", "warmup_steps": 0, "training_steps": 10, "min_fraction": 0.0}
    batches = TrainBatches({"task": "clm", "batch": 4, "seq_len": 128, "corpus_tokens": 20000}, 11)
    check = [batches.next_batch() for _ in range(train.CHECK_STEPS)]
    exact = train.reference_readings(ref, TOY, opt, 0, 11, check, 2)
    fault = train.reference_readings(ref, {**TOY, "_skip_experts": (0,)}, opt, 0, 11, check, 2)
    low = train.reference_readings(ref, TOY, opt, 0, 11, check, 2, precision="fp8")
    assert train.compare(fault, exact)["grad_leaf"] > 0.2  # reads 0.77
    assert train.compare(low, exact)["grad_leaf"] > 1.5 * TOY_LIMITS["grad_leaf"]  # reads 0.0081
    again = train.reference_readings(ref, TOY, opt, 0, 11, check, 4)  # other blocks, same numbers
    assert train.compare(again, exact)["grad_leaf"] < 1e-4


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


ROOF = {**TOY, "hidden_size": 128, "intermediate_size": 384, "moe_intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8, "expert_offset": 0,
        "vocab_size": 512}


@pytest.mark.parametrize("training", [False, True], ids=["forward", "train"])
def test_roofline_count_against_xla(training, monkeypatch):
    """The reference computes every held expert for every token; the
    roofline counts the rows routed. With every expert held and 2 of 8 a
    token XLA's count of the experts' products is 4 times ours, so the
    comparison adds that difference, known from shapes, to ours."""
    monkeypatch.setattr(ref, "RECOMPUTE", False)  # XLA would count the recomputation
    ids = jnp.zeros((2, 256), jnp.int32)
    p = jax.eval_shape(lambda: ref.init_params(jax.random.PRNGKey(0), ROOF))
    step_work = lfm2_work.train_step_work(ROOF, 2, 256)
    tokens, layers = 2 * 256, lfm2_work.expert_layers(ROOF)
    assert lfm2_work.expected_rows(ROOF, tokens) == tokens * 2 * 8 / 8
    dense_rows = tokens * ROOF["num_experts"] - lfm2_work.expected_rows(ROOF, tokens)
    extra = layers * grouped.grouped_flops(grouped.expert_products(ROOF, dense_rows), training)
    fn = lambda q, x: ref.logits(q, ROOF, x)
    if training:
        ours = work.train_step_flops(step_work, count_masked=True) + extra
        xla = _xla_flops(jax.grad(lambda q, x: fn(q, x).sum()), p, ids)
    else:
        ours = work.forward_flops(step_work, count_masked=True) + extra
        xla = _xla_flops(fn, p, ids)
        assert ours <= xla
    assert abs(ours - xla) / xla < 0.15, (ours, xla)


def test_real_configuration_counts_what_the_issue_counted():
    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-24b-a2b-ep8.json")) as f:
        config = json.load(f)
    total = sum(int(jnp.prod(jnp.array(s))) for s in ref.param_shapes(config).values())
    assert total == config["parameters"]["total"] and abs(total - 469.3e6) / 469.3e6 < 0.01
    for key, value in config["published"].items():
        assert config[key] != value or key == "num_hidden_layers"
    assert [k for k, _ in ref.held_layers(config)] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert [d for _, d in ref.held_layers(config)] == [True, False, False, False, False]
    step = lfm2_work.train_step_work(config, 4, 8192)
    assert 39e12 < work.train_step_flops(step) < 42e12  # the issue's 40 TFLOP at 4 rows
    assert step["attentions"] == [dict(b=4, h=32, i=8192, j=8192, dk=64, dv=64, causal=True)]
    peak = {"flops_per_s_bf16": 197e12, "bytes_per_s": 819e9}
    rows = lfm2_work.expected_rows(config, 4 * 8192)
    assert rows == 16384
    least = grouped.grouped_least_time(grouped.expert_products(config, rows), 8, peak)
    assert least == pytest.approx(3 * 3 * 2 * rows * 2048 * 1536 / 197e12, rel=0.25)  # compute-bound


MODEL = "jit(step)/jvp(DecoderLM)/layers_1/checkpoint"
BACK = "jit(step)/transpose(jvp(DecoderLM))/layers_1/checkpoint"
TABLE = {
    "fusion.1": f"{MODEL}/moe/router/dot_general",
    "sort.2": f"{MODEL}/moe/dispatch/sort",
    "gather.3": f"{BACK}/moe/dispatch/gather",
    "fusion.4": f"{MODEL}/moe/experts/mul",
    "fusion.5": f"{BACK}/moe/combine/mul",
    "fusion.6": f"{MODEL}/conv/short_conv/mul",
    "fusion.7": f"{MODEL}/conv/in_proj/dot_general",
    "fusion.8": "",
    "ragged-dot-none.9": "ragged-dot-none",
    "ragged-dot-metadata.10": "ragged-dot-metadata",
}
FUSED = {"fusion.8": ["", f"{BACK}/conv/short_conv/add", f"{MODEL}/moe/router/add"]}
MS = {"fusion.1": 1.0, "sort.2": 2.0, "gather.3": 4.0, "fusion.4": 0.5, "fusion.5": 8.0,
      "fusion.6": 16.0, "fusion.7": 32.0, "fusion.8": 64.0, "ragged-dot-none.9": 128.0,
      "ragged-dot-metadata.10": 0.25}
EXPECTED = {"expert_matmul_device_ms": 128.75, "moe_routing_device_ms": 15.0,
            "short_conv_device_ms": 80.0}


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


def _ctx(trace, **more):
    return {"trace": trace, "cell": {"name": "toy"}, "mix": {"trace": {"step_module": "jit_step"}},
            **more}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
@pytest.mark.parametrize("devices", [1, 4])
def test_phase_reader_sums_its_scopes_a_step_on_any_number_of_devices(metric, devices, monkeypatch):
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, FUSED))
    read = harness.load_reader(FILES, metric)
    assert read(_ctx(_trace(devices=devices))) == pytest.approx(EXPECTED[metric])
    assert read(_ctx(None)) is None
    assert read(_ctx(_trace(steps=0))) is None
    monkeypatch.setattr(scopes, "tables", lambda cell: None)  # the parent's program
    assert read(_ctx(_trace())) is None


def test_expert_roofline_and_load_read_the_programs_gauges(monkeypatch):
    from perceiver_io_tpu.observability import default_registry

    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-24b-a2b-ep8.json")) as f:
        config = json.load(f)
    peak = {"flops_per_s_bf16": 197e12, "bytes_per_s": 819e9}
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, FUSED))
    monkeypatch.setattr(phases, "program_gauge", lambda name: None)
    roofline = harness.load_reader(FILES, "expert_matmul_roofline")
    load = harness.load_reader(FILES, "expert_load_max_over_mean")
    ctx = _ctx(_trace(), peak=peak, config=config)
    assert roofline(ctx) is None and load(ctx) is None  # the parent sets no gauge
    monkeypatch.undo()
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, FUSED))
    default_registry().set_gauge("trainer_moe_assignments_held", 4 * 16384.0)
    default_registry().set_gauge("trainer_moe_expert_load_max_over_mean", 1.25)
    least = 4 * grouped.grouped_least_time(grouped.expert_products(config, 16384.0), 8, peak)
    assert roofline(ctx) == pytest.approx(100.0 * least / 0.12875)
    assert 0 < roofline(ctx) < 100 and load(ctx) == 1.25



@pytest.mark.parametrize("reference,layers", [("lfm2_moe", 4), ("fake_moe", 5)])
def test_expert_roofline_asks_the_configurations_own_roofline_module(reference, layers, monkeypatch):
    """How many expert layers share the held pairs is
    ``rooflines/<reference>.py::expert_layers``'s to say: a second
    architecture (here one that counts a prediction module's expert layer
    beside its four) joins the metric with a file of its own."""
    import sys
    import types

    fake = types.ModuleType("benchmarks.rooflines.fake_moe")
    fake.expert_layers = lambda config: lfm2_work.expert_layers(config) + 1
    monkeypatch.setitem(sys.modules, "benchmarks.rooflines.fake_moe", fake)
    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-24b-a2b-ep8.json")) as f:
        config = {**json.load(f), "reference": reference}
    peak = {"flops_per_s_bf16": 197e12, "bytes_per_s": 819e9}
    monkeypatch.setattr(scopes, "tables", lambda cell: (TABLE, FUSED))
    monkeypatch.setattr(phases, "program_gauge", lambda name: 65536.0)
    read = harness.load_reader(FILES, "expert_matmul_roofline")
    least = layers * grouped.grouped_least_time(
        grouped.expert_products(config, 65536.0 / layers), 8, peak)
    assert read(_ctx(_trace(), peak=peak, config=config)) == pytest.approx(100.0 * least / 0.12875)


def _kernel(instruction: str) -> str:
    return f'%{instruction} = bf16[2,32,8192,64] custom-call(%x), custom_call_target="tpu_custom_call"'


@pytest.mark.parametrize("others", [{}, {"ragged-dot-none.9": 25.0, "ragged-dot-metadata.10": 0.25}],
                         ids=["flash_only", "with_ragged_dot"])
def test_flash_roofline_is_over_the_flash_kernels_time_alone(others):
    """XLA's own Mosaic kernels for the grouped products are custom calls
    too: they are the experts' (``expert_matmul_roofline``), not the
    denominator of the flash kernels' share. A step whose only Mosaic
    kernels are the flash kernels reads as it did over every custom call."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-24b-a2b-ep8.json")) as f:
        config = json.load(f)
    peak = {"flops_per_s_bf16": 197e12, "bytes_per_s": 819e9}
    flash = {"flash_fwd.3": 42.0, "flash_bwd_dkv.1": 25.0, "flash_bwd_dq": 13.0}
    device, t = trace_reduce.DeviceTrace("/device:TPU:0"), 0.0
    for _ in range(2):
        start = t
        for instruction, ms in {**flash, **others, "fusion.7": 100.0}.items():
            name = _kernel(instruction) if instruction != "fusion.7" else "%fusion.7 = bf16[8] fusion(%x)"
            device.ops.append((name, t, ms * 1e-3))
            t += ms * 1e-3
        device.modules.append(("jit_step(1)", start, t - start))
    trace = trace_reduce.Trace([device], [], 0.0)
    ctx = _ctx(trace, peak=peak, config=config, window={"batch": 2, "seq_len": 8192})
    read = harness.load_reader(FILES, "flash_roofline")
    calls = lfm2_work.train_step_work(config, 2, 8192)["attentions"]
    least = work.flash_least_time(calls, peak, itemsize=2, training=True)["seconds"]
    assert read(ctx) == pytest.approx(100.0 * least / 0.080)
    assert trace.custom_call_s() == pytest.approx(2e-3 * (80.0 + sum(others.values())))
    no_flash = trace_reduce.Trace([trace_reduce.DeviceTrace("/device:TPU:0", ops=[
        (_kernel("ragged-dot-none.9"), 0.0, 1.0)], modules=[("jit_step(1)", 0.0, 1.0)])], [], 0.0)
    assert read({**ctx, "trace": no_flash}) is None and read({**ctx, "trace": None}) is None

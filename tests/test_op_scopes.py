"""What runs on the device, by name (docs/observability.md "Device
operations by scope"): the scope tables the ledger gives for the trainer's
step, the scopes of what is no Flax module, and the trainer's phases as
profiler annotations, its two waits as process-wide counters, with and
without a tracer. All on the CPU, at a toy size."""
import gc
import glob
import json
import os

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.observability import (
    CompileLedger,
    MetricsRegistry,
    Tracer,
    default_ledger,
    default_registry,
)
from perceiver_io_tpu.observability.ledger import parse_op_scopes
from perceiver_io_tpu.parallel import MeshConfig, create_train_state, make_mesh, make_train_step
from perceiver_io_tpu.training.tasks import clm_loss_fn
from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

pytestmark = [pytest.mark.timeout(120), pytest.mark.observability]

VOCAB, SEQ, LATENTS = 29, 16, 8
WAITS = ("data_wait", "log_flush")
#: what else has a reader since the start of a fit is counted by phase
FIT_START = ("setup_state", "first_step", "first_step_lower", "first_step_backend")


def _model():
    cfg = CausalLanguageModelConfig(
        vocab_size=VOCAB, max_seq_len=SEQ, max_latents=LATENTS, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.5,
    )
    return CausalLanguageModel(config=cfg)


def _init(model):
    return lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, SEQ), jnp.int32), SEQ - LATENTS
    )["params"]


def _batch(rows=8):
    ids = np.random.default_rng(0).integers(0, VOCAB, (rows, SEQ + 1), dtype=np.int64)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _fit(root, max_steps, *, tracer=None, registry=None, val=False, **cfg):
    model = _model()
    defaults = dict(
        max_steps=max_steps, log_every_n_steps=2, val_check_interval=2,
        default_root_dir=str(root), enable_checkpointing=False, enable_tensorboard=False,
        grad_clip_norm=1.0,
    )
    trainer = Trainer(
        TrainerConfig(**{**defaults, **cfg}), make_mesh(MeshConfig()),
        clm_loss_fn(model, LATENTS), optax.adamw(1e-3), tracer=tracer, registry=registry,
    )
    try:
        trainer.fit(_init(model), [_batch()], val_data=(lambda: [_batch()]) if val else None)
    finally:
        trainer.close()
    return trainer


@pytest.fixture
def clean_defaults():
    """The process-wide registry and ledger as a fresh process has them."""
    default_registry().reset("trainer_")
    default_ledger().reset()
    yield
    default_registry().reset("trainer_")
    default_ledger().reset()


# -- the parser -------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step_table():
    model = _model()
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    state, shardings = create_train_state(_init(model), optax.adamw(1e-3), mesh)
    step = make_train_step(clm_loss_fn(model, LATENTS), mesh, shardings, grad_clip_norm=1.0)
    batch = {k: jnp.asarray(v, jnp.int32) for k, v in _batch(2).items()}
    text = step.lower(state, batch, jax.random.PRNGKey(1)).compile().as_text()
    return *parse_op_scopes(text), text


@pytest.mark.parametrize("what,needle", [
    ("a Flax module's scope", "/jvp(CausalLanguageModel)/perceiver_ar/cross_attention/cross_attn/attention/attention.project_q/q_proj/"),
    ("a backward instruction", "/transpose(jvp(CausalLanguageModel))/perceiver_ar/"),
    ("the optimizer", "jit(step)/optimizer/"),
    ("the gradient clip", "jit(step)/grad_clip/"),
    ("the loss", "jit(step)/jvp(loss)/"),
    ("the loss, backward", "jit(step)/transpose(jvp(loss))/"),
])
def test_parser_finds_the_scope_of(toy_step_table, what, needle):
    table, fused, _ = toy_step_table
    names = {*table.values(), *(n for held in fused.values() for n in held)}
    assert any(needle in op_name for op_name in names), what


def test_parser_names_fusions_by_their_instruction_and_keeps_to_what_runs(toy_step_table):
    table, fused, text = toy_step_table
    assert fused and set(fused) <= set(table)
    named = {k: table[k] for k in fused if table[k]}
    assert named and all(v.startswith("jit(step)/") for v in named.values())
    # a fusion holds what XLA named it by, and as a rule more
    assert all(table[k] in fused[k] for k in named)
    assert any(len(set(held)) > 1 for held in fused.values())
    assert all(len(set(held)) == len(held) for held in fused.values())
    # the instructions inside a fused computation are no device operations
    inside = [line.split("=")[0].strip().lstrip("%") for line in text.splitlines()
              if line.startswith("  ") and "param_0" in line.split("=")[0]]
    assert inside and not set(inside) & set(table)
    # a parameter is in the table under its argument's name, with no scope
    assert any(v.startswith("state.params") for v in table.values())


def test_parser_follows_control_flow_but_not_fusions_and_guesses_nothing():
    text = """HloModule m

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %inside.0 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/optimizer/mul"}
  ROOT %inside.1 = f32[4]{0} add(%inside.0, %inside.0), metadata={op_name="jit(f)/mlp/add"}
}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %again.2 = f32[4]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(f)/mlp/add"}
  ROOT %nested.2 = f32[4]{0} fusion(%again.2), kind=kLoop, calls=%fused_computation
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %in_loop.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/layer/mul" stack_frame_id=3}
}

%cond.4 (p.1: (s32[], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.5 = pred[] compare(%p.1, %p.1), direction=LT
}

ENTRY %main.6 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %kernel.7 = (f32[4]{0:T(8,128)(2,1)S(1)}, f32[4]{0}) custom-call(%x), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(f)/attn/kernel/pallas_call" stack_frame_id=9}, backend_config={"body":"op_name=\\"no\\""}
  %while.8 = (s32[], f32[4]{0}) while(%kernel.7), condition=%cond.4, body=%body.2
  %packed_fusion.9 = f32[4]{0} fusion(), kind=kLoop, calls=%fused_computation.1
  %copy-start.10 = (f32[4]{0}, f32[4]{0:S(1)}, u32[]{:S(2)}) copy-start(%packed_fusion.9)
  %copy-done.11 = f32[4]{0:S(1)} copy-done(%copy-start.10)
  ROOT %iota.12 = f32[4]{0} iota(), iota_dimension=0
}
"""
    scopes, fused = parse_op_scopes(text)
    assert scopes == {
        "x": "x", "kernel.7": "jit(f)/attn/kernel/pallas_call", "iota.12": "",
        # a loop's instructions run as operations of their own; a fusion's do not
        "p": "", "in_loop.3": "jit(f)/while/body/layer/mul", "p.1": "", "lt.5": "",
        # what XLA left no metadata has none: nothing is taken from an operand
        "packed_fusion.9": "", "copy-start.10": "", "copy-done.11": "", "while.8": "",
    }
    assert fused == {
        # what a fusion holds, in the program's order, through a nested fusion, once each
        "in_loop.3": ["jit(f)/optimizer/mul", "jit(f)/mlp/add"],
        "packed_fusion.9": ["jit(f)/mlp/add", "jit(f)/optimizer/mul"],
    }


# -- the ledger's table -----------------------------------------------------
def test_op_scopes_after_fit_with_the_trainer_gone(tmp_path, clean_defaults):
    compiles = []

    def on_compile(event, _, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        trainer = _fit(tmp_path, 2)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    # the step went through jax.jit's own dispatch: compiled once, and the
    # ledger, which compiles ahead of time, counted nothing during fit
    assert compiles.count("jit(step)") == 1
    assert default_ledger().records(site="trainer.step") == []
    del trainer
    gc.collect()
    table = default_ledger().op_scopes("trainer.step")
    fused = default_ledger().fused_scopes("trainer.step")
    assert any("/optimizer/" in v for held in fused.values() for v in held)
    assert any("/perceiver_ar/self_attention/layers_0/" in v for v in table.values())
    (record,) = default_ledger().records(site="trainer.step")
    assert record["components"]["function"] == "step" and record["temp_bytes"] is not None
    assert default_ledger().op_scopes("trainer.step") == table  # kept, not compiled again
    assert len(default_ledger().records(site="trainer.step")) == 1
    assert default_ledger().op_scopes("no.such.site") == {}
    assert default_ledger().fused_scopes("no.such.site") == {}


def test_fit_gives_back_the_executable_of_the_step_the_ledger_keeps(tmp_path, clean_defaults):
    _fit(tmp_path, 2)
    kept = default_ledger()._jits["trainer.step"]["fn"]
    assert kept._cache_size() == 0  # the function is kept for its table, no program with it
    assert default_ledger().op_scopes("trainer.step")
    assert default_ledger()._jits["trainer.step"]["fn"] is None  # the tables are all that stays


def test_note_jit_keeps_shapes_and_no_arrays():
    ledger = CompileLedger(registry=MetricsRegistry())
    x = jnp.ones((4, 3))
    ledger.note_jit("site", jax.jit(lambda a, b: a * b + 2.0), (x, np.ones(3)))
    noted = ledger._jits["site"]
    leaves = jax.tree_util.tree_leaves(noted["args"])
    assert not any(isinstance(leaf, (jax.Array, np.ndarray)) for leaf in leaves)
    assert noted["args"][0].shape == (4, 3) and noted["args"][1].shape == (3,)
    assert ledger.op_scopes("site")  # lowers from the shapes alone
    ledger.reset()
    assert ledger.op_scopes("site") == {} and ledger.fused_scopes("site") == {}


def test_a_step_the_ledger_cannot_lower_gives_an_empty_table_and_is_counted():
    registry = MetricsRegistry()
    ledger = CompileLedger(registry=registry)
    ledger.note_jit("plain", lambda a: a + 1, (jnp.ones(3),))  # nothing to lower
    with pytest.warns(UserWarning, match="op_scopes"):
        assert ledger.op_scopes("plain") == {}
    assert registry.counter("compile_ledger_fallback_total") == 1
    assert ledger.fused_scopes("plain") == {} and ledger.op_scopes("plain") == {}
    assert registry.counter("compile_ledger_fallback_total") == 1  # asked once


# -- the trainer's phases ---------------------------------------------------
@pytest.mark.parametrize("own_registry", [False, True], ids=["private_registry", "given_registry"])
def test_the_waits_are_counted_process_wide_without_a_tracer(tmp_path, clean_defaults, own_registry):
    registry = MetricsRegistry() if own_registry else None
    trainer = _fit(tmp_path, 4, registry=registry, val=True)
    for counters in (default_registry().counters(), trainer.registry.counters()):
        assert counters["trainer_steps_total"] == 4
        for phase in WAITS:
            assert counters[f"trainer_{phase}_seconds_total"] > 0, phase
        # what nothing reads is not counted (the step's seconds are the
        # trainer_step_dispatch_ms histogram's sum)
        assert not {k for k in counters if k.endswith("_seconds_total")} - {
            f"trainer_{phase}_seconds_total" for phase in WAITS + FIT_START}
    assert trainer.registry is not default_registry()
    assert trainer.registry.counter("trainer_data_wait_seconds_total") == pytest.approx(
        default_registry().counter("trainer_data_wait_seconds_total"))


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    names[event.name] = names.get(event.name, 0) + 1
    return names


@pytest.mark.parametrize("with_tracer", [False, True], ids=["no_tracer", "tracer"])
def test_a_capture_holds_the_phases_as_host_events_and_the_table_beside_it(
        tmp_path, clean_defaults, with_tracer):
    tracer = Tracer() if with_tracer else None
    _fit(tmp_path, 4, tracer=tracer, profile_start=2)
    profile = os.path.join(str(tmp_path), "profile")
    events = _host_events(profile)
    # steps 2-4 are captured: three steps, the hand-outs of the last two
    assert events.get("trainer.step") == 3 and events.get("trainer.data_wait") == 2
    assert events.get("trainer.log_flush", 0) >= 1
    for name, table in (("op_scopes.json", default_ledger().op_scopes("trainer.step")),
                        ("fused_scopes.json", default_ledger().fused_scopes("trainer.step"))):
        with open(os.path.join(profile, name)) as f:
            assert table and json.load(f) == table
    # written once, when the loop had ended: one compile beside the run's own
    assert len(default_ledger().records(site="trainer.step")) == 1
    if with_tracer:  # the spans are recorded as before, one per annotation and more
        assert len(tracer.spans(name="trainer.step")) == 4
        assert {s.name for s in tracer.spans()} == {
            "trainer.setup_state", "trainer.first_step",
            "trainer.data_wait", "trainer.step", "trainer.log_flush"}


def test_every_context_managed_span_is_a_profiler_annotation(tmp_path):
    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("serving.batch", rows=3):
            jnp.ones(3).block_until_ready()
        tracer.event("serving.request", start_s=tracer.now() - 1.0)  # backdated: a span only
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    assert events.get("serving.batch") == 1 and "serving.request" not in events
    assert [s.name for s in tracer.spans()] == ["serving.batch", "serving.request"]

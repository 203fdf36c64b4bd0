"""The start of a fit by phase (docs/observability.md "The start of a fit"):
``trainer.setup_state`` and ``trainer.first_step`` as annotations, spans and
counters, the first dispatch split by the ledger's readings of JAX's own
compile events, the ``startup/`` row, and the steps that compiled again. All
on the CPU, at a toy size, with the persistent cache off (``conftest.py``),
so every first dispatch is a cold compile."""
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
from perceiver_io_tpu.observability.exporters import HELP_TEXT
from perceiver_io_tpu.parallel import MeshConfig, make_mesh
from perceiver_io_tpu.training.tasks import clm_loss_fn
from perceiver_io_tpu.training.trainer import _FIT_START, Trainer, TrainerConfig

pytestmark = [pytest.mark.timeout(120), pytest.mark.observability]

VOCAB, SEQ, LATENTS = 29, 16, 8
SECONDS = [name for name in _FIT_START if name.endswith("_seconds_total")]
STARTUP_KEYS = {"startup/setup_state_s", "startup/first_step_s", "startup/trace_s",
                "startup/lower_s", "startup/backend_s", "startup/cache_hit"}
BACKEND = "/jax/core/compile/backend_compile_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"


def _model():
    cfg = CausalLanguageModelConfig(
        vocab_size=VOCAB, max_seq_len=SEQ, max_latents=LATENTS, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.5,
    )
    return CausalLanguageModel(config=cfg)


def _batch(rows=8):
    ids = np.random.default_rng(rows).integers(0, VOCAB, (rows, SEQ + 1), dtype=np.int64)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _trainer(root, max_steps, *, loss_fn=None, tracer=None, registry=None, **cfg):
    model = _model()
    defaults = dict(
        max_steps=max_steps, log_every_n_steps=2, val_check_interval=10_000,
        default_root_dir=str(root), enable_checkpointing=False, enable_tensorboard=False,
    )
    trainer = Trainer(
        TrainerConfig(**{**defaults, **cfg}), make_mesh(MeshConfig()),
        loss_fn or clm_loss_fn(model, LATENTS), optax.adamw(1e-3),
        tracer=tracer, registry=registry,
    )
    init = lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, SEQ), jnp.int32), SEQ - LATENTS
    )["params"]
    return trainer, init


def _rows(root):
    with open(os.path.join(str(root), "metrics.jsonl")) as f:
        return [json.loads(line) for line in f]


@pytest.fixture
def clean_defaults():
    default_registry().reset("trainer_")
    yield
    default_registry().reset("trainer_")


class _Heard:
    """A listener of the test's own beside the ledger's."""

    def __init__(self):
        self.seconds = {LOWER: 0.0, BACKEND: 0.0}
        self.compiles = 0
        self.traced = []  # the functions whose tracing JAX reported

    def __call__(self, event, seconds, fun_name=None, **_):
        if event in self.seconds:
            self.seconds[event] += seconds
            self.compiles += event == BACKEND
        elif event == TRACE:
            self.traced.append(fun_name)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def test_the_counters_are_declared_when_fit_begins_and_each_phase_counted_once_a_fit(
        tmp_path, clean_defaults):
    registry, tracer = MetricsRegistry(), Tracer()
    trainer, init = _trainer(tmp_path, 3, registry=registry, tracer=tracer, val_check_interval=2)
    assert not set(_FIT_START) & set(registry.counters())  # a trainer that never fits has none
    seen = []

    def init_and_look():  # called by setup_state: the first thing a fit does
        seen.append({name: registry.counters().get(name) for name in _FIT_START})
        return init()

    try:
        trainer.fit(init_and_look, [_batch()], val_data=lambda: [_batch()])
        assert seen and all(s == dict.fromkeys(_FIT_START, 0.0) for s in seen)
        once = registry.counters()
        assert all(once[name] > 0 for name in SECONDS)
        # neither the first step nor the first validation, which compiles the
        # evaluation step outside any trainer.step, is a step that compiled again
        assert any("val/loss" in r for r in _rows(tmp_path))
        assert once["trainer_step_recompiles_total"] == 0
        for name in ("trainer.setup_state", "trainer.first_step"):
            assert len(tracer.spans(name=name)) == 1, name
        assert len(tracer.spans(name="trainer.step")) == 3  # the first step's is still one
        trainer.fit(init_and_look, [_batch()])  # a second fit of the process: counted again
    finally:
        trainer.close()
    twice = registry.counters()
    assert all(twice[name] > once[name] for name in SECONDS)
    for name in ("trainer.setup_state", "trainer.first_step"):
        assert len(tracer.spans(name=name)) == 2, name
    # one trace a fit, and the fit's first phase is under it
    fits = [s.trace_id for s in tracer.spans(name="trainer.setup_state")]
    assert len(set(fits)) == 2
    assert {s.trace_id for s in tracer.spans()} == set(fits)
    # kept process-wide too, where a reader finds them with the trainer gone
    for name in _FIT_START:
        assert default_registry().counters()[name] == pytest.approx(twice[name]), name
        assert name in HELP_TEXT
    starts = [r for r in _rows(tmp_path) if "startup/first_step_s" in r]
    assert [r["step"] for r in starts] == [1, 1] and all(set(r) == STARTUP_KEYS | {"step"}
                                                          for r in starts)
    # the row's state seconds are its own fit's, not the trainer's total
    assert sum(r["startup/setup_state_s"] for r in starts) == pytest.approx(
        twice["trainer_setup_state_seconds_total"])


def _loss_with_an_inner_jit(model):
    plain = clm_loss_fn(model, LATENTS)

    @jax.jit
    def scaled(x):
        return x * 1.0

    def loss_fn(params, batch, rng):
        loss, metrics = plain(params, batch, rng)
        return scaled(loss), metrics

    return loss_fn


@pytest.mark.parametrize("inner_jit", [False, True], ids=["plain_step", "step_holds_a_jit"])
def test_the_first_dispatch_is_split_into_trace_lower_and_backend(
        tmp_path, clean_defaults, inner_jit):
    registry, tracer = MetricsRegistry(), Tracer()
    loss_fn = _loss_with_an_inner_jit(_model()) if inner_jit else None
    trainer, init = _trainer(tmp_path, 2, registry=registry, tracer=tracer, loss_fn=loss_fn)
    with _Heard() as heard:
        try:
            trainer.fit(init, [_batch()])
        finally:
            trainer.close()
    (span,) = tracer.spans(name="trainer.first_step")
    (row,) = [r for r in _rows(tmp_path) if "startup/first_step_s" in r]
    counters = registry.counters()
    trace_s, lower_s, backend_s = (span.attrs[k] for k in ("trace_s", "lower_s", "backend_s"))
    # a cold compile on the CPU: every part is there, and the parts are the whole
    assert lower_s > 0 and backend_s > 0 and trace_s > 0 and span.attrs["cache"] == "miss"
    assert trace_s + lower_s + backend_s == pytest.approx(
        counters["trainer_first_step_seconds_total"], rel=1e-9)
    assert counters["trainer_first_step_lower_seconds_total"] == lower_s
    assert counters["trainer_first_step_backend_seconds_total"] == backend_s
    # the span is the dispatch and the bookkeeping around it: milliseconds apart
    assert span.duration_ms / 1e3 == pytest.approx(
        counters["trainer_first_step_seconds_total"], abs=0.05)
    assert (row["startup/trace_s"], row["startup/lower_s"], row["startup/backend_s"]) == (
        trace_s, lower_s, backend_s)
    assert row["startup/first_step_s"] == counters["trainer_first_step_seconds_total"]
    assert row["startup/cache_hit"] == 0.0
    assert row["startup/setup_state_s"] == counters["trainer_setup_state_seconds_total"]
    # the step nests under it, in the fit's trace
    first_of = [s for s in tracer.spans(name="trainer.step") if s.parent_id == span.span_id]
    assert [s.attrs["step"] for s in first_of] == [1]
    # JAX's trace events nest: the inner jit reports its own tracing inside
    # the step's, so a sum of them would count it twice; the remainder cannot
    assert "step" in heard.traced and ("scaled" in heard.traced) == inner_jit
    assert trace_s < counters["trainer_first_step_seconds_total"]


def test_two_trainers_and_two_ledgers_hear_each_compile_once(tmp_path, clean_defaults):
    one, other = CompileLedger(registry=MetricsRegistry()), CompileLedger(registry=MetricsRegistry())
    before = one.jax_totals()
    assert other.jax_totals() == before and default_ledger().jax_totals() == before
    with _Heard() as heard:
        for i in range(2):
            trainer, init = _trainer(tmp_path / str(i), 1)
            try:
                trainer.fit(init, [_batch()])
            finally:
                trainer.close()
    after = other.jax_totals()
    assert one.jax_totals() == after == default_ledger().jax_totals()
    assert default_ledger().snapshot()["jax"] == after
    assert heard.compiles >= 4  # two states, two steps
    assert after["backend_compiles"] - before["backend_compiles"] == heard.compiles
    assert after["backend_s"] - before["backend_s"] == pytest.approx(heard.seconds[BACKEND])
    assert after["lower_s"] - before["lower_s"] == pytest.approx(heard.seconds[LOWER])
    assert after["cache_hits"] == before["cache_hits"]  # the suite runs with the cache off
    one.reset()  # drops the ledger's records, not the process's totals
    assert one.jax_totals() == after


def test_a_batch_of_another_shape_in_mid_fit_is_a_step_that_compiled_again(
        tmp_path, clean_defaults):
    registry = MetricsRegistry()
    trainer, init = _trainer(tmp_path, 4, registry=registry)
    batches = [_batch(8), _batch(8), _batch(16), _batch(16)]
    try:
        trainer.fit(init, batches)
    finally:
        trainer.close()
    rows = _rows(tmp_path)
    assert [r for r in rows if "step_recompiled_at" in r] == [
        {"step": 3, "step_recompiled_at": 3.0}]
    for counters in (registry.counters(), default_registry().counters()):
        assert counters["trainer_step_recompiles_total"] == 1
    assert len([r for r in rows if "startup/first_step_s" in r]) == 1


def test_with_steps_per_execution_both_step_functions_have_a_first_step(
        tmp_path, clean_defaults):
    registry, tracer = MetricsRegistry(), Tracer()
    # steps 1-2 and 3-4 run fused, step 5 alone: two step functions
    trainer, init = _trainer(tmp_path, 5, registry=registry, tracer=tracer,
                             steps_per_execution=2)
    try:
        trainer.fit(init, [_batch()])
    finally:
        trainer.close()
    firsts = tracer.spans(name="trainer.first_step")
    assert [s.attrs["step"] for s in firsts] == [1, 5]
    assert all(s.attrs["backend_s"] > 0 and s.attrs["lower_s"] > 0 for s in firsts)
    fused = [s for s in tracer.spans(name="trainer.step") if s.parent_id == firsts[0].span_id]
    assert [s.attrs["fused"] for s in fused] == [2]
    counters = registry.counters()
    assert counters["trainer_first_step_seconds_total"] == pytest.approx(
        sum(s.attrs[k] for s in firsts for k in ("trace_s", "lower_s", "backend_s")))
    assert counters["trainer_step_recompiles_total"] == 0
    assert [r["step"] for r in _rows(tmp_path) if "startup/first_step_s" in r] == [1, 5]

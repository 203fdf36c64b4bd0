"""The readers of the start of a fit (``fit_state_init_s``, ``step_trace_s``,
``step_lower_s``, ``step_backend_s``, ``step_recompiles``): nothing against a
program that lacks their counters, as the parent of the PR that added them
does, and the program's numbers where it has them."""
import json
import os

import pytest

from conftest import ROOT

from benchmarks import harness
from perceiver_io_tpu import observability
from perceiver_io_tpu.observability import MetricsRegistry

FILES = os.path.join(ROOT, "benchmarks")
COUNTERS = {
    "trainer_setup_state_seconds_total": 0.75,
    "trainer_first_step_seconds_total": 12.5,
    "trainer_first_step_lower_seconds_total": 3.0,
    "trainer_first_step_backend_seconds_total": 2.25,
    "trainer_step_recompiles_total": 0.0,
}
EXPECTED = {
    "fit_state_init_s": 0.75, "step_trace_s": 7.25, "step_lower_s": 3.0,
    "step_backend_s": 2.25, "step_recompiles": 0.0,
}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_gives_nothing_without_its_counter_and_the_programs_number_with_it(
        monkeypatch, metric):
    registry = MetricsRegistry()
    monkeypatch.setattr(observability, "default_registry", lambda: registry)
    read = harness.load_reader(FILES, metric)
    registry.declare_counters("trainer_steps_total")  # the parent's program: no such counter
    assert read({}) is None
    registry.declare_counters(*COUNTERS)  # a fit has begun and nothing is counted yet
    assert read({}) == 0.0
    for name, value in COUNTERS.items():
        registry.inc(name, value)
    assert read({}) == pytest.approx(EXPECTED[metric])
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [w["name"] for w in BENCH["workloads"]]
    assert entry["moves"] == ("train_tokens_per_s" if metric == "step_recompiles" else "setup_s")


def test_the_trace_needs_all_three_of_its_counters():
    registry = MetricsRegistry()
    registry.declare_counters("trainer_first_step_seconds_total",
                              "trainer_first_step_lower_seconds_total")
    read = harness.load_reader(FILES, "step_trace_s")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(observability, "default_registry", lambda: registry)
        assert read({}) is None

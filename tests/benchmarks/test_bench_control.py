"""What decides ``correct`` has been shown to fail, part one: the control (the
reference in the next precision below the configuration's: float8 inputs for
bfloat16) reads over a limit that sound runs keep, at the test's size on three
seeds; and the arithmetic of the comparison (a state left unchanged or moved
double reads 1, a leaf without gradient is left out by rule)."""
import importlib

import pytest

from benchmarks.drivers import train
from benchmarks.traffic.train_batches import TrainBatches
from conftest import TOY_AR, TOY_FEEDS, TOY_LIMITS, TOY_MLM

OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0, "schedule": "cosine",
       "warmup_steps": 200, "training_steps": 100000, "min_fraction": 0.1}


def _over(compared):
    return [k for k, limit in TOY_LIMITS.items() if not compared[k] <= limit]


@pytest.mark.parametrize("cfg,traffic", [(TOY_AR, "toy-fit-ar"), (TOY_MLM, "toy-fit-mlm")],
                         ids=["perceiver_ar", "perceiver_io_mlm"])
@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_control_in_float8_reads_over_a_limit(cfg, traffic, seed):
    ref = importlib.import_module(f"benchmarks.reference.{cfg['reference']}")
    batches = TrainBatches(TOY_FEEDS[traffic], seed)
    check = [batches.next_batch() for _ in range(train.CHECK_STEPS)]
    exact = train.reference_readings(ref, cfg, OPT, 0, seed, check, 4)
    same = train.reference_readings(ref, cfg, OPT, 0, seed, check, 8)
    low = train.reference_readings(ref, cfg, OPT, 0, seed, check, 4, precision="fp8")
    assert _over(train.compare(same, exact)) == []  # blocks of rows change nothing
    assert _over(train.compare(low, exact)), train.compare(low, exact)


def test_state_left_unchanged_reads_one():
    exact = {"losses": [5.0, 5.0, 5.0], "grad_norms": {"a": 1.0, "b": 2.0, "c": 3.0},
             "delta_norms": {"a": 0.1, "b": 0.2, "c": 0.3}}
    stuck = {**exact, "delta_norms": {"a": 0.0, "b": 0.0, "c": 0.0}}
    assert train.compare(stuck, exact)["delta_leaf"] == 1.0
    double = {**exact, "delta_norms": {"a": 0.1, "b": 0.4, "c": 0.3}}
    assert train.compare(double, exact)["delta_leaf"] == 1.0
    nan = {**exact, "grad_norms": {"a": float("nan"), "b": 2.0, "c": 3.0}}
    assert train.compare(nan, exact)["grad_leaf"] == float("inf")


def test_leaf_without_gradient_is_left_out_of_the_change_by_rule():
    exact = {"losses": [5.0], "grad_norms": {"a": 1.0, "b": 2.0, "c": 3.0, "kbias": 1e-9},
             "delta_norms": {"a": 0.1, "b": 0.2, "c": 0.3, "kbias": 0.05}}
    prog = {**exact, "delta_norms": {**exact["delta_norms"], "kbias": 0.4}}
    assert train.compare(prog, exact)["delta_leaf"] == 0.0

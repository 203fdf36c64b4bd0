"""The harness is driven by data: a cell, a configuration and a per-layer
metric added as files (and entries of ``BENCHMARK.json``) are found and run
with no file edited; ``run.py`` refuses to print a result without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness
from conftest import ROOT, TOY_AR, TOY_FEEDS, TOY_LIMITS


def _add_files_only(root, files):
    """A new configuration, traffic mix and metric: files, plus entries."""
    cfg = {**TOY_AR, "name": "added-ar", "num_self_attention_layers": 1}
    with open(os.path.join(root, "cfg", "added-ar.json"), "w") as f:
        json.dump(cfg, f)
    mix = {"driver": "train", "feed": {**TOY_FEEDS["toy-fit-ar"], "batch": 16}, "warmup_steps": 1,
           "fit": {"trainer": {"max_steps": 1000, "enable_tensorboard": False}}, "trace_steps": 2,
           "reference_rows": 2, "trace": {"step_module": "jit_step"},
           # a cell's limits are its own: this one-layer model reads 0.020 at head.bias
           "limits": {**TOY_LIMITS, "grad_leaf": 0.05}}
    with open(os.path.join(files, "traffic", "mixes", "added-fit.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(files, "metrics", "steps_in_window.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['window']['steps']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "added-ar", "source": "toy", "reduced": [],
                             "file": "cfg/added-ar.json", "why": "added"})
    bench["workloads"].append({"name": "added-train", "config": "added-ar", "traffic": "added-fit",
                               "chips": 1, "why": "added"})
    bench["end_to_end"][0]["workloads"].append("added-train")
    bench["per_layer"].append({"name": "steps_in_window", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "train step",
                               "moves": "train_tokens_per_s", "workloads": ["added-train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_added_cell_configuration_and_metric_are_found_as_files(toy_root):
    root, files = toy_root
    _add_files_only(root, files)
    spec = harness.load_cell(root, "added-train", files)
    assert spec["config"]["name"] == "added-ar" and spec["mix"]["feed"]["batch"] == 16
    assert "steps_in_window" in [m["name"] for m in spec["per_layer"]]
    assert "steps_in_window" not in [
        m["name"] for m in harness.load_cell(root, "toy-ar-train", files)["per_layer"]]
    assert harness.load_reader(files, "steps_in_window")({"window": {"steps": 7}}) == 7


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_added_cell_runs_end_to_end(toy_root, trace):
    root, files = toy_root
    _add_files_only(root, files)
    result = harness.run_cell(root, "added-train", 2**31 + 5, 0.3, trace,
                              files_dir=files, need_tpu=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == set(TOY_LIMITS)
    assert result["window"]["compiles_in_window"] == 0
    if trace:
        # the CPU has no device plane: readers of the trace find nothing and
        # their metrics are left out, never reported as 0
        assert set(result["metrics"]) == {
            "compile_s", "data_wait_ms", "train_mfu", "einsum_fallbacks", "steps_in_window",
        } - {"train_mfu"}
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert result["metrics"]["train_tokens_per_s"]["value"] > 0


def test_same_seed_same_inputs_other_seed_other_inputs():
    from benchmarks.traffic.train_batches import TrainBatches

    big = 2**31 + 12345
    a, b, c = (TrainBatches(TOY_FEEDS["toy-fit-mlm"], s).next_batch() for s in (big, big, big + 1))
    assert all((a[k] == b[k]).all() for k in a) and (a["input_ids"] != c["input_ids"]).any()
    assert a["input_ids"].shape == c["input_ids"].shape == (8, 64)
    chosen = a["labels"] != -100
    assert 0.05 < chosen.mean() < 0.3
    assert (a["input_ids"][chosen] == 3).mean() > 0.6  # most of the chosen are [MASK]


def test_unknown_workload_and_missing_reader_are_errors(toy_root):
    root, files = toy_root
    with pytest.raises(harness.BenchmarkError):
        harness.load_cell(root, "no-such-cell", files)
    with pytest.raises(harness.BenchmarkError):
        harness.load_reader(files, "no_such_metric")


def test_unknown_device_kind_has_no_peak():
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"] and "cpu" not in peaks["by_device_kind"]
    assert peaks["by_device_kind"]["TPU v5 lite"]["flops_per_s_bf16"] == 197e12


@pytest.mark.parametrize("workload", ["ar8k-train", "no-such-cell"])
def test_run_py_prints_no_result_without_a_tpu(workload):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", workload,
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "1"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "benchmark:" in proc.stderr

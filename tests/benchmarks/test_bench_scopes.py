"""The per-layer metrics that read the program's scope tables
(``benchmarks/scopes.py``), each on a hand-built trace with hand-built
tables: the right sum a step, ``None`` without a trace and ``None`` with an
empty table, as the parent of the PR that added them gives."""
import json
import os

import pytest

from benchmarks import harness, scopes, trace_reduce
from conftest import ROOT

FILES = os.path.join(ROOT, "benchmarks")
CELLS = ["ar8k-train", "mlm201m-train"]
MODEL = "jit(step)/jvp(Model)/encoder/self_attn_1/layers_0"
BACK = "jit(step)/transpose(jvp(Model))/encoder/self_attn_1/layers_0"
TABLE = {
    "flash_fwd.1": f"{MODEL}/self_attn/attention/attention.attend/cond/branch_0_fun/flash_fwd/pallas_call",
    "flash_bwd_dkv.2": f"{BACK}/self_attn/attention/attention.attend/cond/branch_0_fun/flash_bwd_dkv/pallas_call",
    "flash_bwd_dq.3": f"{BACK}/self_attn/attention/attention.attend/cond/branch_0_fun/flash_bwd_dq/pallas_call",
    "copy.4": f"{MODEL}/self_attn/attention/attention.attend/cond/branch_0_fun/flash_fwd/pallas_call",
    "copy.5": f"{MODEL}/self_attn/attention/transpose",
    "fusion.6": f"{MODEL}/self_attn/attention/q_proj/dot_general",
    "fusion.7": f"{BACK}/self_attn/norm/reduce_sum",
    "fusion.8": f"{MODEL}/mlp/norm/rsqrt",
    "fusion.9": f"{MODEL}/mlp/hidden/dot_general",
    "fusion.10": "jit(step)/optimizer/add",
    "fusion.11": "jit(step)/grad_clip/mul",
    "fusion.12": "jit(step)/jvp(loss)/reduce_sum",
    "fusion.13": "jit(step)/jvp()/add",
    "copy-done.14": "",
    "state_step.1": "state.step",
    "fusion.15": "",
    "fusion.16": "",
}
#: what the fusions hold besides: an AdamW update and the next block's norm in
#: the MLP's matmul; XLA's own fusions by what was fused into them
FUSED = {
    "fusion.9": [f"{BACK}/mlp/hidden/dot_general", "jit(step)/optimizer/mul", f"{MODEL}/mlp/norm/mul"],
    "fusion.10": ["jit(step)/optimizer/add", "jit(step)/optimizer/sqrt"],
    "fusion.15": [f"{MODEL}/mlp/norm/mul", f"{MODEL}/mlp/norm/sub"],
    "fusion.16": [f"{MODEL}/self_attn/attention/transpose", f"{MODEL}/mlp/out/add"],
}
TABLES = (TABLE, FUSED)
#: device milliseconds of each instruction in ONE step; the trace holds two
MS = {
    "flash_fwd.1": 8.0, "flash_bwd_dkv.2": 7.0, "flash_bwd_dq.3": 6.0, "copy.4": 1.0,
    "copy.5": 2.0, "fusion.6": 20.0, "fusion.7": 3.0, "fusion.8": 1.5, "fusion.9": 30.0,
    "fusion.10": 4.0, "fusion.11": 0.5, "fusion.12": 2.5, "fusion.13": 0.25,
    "copy-done.14": 1.25, "fusion.15": 0.75, "fusion.16": 1.75,
    "fusion.99": 0.5,  # in no table
}
EXPECTED = {
    "flash_fwd_device_ms": 8.0, "flash_dkv_device_ms": 7.0, "flash_dq_device_ms": 6.0,
    "attention_glue_device_ms": 3.0,
    "layernorm_alone_device_ms": 5.25, "layernorm_fused_device_ms": 30.0,
    "optimizer_alone_device_ms": 4.5, "optimizer_fused_device_ms": 30.0,
    "unscoped_device_pct": 100.0 * 2.0 / sum(MS.values()),
}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    PER_LAYER = {m["name"]: m for m in json.load(_f)["per_layer"]}


def _event(instruction: str) -> str:
    if instruction.startswith("flash_"):
        return f'%{instruction} = bf16[2,2,128,64] custom-call(%x), custom_call_target="tpu_custom_call"'
    return f"%{instruction} = bf16[2,128,64] {instruction.split('.')[0]}(%x)"


def _trace(steps: int = 2) -> trace_reduce.Trace:
    device = trace_reduce.DeviceTrace("/device:TPU:0")
    t = 0.0
    for _ in range(steps):
        start = t
        for instruction, ms in MS.items():
            device.ops.append((_event(instruction), t, ms * 1e-3))
            t += ms * 1e-3
        device.modules.append(("jit_step(123)", start, t - start))
    return trace_reduce.Trace([device], [], 0.0)


def _ctx(trace):
    return {"trace": trace, "cell": {"name": "toy"}, "mix": {"trace": {"step_module": "jit_step"}}}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_device_reader_sums_its_class_a_step(metric, monkeypatch):
    monkeypatch.setattr(scopes, "tables", lambda cell: TABLES)
    read = harness.load_reader(FILES, metric)
    assert read(_ctx(_trace())) == pytest.approx(EXPECTED[metric])
    assert read(_ctx(None)) is None
    assert read(_ctx(trace_reduce.Trace([], [], 0.0))) is None  # no device plane
    assert read(_ctx(_trace(steps=0))) is None  # no jit_step event
    monkeypatch.setattr(scopes, "tables", lambda cell: None)  # the parent's program
    assert read(_ctx(_trace())) is None


def test_kernel_classes_sum_to_the_custom_call_time_and_every_class_to_the_whole():
    trace = _trace()
    found = scopes.device_seconds(trace, "jit_step", TABLES)
    kernels = sum(found["by_class"][k] for k in scopes.KERNELS)
    assert kernels == pytest.approx(trace.custom_call_s())
    assert found["steps"] == 2 and found["total"] == pytest.approx(2e-3 * sum(MS.values()))
    assert set(found["by_class"]) == {
        *scopes.KERNELS, "attention_glue", "attention_proj", "layernorm", "mlp", "optimizer",
        "loss", "unscoped", "attention_glue+mlp",
    }


@pytest.mark.parametrize("instruction,own,held", [
    ("fusion.9", "mlp", {"mlp", "optimizer", "layernorm"}),  # named by the matmul, holds more
    ("fusion.10", "optimizer", {"optimizer"}),
    ("fusion.15", "layernorm", {"layernorm"}),  # bare: of the one class it holds
    ("fusion.16", "attention_glue+mlp", {"attention_glue", "mlp"}),  # bare, and mixed
    ("copy-done.14", "unscoped", set()),  # bare and empty: nothing is guessed from its operand
    ("fusion.13", "unscoped", set()),
    ("fusion.99", "unscoped", set()),
])
def test_an_operation_is_of_the_class_xla_named_it_by_and_holds_what_was_fused(instruction, own, held):
    assert scopes.classes_of(instruction, False, TABLES) == (own, held)


@pytest.mark.parametrize("op_name,scopes_found", [
    ("jit(step)/transpose(jvp(Model))/decoder/cross_attn/q_norm/mul", ("Model", "decoder", "cross_attn", "q_norm")),
    ("jit(step)/jvp()/jit(_threefry_split)/slice", ("_threefry_split",)),
    ("jit(step)/jvp()/add", ()),
    ("jit(step)/jvp(M)/attention/transpose;jit(step)/jvp(M)/attention/attention.attend/dot", ("M", "attention")),
    ("jit(step)/add", ()),
    ("state.params['encoder']['latents']", ()),
    ("", ()),
])
def test_scopes_of_takes_transformations_off(op_name, scopes_found):
    assert scopes.scopes_of(op_name) == scopes_found


def test_program_without_the_tables_reads_as_none(monkeypatch):
    import perceiver_io_tpu.observability as observability

    class _ParentLedger:  # has no op_scopes, as before the tables existed
        pass

    monkeypatch.setattr(observability, "default_ledger", lambda: _ParentLedger())
    assert scopes.tables("toy") is None


def test_tables_are_kept_beside_a_kept_trace(monkeypatch, tmp_path):
    import perceiver_io_tpu.observability as observability

    class _Ledger:
        def op_scopes(self, site):
            return TABLE if site == "trainer.step" else {}

        def fused_scopes(self, site):
            return FUSED if site == "trainer.step" else {}

    monkeypatch.setattr(observability, "default_ledger", lambda: _Ledger())
    monkeypatch.setenv("BENCH_KEEP_TRACE", str(tmp_path / "kept"))
    assert scopes.tables("toy") == TABLES
    with open(tmp_path / "kept" / "toy.scopes.json") as f:
        assert json.load(f) == [TABLE, FUSED]


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_new_entry_lists_its_workloads(metric):
    entry = PER_LAYER[metric]
    assert entry["workloads"] == CELLS and entry["moves"] == "train_tokens_per_s"
    assert entry["better"] == "lower" and entry["source"] == "device_trace"


def test_every_reader_of_the_scope_tables_has_its_entry():
    readers = {f[:-3] for f in os.listdir(os.path.join(FILES, "metrics"))
               if "scopes." in open(os.path.join(FILES, "metrics", f)).read()}
    assert readers == set(EXPECTED) and readers <= set(PER_LAYER)

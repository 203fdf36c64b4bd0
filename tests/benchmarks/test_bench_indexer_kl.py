"""The readers of the indexer loss's kernels: ``indexer_kl_device_ms`` on a
hand-built trace (the ``indexer_kl*`` Mosaic kernels by name, a step, a
device; nothing from a program without them, as the parent's), and
``indexer_kl_calls`` on the program's counter, declared by every indexer
loss and counted by one that ran the kernels."""
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, trace_reduce
from conftest import ROOT

FILES = os.path.join(ROOT, "benchmarks")

#: a step's events: the two KL kernels, a flash kernel and XLA's operations
MS = {"indexer_kl.3": 4.0, "indexer_kl_grad.4": 16.0, "flash_fwd.1": 1.0, "fusion.5": 2.0,
      "indexer_kl.remat7": 0.0}


def _event(instruction: str) -> str:
    if instruction.startswith(("flash_", "indexer_kl")):
        return f'%{instruction} = f32[1,16384,128] custom-call(%x), custom_call_target="tpu_custom_call"'
    return f"%{instruction} = bf16[8,128] op(%x)"


def _trace(ms=MS, steps=2, devices=1):
    out = []
    for d in range(devices):
        device, t = trace_reduce.DeviceTrace(f"/device:TPU:{d}"), 0.0
        for _ in range(steps):
            start = t
            for instruction, took in ms.items():
                device.ops.append((_event(instruction), t, took * 1e-3))
                t += took * 1e-3
            device.modules.append(("jit_step(1)", start, t - start))
        out.append(device)
    return trace_reduce.Trace(out, [], 0.0)


def _ctx(trace):
    return {"trace": trace, "mix": {"trace": {"step_module": "jit_step"}}}


def test_kernel_reader_sums_the_kl_kernels_a_step_by_name():
    read = harness.load_reader(FILES, "indexer_kl_device_ms")
    assert read(_ctx(_trace())) == pytest.approx(20.0)
    assert read(_ctx(_trace(devices=4))) == pytest.approx(20.0)
    assert read(_ctx(None)) is None and read(_ctx(_trace(steps=0))) is None
    # the parent's program: its indexer loss is XLA's, no such kernel
    parent = {k: v for k, v in MS.items() if not k.startswith("indexer_kl")}
    assert read(_ctx(_trace(parent))) is None


def test_calls_reader_reads_the_programs_counter(monkeypatch):
    import perceiver_io_tpu.observability as observability
    from perceiver_io_tpu.observability import MetricsRegistry
    from perceiver_io_tpu.ops import sparse_attention as sa

    registry = MetricsRegistry()
    monkeypatch.setattr(observability, "default_registry", lambda: registry)
    read = harness.load_reader(FILES, "indexer_kl_calls")
    assert read({}) is None  # a program that never declared it

    def loss(n):
        q = jax.ShapeDtypeStruct((1, 4, n, 32), jnp.float32)
        k = jax.ShapeDtypeStruct((1, 2, n, 32), jnp.float32)
        lse = jax.ShapeDtypeStruct((1, 4, n), jnp.float32)
        q_i = jax.ShapeDtypeStruct((1, n, 2, 16), jnp.float32)
        k_i = jax.ShapeDtypeStruct((1, n, 16), jnp.float32)
        w = jax.ShapeDtypeStruct((1, n, 2), jnp.float32)
        bits = jax.ShapeDtypeStruct((1, -(-n // 32), n), jnp.int32)
        jax.eval_shape(sa.indexer_loss, q, k, lse, q_i, k_i, w, bits)

    loss(80)  # rows that are no whole block: XLA's loss, declared
    assert read({}) == 0.0
    loss(256)  # the kernels, once a trace
    assert read({}) == 1.0
    loss(512)
    assert read({}) == 2.0

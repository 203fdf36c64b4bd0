"""No path hides the device: the smoke refuses a CPU backend before any model
work and prints no result (the benchmark's own refusal and its peak table are
held by tests/benchmarks/test_bench_harness.py); the compile cache goes where
the environment says, or to the one fixed directory in the checkout; a mesh
the TPU topology cannot hold raises; a compile
error is not retried by the plain jitted callable; the einsum path never
stands in for the flash kernel in silence. (Replaces the probe tests of the
scaffolding that talked to the chip through a proxy.)
"""
import importlib.util
import json
import os
import re
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.observability import CompileLedger, MetricsRegistry, default_registry
from perceiver_io_tpu.ops import attention
from perceiver_io_tpu.parallel import MeshConfig, make_mesh
from perceiver_io_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_on_cpu(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, script), *args],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_refuses_a_cpu_backend_before_model_work():
    proc = _run_on_cpu("chip_smoke.py")
    assert proc.returncode not in (0, 2), proc.stderr  # 2 is the debug run's
    lines = proc.stdout.strip().splitlines()
    # it named the device it found and nothing else: no phase, no result
    assert len(lines) == 1 and lines[0].startswith("device: platform=cpu kind=cpu"), lines
    assert "needs a TPU" in proc.stderr
    assert not os.path.exists(os.path.join(ROOT, "chip_smoke_out"))


def test_chip_smoke_verdict_line_holds_exactly_the_contract_keys():
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    line = smoke.verdict_line({"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_compile_cache_honours_the_environment_variable(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.configure_compile_cache() == "/somewhere/else"
    assert calls == []  # JAX reads the variable itself; the code sets nothing


def test_compile_cache_defaults_to_the_fixed_directory_in_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.configure_compile_cache() == os.path.join(ROOT, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_make_mesh_raises_on_a_shape_the_tpu_topology_cannot_hold():
    pytest.importorskip("libtpu")
    from jax.experimental import topologies

    tpus = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    assert make_mesh(MeshConfig(data=2, fsdp=2), devices=tpus).shape["fsdp"] == 2
    with pytest.raises((ValueError, AssertionError)):
        make_mesh(MeshConfig(data=3), devices=tpus[:3])  # no reshape fallback


@pytest.mark.parametrize(
    "b,h,hk,n,d,dtype,window",
    [(2, 32, 8, 8192, 64, jnp.bfloat16, None), (1, 20, 20, 8192, 256, jnp.bfloat16, None),
     (1, 28, 4, 16384, 128, jnp.bfloat16, 4096), (1, 2, 2, 8192, 512, jnp.float32, None)],
    ids=["lfm2moe_cell", "glm47flash_cell", "smallthinker_cell_banded", "float32_512_wide_at_the_budget"],
)
def test_one_kernel_flash_backward_compiles_for_a_v5e_with_the_vmem_it_asks_for(b, h, hk, n, d, dtype, window):
    """The real TPU compiler, Mosaic included, on a described v5e: the
    backward that keeps up to 16 MiB of dQ in VMEM fits the
    ``vmem_limit_bytes`` that ``_fused_vmem_limit`` works out from its
    shapes, at the three ``lm`` cells' and at the widest float32 head the
    budget admits (65.2 MiB needed of the 70 asked for)."""
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perceiver_io_tpu.ops import flash_attention

    one_chip = SingleDeviceSharding(topologies.get_topology_desc("v5e:2x2", "tpu").devices[0])
    q = do = jax.ShapeDtypeStruct((b, h, n, d), dtype, sharding=one_chip)
    k = v = jax.ShapeDtypeStruct((b, hk, n, d), dtype, sharding=one_chip)
    lse = delta = jax.ShapeDtypeStruct((b, h, n, flash_attention.LANES), jnp.float32, sharding=one_chip)
    heads = flash_attention._resident_heads(q, k)
    assert heads > 0

    def backward(q, k, v, lse, delta, do):
        return flash_attention._backward_dkv(q, k, v, None, lse, delta, do, True, window, dq_heads=heads)

    text = jax.jit(backward).lower(q, k, v, lse, delta, do).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "flash_bwd_dkv" in text


def test_indexer_kl_kernels_compile_for_a_v5e_at_the_keyevl2_cell_in_the_vmem_they_ask_for():
    """The indexer loss's two kernels, both of which a training step runs in
    its forward pass, compiled by the real TPU compiler on a described v5e at
    ``keyevl2-train-16k``'s layer (16,384 rows, 32 query heads on 4 of 128,
    16 indexer heads of 64): they fit the ``vmem_limit_bytes`` that
    ``_kl_vmem_bytes`` works out from their shapes, and lower under their own
    names, none of them ``flash_*``."""
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perceiver_io_tpu.ops import sparse_attention

    one_chip = SingleDeviceSharding(topologies.get_topology_desc("v5e:2x2", "tpu").devices[0])
    n = 16384
    shapes = [((1, 32, n, 128), jnp.bfloat16), ((1, 4, n, 128), jnp.bfloat16), ((1, 32, n), jnp.float32),
              ((1, n, 16, 64), jnp.bfloat16), ((1, n, 64), jnp.bfloat16), ((1, n, 16), jnp.float32),
              ((1, n // 32, n), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip) for s, dtype in shapes]
    assert sparse_attention._kl_kernels_fit(*args[:2], args[3])
    lowered = jax.jit(sparse_attention._kl_fwd).lower(*args)
    assert sorted(re.findall(r'kernel_name = "([^"]*)"', lowered.as_text())) == ["indexer_kl", "indexer_kl_grad"]
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 2 and not re.search(r"%flash_\w*(\.\w+)* = ", text)
    assert len(re.findall(r"%indexer_kl(_grad)?(\.\w+)* = ", text)) == 2


def test_ledger_lets_a_compile_error_raise():
    registry = MetricsRegistry()
    ledger = CompileLedger(registry=registry)

    def refuses(x):
        raise NotImplementedError("Mosaic kernels cannot be automatically partitioned")

    wrapped = ledger.wrap(jax.jit(refuses), site="train", components={})
    with pytest.raises(NotImplementedError, match="Mosaic"):
        wrapped(jnp.ones(3))
    # not demoted to the plain callable, which would compile it a second time
    assert registry.counter("compile_ledger_fallback_total") == 0
    assert wrapped.compiled_text() is None


def test_auto_attention_counts_and_warns_when_flash_gives_way(monkeypatch, rng):
    """On a TPU, a multi-block query shape the kernel refuses is counted and
    warned about; a single-query decode step is the einsum path's by design."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    counter = "attention_einsum_fallback_total"
    before = default_registry().counter(counter)

    def qkv(i, j, d):
        return (
            jnp.asarray(rng.normal(size=(1, 2, n, d)), jnp.float32) for n in (i, j, j)
        )

    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the decode shape must not warn
        attention.dot_product_attention(*qkv(1, 256, 64))
    assert default_registry().counter(counter) == before
    with pytest.warns(RuntimeWarning, match="einsum path"):
        attention.dot_product_attention(*qkv(128, 200, 64))  # kv not a block multiple
    assert default_registry().counter(counter) == before + 1


def test_cli_names_the_device_before_model_work(tmp_path, capsys):
    from perceiver_io_tpu.scripts.text import clm

    with pytest.raises(FileNotFoundError):  # no such checkpoint: fails after the line
        clm.main(["serve", "--ckpt", str(tmp_path / "missing")])
    assert "[serve] device: platform=cpu kind=cpu" in capsys.readouterr().err


@pytest.mark.parametrize("form", ["trainer_checkpoint", "pretrained_dir"])
def test_load_pretrained_does_not_inherit_the_mesh_that_saved_it(tmp_path, devices, form):
    """A checkpoint written by a four-device ``fit`` and loaded without a
    target lands on the default device, uncommitted: ``serve`` without mesh
    flags is a one-device program (on a four-chip host the inherited
    placement made it a four-device one, which Mosaic refuses to partition),
    and a sharded engine or trainer can still place it where it wants."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perceiver_io_tpu.models.text.clm import CausalLanguageModelConfig
    from perceiver_io_tpu.training.checkpoint import (
        BestCheckpointManager,
        load_pretrained,
        save_pretrained,
    )

    mesh = make_mesh(MeshConfig(data=2, fsdp=2), devices=devices[:4])
    params = {
        "w": jax.device_put(np.arange(32.0).reshape(8, 4), NamedSharding(mesh, P("fsdp"))),
        "b": jax.device_put(np.ones(4), NamedSharding(mesh, P())),
    }
    config = CausalLanguageModelConfig(max_seq_len=64, max_latents=16, num_channels=32)
    if form == "trainer_checkpoint":
        manager = BestCheckpointManager(str(tmp_path))
        manager.save(3, params, config, val_loss=1.0)
        manager.close()
    else:
        save_pretrained(str(tmp_path), params, config)

    loaded, loaded_config = load_pretrained(str(tmp_path))
    assert loaded_config == config
    for name, leaf in loaded.items():
        assert leaf.sharding.device_set == {jax.devices()[0]}, (name, leaf.sharding)
        assert not leaf.committed, name
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(params[name]))

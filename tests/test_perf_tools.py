"""Smokes for the perf-evidence tooling so it cannot rot between TPU
sessions: the decode context-scaling script (both cache phases) and the
xplane trace summarizer (against a live capture)."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_decode_scaling_both_phases(tmp_path):
    out = tmp_path / "points.jsonl"
    for phase in ("boundary", "latent"):
        proc = subprocess.run(
            [
                sys.executable, "examples/perf/decode_scaling.py",
                "--ctxs", "128", "--num-latents", "64", "--num-channels", "32",
                "--num-layers", "1", "--new-tokens", "4",
                "--phase", phase, "--out", str(out),
            ],
            capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=600,
        )
        assert proc.returncode == 0, proc.stderr
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert {r["phase"] for r in rows} == {"boundary", "latent"}
    for r in rows:
        assert r["cached_tokens_per_sec"] > 0 and r["recompute_tokens_per_sec"] > 0
        assert r["ctx"] == 128


@pytest.mark.slow
def test_trace_summary_on_live_capture(tmp_path):
    """Capture a real (tiny) jax.profiler trace in a subprocess, then
    summarize it: the summarizer must find the xplane, parse it, and print
    at least one per-line table."""
    pytest.importorskip("tensorflow")  # xplane_pb2 provider (sandbox wheel)
    capture = (
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        f"with jax.profiler.trace({str(tmp_path)!r}):\n"
        "    x = jnp.ones((256, 256))\n"
        "    (x @ x).block_until_ready()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", capture],
        capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=300,
    )
    assert proc.returncode == 0, proc.stderr

    proc = subprocess.run(
        [sys.executable, "examples/perf/trace_summary.py", str(tmp_path), "--top", "5"],
        capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert "== plane:" in proc.stdout
    assert "%busy" in proc.stdout


# -- tune_step backend detection --------------------------------------------
@pytest.fixture
def tune_step():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tune_step", str(REPO_ROOT / "examples" / "perf" / "tune_step.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_on_cpu_follows_jax_platforms(tune_step, monkeypatch):
    """The children's backend is what JAX_PLATFORMS says, so the variable
    alone decides whether tpu_only configs skip; the parent starts no probe
    process (it would claim the chip the children need)."""
    def boom(*a, **k):
        raise AssertionError("the parent must not start a process to decide")

    monkeypatch.setattr(tune_step.subprocess, "run", boom)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert tune_step._on_cpu() is True
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert tune_step._on_cpu() is False
    monkeypatch.setenv("JAX_PLATFORMS", "cpu,tpu")  # membership, not equality
    assert tune_step._on_cpu() is True
    monkeypatch.delenv("JAX_PLATFORMS")  # unset: JAX's own default decides
    assert tune_step._on_cpu() is False

"""Smoke for the decode context-scaling script (both cache phases), so it
cannot rot between TPU sessions."""
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

"""On-hardware tuning sweep for the AR train step.

Runs one subprocess per configuration, one at a time (fresh trace, fresh env
knobs, hard timeout so a hung backend cannot take the sweep down) and records
chained step times with the value-fetch fencing from ``bench.py``. The parent
never imports jax, so each child in turn is the one process that holds the
chip; every child's result names the platform and device kind it ran on, and
the children share the persistent compile cache
(``perceiver_io_tpu/utils/compile_cache.py``).

Swept knobs:
- ``attention_impl``: flash vs xla end-to-end
- ``PERCEIVER_FLASH_MIN_KV``: auto-dispatch floor — xla for the short
  (1024×1024) self-attention, flash for the long-kv cross-attention
- ``PERCEIVER_FLASH_BLOCKS``: Pallas block-size schedule

Usage::

    python examples/perf/tune_step.py            # bench shape, full sweep
    python examples/perf/tune_step.py --quick    # small shape smoke
    python examples/perf/tune_step.py --out results.json

Exit is always 0 with a JSON summary on stdout; individual config failures
and timeouts are recorded, not fatal.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import bench  # noqa: E402  (heavy imports inside bench are function-local)

FULL_SHAPE = bench.FULL_SHAPE
QUICK_SHAPE = (2, 2048, 256, 256, 8, 2)

SWEEP = [
    {"name": "flash-default", "impl": "auto", "env": {}},
    {"name": "flash-minkv2048", "impl": "auto", "env": {"PERCEIVER_FLASH_MIN_KV": "2048"}},
    {"name": "flash-minkv1536", "impl": "auto", "env": {"PERCEIVER_FLASH_MIN_KV": "1536"}},
    {"name": "flash-blocks1024", "impl": "auto", "env": {"PERCEIVER_FLASH_BLOCKS": "1024,512,256,128"}},
    {"name": "flash-blocks256", "impl": "auto", "env": {"PERCEIVER_FLASH_BLOCKS": "256,128"}},
    {
        "name": "flash-blocks1024-minkv2048",
        "impl": "auto",
        "env": {"PERCEIVER_FLASH_BLOCKS": "1024,512,256,128", "PERCEIVER_FLASH_MIN_KV": "2048"},
    },
    {"name": "xla", "impl": "xla", "env": {}},
    # Fused same-input projections (modules.py:_fused_dense): one wider
    # matmul for self-attn q/k/v and cross-attn k/v. Exactness-tested on CPU
    # (tests/test_fused_qkv.py); throughput effect is measured here.
    {"name": "flash-fusedqkv", "impl": "auto", "env": {"PERCEIVER_FUSED_QKV": "1"}},
    {
        "name": "flash-fusedqkv-minkv2048",
        "impl": "auto",
        "env": {"PERCEIVER_FUSED_QKV": "1", "PERCEIVER_FLASH_MIN_KV": "2048"},
    },
    # Latency-hiding scheduler: overlaps collective/memory traffic with
    # compute at the XLA schedule level — a pure-flags candidate for the
    # ~20%-MFU dense blocks (appended to ambient XLA_FLAGS by run_one).
    {
        "name": "flash-lhs",
        "impl": "auto",
        "env": {"XLA_FLAGS": "--xla_tpu_enable_latency_hiding_scheduler=true"},
        "tpu_only": True,  # the flag is rejected by the CPU backend
    },
    {
        "name": "flash-fusedqkv-lhs",
        "impl": "auto",
        "env": {
            "PERCEIVER_FUSED_QKV": "1",
            "XLA_FLAGS": "--xla_tpu_enable_latency_hiding_scheduler=true",
        },
        "tpu_only": True,
    },
]


def child(shape, impl: str, trace_dir: str | None = None) -> None:
    import contextlib

    import jax
    import numpy as np

    from perceiver_io_tpu.parallel import shard_batch, single_device_mesh
    from perceiver_io_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    cfg = bench._mk_config(shape)
    batch_size = shape[0]
    mesh = single_device_mesh(jax.devices()[0])
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(batch_size, cfg.max_seq_len + 1), dtype=np.int32)
    with mesh:
        sharded = shard_batch({"input_ids": ids[:, :-1], "labels": ids[:, 1:]}, mesh)
        _, state, step, _ = bench._build_ar(cfg, mesh, impl)
        # When tracing, capture the already-warm chained window only: the
        # xplane then contains just N identical steady-state steps — the
        # per-kernel decomposition the MFU analysis needs.
        ctx = (
            jax.profiler.trace(trace_dir)
            if trace_dir is not None
            else contextlib.nullcontext()
        )
        chained_ms, synced_ms, state, loss = bench._time_train(
            step, state, sharded, jax.random.PRNGKey(1), n_chain=20, n_sync=2
        )
        if trace_dir is not None:
            with ctx:
                for i in range(3):
                    state, metrics = step(state, sharded, jax.random.fold_in(jax.random.PRNGKey(3), i))
                bench._fetch(metrics["loss"])
    device = jax.devices()[0]
    out = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "chained_ms": round(chained_ms, 2),
        "synced_ms": round(synced_ms, 2),
        "loss": round(loss, 4),
        "tokens_per_sec": round(batch_size * cfg.max_seq_len / (chained_ms / 1e3), 1),
    }
    if trace_dir is not None:
        out["trace_dir"] = trace_dir
    print(json.dumps(out), flush=True)


def ceiling_child() -> None:
    import jax

    from perceiver_io_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    print(json.dumps({
        "platform": device.platform,
        "device_kind": device.device_kind,
        "matmul_tflops": round(bench._matmul_ceiling_tflops(), 1),
    }), flush=True)


def run_one(args_list, env_extra, timeout_s):
    # Start from an env with every perf knob stripped: configs must see
    # exactly the knobs they declare, not leftovers from the shell.
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("PERCEIVER_FLASH_") and k != "PERCEIVER_FUSED_QKV"
    }
    # XLA_FLAGS entries append to (not replace) the ambient flags — the host
    # may carry required platform flags.
    if "XLA_FLAGS" in env_extra:
        env_extra = dict(env_extra)
        env_extra["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + " " + env_extra["XLA_FLAGS"]
        ).strip()
    env.update(env_extra)
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *args_list],
            env=env, stdout=subprocess.PIPE, stderr=sys.stderr,
            text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return {"error": "timeout", "wall_s": round(time.monotonic() - t0, 1)}
    if proc.returncode != 0:
        return {"error": f"rc={proc.returncode}", "wall_s": round(time.monotonic() - t0, 1)}
    for line in (proc.stdout or "").splitlines()[::-1]:
        try:
            out = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(out, dict):
            out["wall_s"] = round(time.monotonic() - t0, 1)
            return out
    return {"error": "no JSON result on stdout", "wall_s": round(time.monotonic() - t0, 1)}


def _on_cpu() -> bool:
    """True when ``JAX_PLATFORMS`` names the CPU (membership, not equality:
    'cpu,tpu' etc.): the children then run there, and ``tpu_only`` sweep
    configs, whose XLA flags a CPU backend rejects, skip. Unset, the children
    land on whatever JAX finds; a ``tpu_only`` config on a host without a
    chip is then recorded as a failed config like any other."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    return "cpu" in [p.strip() for p in platforms.split(",")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument(
        "--trace", default=None, metavar="NAME",
        help="run only the named sweep config with a jax.profiler device "
        "trace of 3 steady-state steps (xplane written under "
        "<out dir>/trace-NAME) — the per-kernel decomposition for MFU "
        "analysis",
    )
    args = ap.parse_args()
    shape = QUICK_SHAPE if args.quick else FULL_SHAPE
    shape_arg = ",".join(map(str, shape))

    if args.trace is not None:
        cfg = next((c for c in SWEEP if c["name"] == args.trace), None)
        if cfg is None:
            raise SystemExit(
                f"unknown config {args.trace!r}; choose from "
                f"{[c['name'] for c in SWEEP]}"
            )
        if cfg.get("tpu_only") and _on_cpu():
            raise SystemExit(f"{cfg['name']} is a tpu-only config; needs hardware")
        trace_dir = os.path.abspath(
            os.path.join(os.path.dirname(args.out or "."), f"trace-{cfg['name']}")
        )
        r = run_one(
            ["--child", shape_arg, cfg["impl"], trace_dir], cfg["env"], args.timeout
        )
        print(json.dumps({"shape": list(shape), "trace": r}))
        return

    results = {"shape": list(shape), "configs": {}}
    print(f"[tune] matmul ceiling...", file=sys.stderr, flush=True)
    results["ceiling"] = run_one(["--ceiling"], {}, min(args.timeout, 300.0))
    print(f"[tune] ceiling: {results['ceiling']}", file=sys.stderr, flush=True)

    for cfg in SWEEP:
        if cfg.get("tpu_only") and _on_cpu():
            results["configs"][cfg["name"]] = {"skipped": "tpu-only config"}
            print(f"[tune] {cfg['name']}: skipped (tpu-only)", file=sys.stderr, flush=True)
            continue
        print(f"[tune] {cfg['name']}...", file=sys.stderr, flush=True)
        r = run_one(["--child", shape_arg, cfg["impl"]], cfg["env"], args.timeout)
        results["configs"][cfg["name"]] = r
        print(f"[tune] {cfg['name']}: {r}", file=sys.stderr, flush=True)

    print(json.dumps(results))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(
            tuple(int(x) for x in sys.argv[2].split(",")),
            sys.argv[3],
            trace_dir=sys.argv[4] if len(sys.argv) > 4 else None,
        )
    elif len(sys.argv) > 1 and sys.argv[1] == "--ceiling":
        ceiling_child()
    else:
        main()

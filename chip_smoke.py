"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process drives the main path once, through the entry points a user would
call, at the full width of the one model with any chip history: Perceiver AR,
vocab 262, 8192 ctx / 1024 latents / 512 channels / 8 heads / 8 layers,
batch 8, bf16 training. Weights are random, made from a seed. It fails, and
prints no result, on anything but a TPU; on a TPU it runs these phases and
exits 0 only if every check in every phase held (no phase's failure is
caught):

- **kernels**: the flash kernel forward and backward against the einsum path
  (causal without pad at batch 8; causal with a pad mask at batch 8;
  non-causal with a pad mask, the MLM form) and the ragged paged kernel
  against the gather oracle (q_len 1, q_len ``max_latents``, int8), at this
  model's head shapes. Each compiled program's text must hold the Mosaic
  kernel (``tpu_custom_call``), so neither the Pallas interpreter nor the
  einsum path can stand in.
- **train**: ``perceiver_io_tpu.scripts.text.clm.main(["fit", ...])`` on the
  synthetic datamodule's own batches (pad mask included), with a validation
  pass and a checkpoint; then the same step, built from the same public
  factories, timed twice after warm-up: fenced by ``jax.block_until_ready``
  and by a host value fetch.
- **serve**: ``main(["serve", "--ckpt", <that checkpoint>, ...])`` on the slot
  engine with the paged layout, prompts on both sides of ``max_latents`` (so
  latent-growth and boundary steps both run), every choice pinned and a short
  bucket grid, so the cold compile count is known.
- **mesh** (when JAX reports four or more devices): the same fit under
  ``--mesh.data=2 --mesh.fsdp=2`` and the same serve under
  ``--serve.mesh.data=2 --serve.mesh.model=2``, with shards and live bytes
  required on four distinct devices.

The last line of standard output is the verdict and nothing else,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reports it: the driver that reads it accepts exactly those
keys. The line before it, ``summary: {...}``, is one JSON object with each
phase's seconds, set-up (lowering and compilation) apart from run seconds,
the compile count and both step timings; the same object is written to
``summary.json`` in the output directory.

Everything it writes goes under its output directory (``chip_smoke_out/``
beside this file, emptied at start); the compile cache goes where
``perceiver_io_tpu/utils/compile_cache.py`` puts it.

``--debug-tiny`` runs the same control flow at a toy size on whatever
platform JAX finds, for debugging the script itself. It names the platform,
prints the summary but no verdict line and always exits 2: it is never a pass
for the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# ctx / latents / channels / heads / layers / batch: the `perceiver-ar-8k`
# configuration's widths (benchmarks/configs/) at batch 8
FULL = dict(
    ctx=8192, latents=1024, channels=512, heads=8, layers=8, batch=8,
    steps=30, timed_steps=10,
    # serve: prompts of exactly the first bucket (below max_latents: their
    # rows decode in latent growth) and above max_latents (boundary phase
    # from the first token); num_latents = max_latents makes that split
    buckets=(768, 2048, 4096), prompt_lens=(768, 768, 768, 768, 1500, 2048, 1800, 3000, 4096, 2500),
    slots=8, new_tokens=24, block_size=16,
    # kernel shapes: the train step's cross-attention after prefix dropout
    # (kv 4608), and the MLM form
    flash=dict(b=8, q=1024, kv=4608), mlm=dict(b=8, q=256, kv=2048),
    ragged_rows=8, window_rows=4,
)
TINY = dict(
    ctx=256, latents=64, channels=128, heads=4, layers=1, batch=4,
    steps=4, timed_steps=2,
    buckets=(48, 128, 192), prompt_lens=(48, 48, 100, 128, 150, 192, 48, 170),
    slots=2, new_tokens=6, block_size=16,
    flash=dict(b=2, q=128, kv=384), mlm=dict(b=2, q=128, kv=256),
    ragged_rows=3, window_rows=2,
)

MIN_LIVE_BYTES = 32 << 20  # "non-trivial" per-device footprint in the mesh phase


def say(msg):
    print(f"[smoke +{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


# ---------------------------------------------------------------- kernels


def _rel_err(got, want):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.all(np.isfinite(got)), "non-finite kernel output"
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


def _compile_with_kernel(fn, args, on_tpu, at_least):
    """AOT-compile ``fn`` and require the Mosaic kernel in the program."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    kernels = compiled.as_text().count("tpu_custom_call")
    if on_tpu:
        assert kernels >= at_least, (
            f"expected >= {at_least} Mosaic kernels in the compiled program, "
            f"found {kernels}"
        )
    return compiled, kernels


def check_flash(name, shape, heads, *, causal, pad, on_tpu, tol=3e-2):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.ops.attention import dot_product_attention

    b, i, j, d = shape["b"], shape["q"], shape["kv"], 64
    rng = np.random.default_rng(0)

    def arr(*s, scale=1.0):
        return jnp.asarray(rng.normal(size=s) * scale, jnp.bfloat16)

    q, k, v = arr(b, heads, i, d, scale=d ** -0.5), arr(b, heads, j, d), arr(b, heads, j, d)
    do = arr(b, heads, i, d)
    pad_mask = None
    if pad:
        # left padding of a different length per row, inside the prefix, so
        # that no query row is fully masked (the two paths differ there by
        # design, ops/flash_attention.py)
        counts = (np.arange(b) * ((j - i) // max(b, 1))) // 2
        pad_mask = jnp.asarray(np.arange(j)[None, :] < counts[:, None])

    def fwd_bwd(impl):
        def run(q, k, v, do):
            out, vjp = jax.vjp(
                lambda q, k, v: dot_product_attention(
                    q, k, v, pad_mask=pad_mask, causal=causal, impl=impl
                ),
                q, k, v,
            )
            return (out,) + vjp(do)

        return run

    compiled, kernels = _compile_with_kernel(
        fwd_bwd("flash"), (q, k, v, do), on_tpu, at_least=2  # forward, and one backward: dQ fits VMEM
    )
    got = compiled(q, k, v, do)
    want = jax.jit(fwd_bwd("xla"))(q, k, v, do)
    errs = {n: _rel_err(g, w) for n, g, w in zip(("out", "dq", "dk", "dv"), got, want)}
    say(f"kernels: flash {name} b{b} q{i} kv{j}: {kernels} Mosaic kernels, "
        f"max error / max |reference| = {errs}")
    assert all(e < tol for e in errs.values()), (name, errs)
    return {"mosaic_kernels": kernels, **{f"err_{n}": round(e, 5) for n, e in errs.items()}}


def check_ragged(name, cfg, *, rows, q_len, int8, on_tpu, tol=3e-2):
    """The ragged kernel against the gather path's dense view: rows of
    ragged length (one idle, one shorter than the query window) over pages
    scattered through the pool, garbage parked in the null block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.ops import paged_attention as paged
    from perceiver_io_tpu.ops.ragged_attention import ragged_paged_attention

    h, d, bs, n = cfg["heads"], 64, cfg["block_size"], cfg["ctx"]
    pages = n // bs
    rng = np.random.default_rng(1)
    # full window down to a span shorter than the query window, then an idle row
    lengths = np.append(np.linspace(n, q_len // 2 + 1, rows - 1), 0).astype(np.int32)
    blocks = 1 + rng.permutation(rows * pages)  # block 0 is the null block
    table = np.zeros((rows, pages), np.int32)
    for r in range(rows):
        used = -(-int(lengths[r]) // bs)
        table[r, :used] = blocks[r * pages:r * pages + used]
    tokens = (rows * pages + 1) * bs
    pool_k = rng.normal(size=(tokens, h, d)).astype(np.float32)
    pool_v = rng.normal(size=(tokens, h, d)).astype(np.float32)
    pool_k[:bs], pool_v[:bs] = 1e3, -1e3  # must never surface
    q = jnp.asarray(rng.normal(size=(rows, h, q_len, d)) * d ** -0.5, jnp.bfloat16)
    if int8:
        pool_k, scale_k = paged.quantize_kv(jnp.asarray(pool_k))
        pool_v, scale_v = paged.quantize_kv(jnp.asarray(pool_v))
    else:
        pool_k, pool_v = jnp.asarray(pool_k, jnp.bfloat16), jnp.asarray(pool_v, jnp.bfloat16)
        scale_k = scale_v = None
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)

    def kernel(q, pool_k, pool_v, table, lengths, scale_k, scale_v):
        return ragged_paged_attention(
            q, pool_k, pool_v, table, lengths, block_size=bs,
            scale_k=scale_k, scale_v=scale_v,
        )

    def oracle(q, pool_k, pool_v, table, lengths, scale_k, scale_v):
        flat = paged.flat_position_indices(table, bs, n)
        k = paged.gather_kv(pool_k, flat, scale_k, jnp.float32).astype(jnp.float32)
        v = paged.gather_kv(pool_v, flat, scale_v, jnp.float32).astype(jnp.float32)

        def row(args):  # one row at a time: (h, q_len, n) logits, not (b, ...)
            q_r, k_r, v_r, length = args
            pos = jnp.arange(n)[None, :]
            qi = jnp.arange(q_len)[:, None]
            valid = (pos + (q_len - 1) - qi < length)[None]  # right-aligned causal
            s = jnp.einsum("hqd,hnd->hqn", q_r.astype(jnp.float32), k_r)
            s = jnp.where(valid, s, -1e30)
            p = jnp.where(valid, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
            den = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
            return jnp.einsum("hqn,hnd->hqd", p / den, v_r)

        return jax.lax.map(row, (q, k, v, lengths))

    args = (q, pool_k, pool_v, table, lengths, scale_k, scale_v)
    compiled, kernels = _compile_with_kernel(kernel, args, on_tpu, at_least=1)
    temp = compiled.memory_analysis().temp_size_in_bytes if on_tpu else None
    got = compiled(*args)
    want = jax.jit(oracle)(*args)
    err = _rel_err(got, want)
    assert float(jnp.max(jnp.abs(got[-1].astype(jnp.float32)))) == 0.0, "idle row must be zeros"
    say(f"kernels: ragged {name} rows{rows} q_len{q_len}: {kernels} Mosaic kernels, "
        f"max error / max |oracle| = {err:.5f}, temp bytes {temp}")
    assert err < tol, (name, err)
    return {"mosaic_kernels": kernels, "err": round(err, 5), "temp_bytes": temp}


def phase_kernels(cfg, on_tpu):
    h = cfg["heads"]
    return {
        "flash_causal": check_flash("causal", cfg["flash"], h, causal=True, pad=False, on_tpu=on_tpu),
        "flash_causal_pad": check_flash("causal+pad", cfg["flash"], h, causal=True, pad=True, on_tpu=on_tpu),
        "flash_mlm_pad": check_flash("non-causal+pad", cfg["mlm"], h, causal=False, pad=True, on_tpu=on_tpu),
        "ragged_decode": check_ragged(
            "decode", cfg, rows=cfg["ragged_rows"], q_len=1, int8=False, on_tpu=on_tpu),
        "ragged_window": check_ragged(
            "window", cfg, rows=cfg["window_rows"], q_len=cfg["latents"], int8=False, on_tpu=on_tpu),
        "ragged_int8": check_ragged(
            "int8", cfg, rows=cfg["ragged_rows"], q_len=1, int8=True, on_tpu=on_tpu),
    }


# ------------------------------------------------------------------ train


def _data_flags(cfg, out_dir):
    chunk = cfg["ctx"] + 1  # the CLM view's rows
    return {
        "dataset_dir": os.path.join(out_dir, "data"),
        "max_seq_len": cfg["ctx"],
        "batch_size": cfg["batch"],
        # two training batches and one validation batch of full chunks
        "num_train_docs": 2 * cfg["batch"] + 1,
        "num_valid_docs": cfg["batch"] + 1,
        "doc_chars": chunk,
    }


def _model_flags(cfg):
    return {
        "max_latents": cfg["latents"],
        "num_channels": cfg["channels"],
        "num_heads": cfg["heads"],
        "num_self_attention_layers": cfg["layers"],
    }


def run_fit(cfg, out_dir, name, mesh_axes):
    """The CLI's ``fit`` and what it left behind: (state, root dir)."""
    import numpy as np

    from perceiver_io_tpu.scripts.text import clm

    root = os.path.join(out_dir, name)
    argv = ["fit", "--data=synthetic"]
    argv += [f"--data.{k}={v}" for k, v in _data_flags(cfg, out_dir).items()]
    argv += [f"--model.{k}={v}" for k, v in _model_flags(cfg).items()]
    argv += [f"--mesh.{k}={v}" for k, v in mesh_axes.items()]
    argv += [
        f"--trainer.max_steps={cfg['steps']}",
        f"--trainer.val_check_interval={cfg['steps']}",
        "--trainer.log_every_n_steps=1",
        "--trainer.enable_tensorboard=false",
        f"--trainer.default_root_dir={root}",
        "--optimizer.lr=1e-3",
        "--lr_scheduler.warmup_steps=5",
    ]
    say(f"{name}: clm.main({argv})")
    state = clm.main(argv)

    assert int(state.step) == cfg["steps"], int(state.step)
    with open(os.path.join(root, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    train = {r["step"]: r["train/loss"] for r in rows if "train/loss" in r}
    val = [r["val/loss"] for r in rows if "val/loss" in r]
    assert sorted(train) == list(range(1, cfg["steps"] + 1)), sorted(train)
    assert all(np.isfinite(l) for l in train.values()), train
    assert len(val) == 1 and np.isfinite(val[0]), val
    ckpt = os.path.join(root, "checkpoints")
    assert any(d.isdigit() for d in os.listdir(ckpt)), os.listdir(ckpt)
    say(f"{name}: {cfg['steps']} steps, loss {train[1]:.4f} -> {train[cfg['steps']]:.4f}, "
        f"val loss {val[0]:.4f}, checkpoint in {ckpt}")
    detail = {"steps": cfg["steps"], "first_loss": round(train[1], 4),
              "last_loss": round(train[cfg["steps"]], 4), "val_loss": round(val[0], 4)}
    return state, ckpt, detail


def time_step(cfg, out_dir, state, mesh_axes, on_tpu):
    """The train step the CLI ran, rebuilt from the same public factories on
    the datamodule's own batch, timed after warm-up under both fences."""
    import jax

    from perceiver_io_tpu.models.text.clm import CausalLanguageModelConfig
    from perceiver_io_tpu.parallel import MeshConfig, make_mesh, make_train_step, shard_or_assemble
    from perceiver_io_tpu.scripts.text import clm

    dm = clm.DATA["synthetic"](task="clm", padding_side="left", **_data_flags(cfg, out_dir))
    dm.prepare_data()
    dm.setup()
    batch = next(iter(dm.train_dataloader()))
    assert set(batch) == {"input_ids", "labels", "pad_mask"}, set(batch)
    model_cfg = CausalLanguageModelConfig(
        vocab_size=dm.vocab_size, max_seq_len=dm.max_seq_len, **_model_flags(cfg)
    )
    model = clm.FAMILY.build_model(model_cfg, dm)
    mesh = make_mesh(MeshConfig(**mesh_axes))
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, state)
    step = make_train_step(clm.FAMILY.make_loss(model, model_cfg), mesh, shardings)
    sharded = shard_or_assemble(batch, mesh)
    key = jax.random.PRNGKey(7)

    compiled = step.lower(state, sharded, key).compile()
    kernels = compiled.as_text().count("tpu_custom_call")
    if on_tpu:
        assert kernels > 0, "the compiled train step holds no Mosaic kernel"

    def chain(n, offset, fence):
        nonlocal state
        t0 = time.perf_counter()
        for i in range(n):
            state, metrics = compiled(state, sharded, jax.random.fold_in(key, offset + i))
        fence(metrics["loss"])
        return (time.perf_counter() - t0) / n * 1e3

    n = cfg["timed_steps"]
    chain(3, 0, jax.block_until_ready)  # warm-up
    ready_ms = chain(n, 100, jax.block_until_ready)
    fetch_ms = chain(n, 200, float)
    say(f"train: {n} chained steps after warm-up: {ready_ms:.2f} ms/step fenced by "
        f"jax.block_until_ready | {fetch_ms:.2f} ms/step fenced by a host value fetch "
        f"({kernels} Mosaic kernels in the step)")
    return state, sharded, {
        "mosaic_kernels": kernels,
        "step_ms_block_until_ready": round(ready_ms, 3),
        "step_ms_value_fetch": round(fetch_ms, 3),
    }


# ------------------------------------------------------------------ serve


class LiveBytes(threading.Thread):
    """Largest ``bytes_in_use`` seen on each device while a phase runs."""

    def __init__(self, devices):
        super().__init__(daemon=True)
        self.devices = devices
        self.peak = [0] * len(devices)
        self._done = threading.Event()

    def run(self):
        while not self._done.wait(0.25):
            for i, d in enumerate(self.devices):
                stats = d.memory_stats() or {}
                self.peak[i] = max(self.peak[i], int(stats.get("bytes_in_use", 0)))

    def stop(self):
        self._done.set()
        self.join()
        return self.peak


def run_serve(cfg, out_dir, name, ckpt, mesh_axes, on_tpu):
    import jax
    import numpy as np

    from perceiver_io_tpu.observability import default_registry
    from perceiver_io_tpu.scripts.text import clm
    from perceiver_io_tpu.serving import slots as slots_mod

    rng = np.random.default_rng(3)
    alphabet = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    prompts = ["".join(rng.choice(alphabet, size=n)) for n in cfg["prompt_lens"]]
    assert min(cfg["prompt_lens"]) < cfg["latents"] < max(cfg["prompt_lens"])
    prompts_file = os.path.join(out_dir, f"{name}_prompts.txt")
    with open(prompts_file, "w") as f:
        f.write("\n".join(prompts) + "\n")
    argv = [
        "serve", "--ckpt", ckpt, "--serve.engine=slots", f"--serve.prompts={prompts_file}",
        "--serve.kv_layout=paged", f"--serve.kv_block_size={cfg['block_size']}",
        "--serve.speculation=off", "--serve.decode_strategy=cached",
        "--serve.prefix_cache=off",
        "--serve.prompt_buckets=" + ",".join(map(str, cfg["buckets"])),
        f"--serve.slots={cfg['slots']}", f"--serve.max_new_tokens={cfg['new_tokens']}",
        f"--serve.num_latents={cfg['latents']}",
    ]
    argv += [f"--serve.mesh.{k}={v}" for k, v in mesh_axes.items()]
    say(f"{name}: clm.main({argv})")
    before = set(slots_mod._EXECUTOR_CACHE)
    sampler = LiveBytes(jax.devices())
    sampler.start()
    stdout = io.StringIO()
    try:
        with contextlib.redirect_stdout(stdout):
            rows = clm.main(argv)
    finally:
        live = sampler.stop()
    stats = json.loads(stdout.getvalue().splitlines()[-1])["serve_stats"]

    n = len(prompts)
    assert len(rows) == n and all(r["status"] == "ok" for r in rows), rows
    assert stats["completed"] == n, stats["completed"]
    assert stats["tokens_generated"] == n * cfg["new_tokens"], stats["tokens_generated"]
    for key in ("rejected", "failed", "timed_out", "shed", "cancelled"):
        assert stats[key] == 0, (key, stats[key])
    assert default_registry().counter("compile_ledger_fallback_total") == 0
    assert default_registry().counter("attention_einsum_fallback_total") == 0
    # one prefill per bucket, the decode step and its boundary variant, all
    # built by the warm-up: a later build would push the count past this
    expected = len(cfg["buckets"]) + 2
    assert stats["compiles"] == expected, (stats["compiles"], expected)

    # the executors this run built: the prefills and the boundary step (the
    # multi-query attends) must hold the Mosaic kernel
    built = {k: v for k, v in slots_mod._EXECUTOR_CACHE.items() if k not in before}
    assert len(built) == expected, sorted(k[0] for k in built)
    kernels = {}
    for key, executor in built.items():
        label = f"{key[0]}{key[-2:] if key[0] == 'slot_decode' else key[-1:]}"
        text = executor.compiled_text()
        assert text is not None, f"{label} never compiled ahead of time"
        kernels[label] = text.count("tpu_custom_call")
        multi_query = key[0] == "slot_prefill" or (key[0] == "slot_decode" and key[-2])
        if on_tpu and multi_query:
            assert kernels[label] > 0, f"{label} holds no Mosaic kernel"
    say(f"{name}: {n} requests ok, {stats['tokens_generated']} tokens, "
        f"{stats['compiles']} executors compiled, none after warm-up; "
        f"Mosaic kernels per executor: {kernels}; wall {stats['wall_s']} s; "
        f"ttft p50/p95 {stats['ttft_ms']['p50']}/{stats['ttft_ms']['p95']} ms, "
        f"inter-token p50/p95 {stats['inter_token_ms']['p50']}/{stats['inter_token_ms']['p95']} ms")
    return {
        "requests": n, "tokens_generated": stats["tokens_generated"],
        "executors_compiled": stats["compiles"], "mosaic_kernels": kernels,
        "drain_wall_s": stats["wall_s"], "live_bytes_per_device": live,
    }


# ------------------------------------------------------------------- main


def verdict_line(info):
    """The last line of standard output. The driver that reads it accepts
    exactly these keys, so everything else goes on the line before."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(info["platform"]), "kind": str(info["kind"]),
                   "count": int(info["count"])},
    })


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    parser.add_argument(
        "--debug-tiny", action="store_true",
        help="toy size on whatever platform JAX finds; prints no result, exits 2",
    )
    args = parser.parse_args()

    import jax

    device = jax.devices()[0]
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.debug_tiny:
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {device.platform!r}. No result.")
    cfg = TINY if args.debug_tiny else FULL
    if args.debug_tiny:
        say(f"DEBUG RUN at a toy size on platform={device.platform}: not a chip result")

    from perceiver_io_tpu.utils.compile_cache import configure_compile_cache

    if args.debug_tiny:  # a toy run leaves nothing in the cache
        jax.config.update("jax_enable_compilation_cache", False)
    cache_dir = configure_compile_cache()
    out_dir = os.path.abspath(args.out)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    os.chdir(out_dir)  # nothing lands in the checkout by default paths
    say(f"output directory {out_dir}; compile cache {cache_dir}")

    from perceiver_io_tpu.observability import default_ledger

    def built():
        """What JAX has spent building programs so far (lowering and the
        backend, a persistent-cache hit being a short compile), the programs
        and the cache's hits among them, by the ledger's one listener.
        Tracing is left with the run seconds: its events nest, and
        compilation is what dominates a cold start."""
        totals = default_ledger().jax_totals()
        return (totals["lower_s"] + totals["backend_s"], totals["backend_compiles"],
                totals["cache_hits"])

    phases = {}

    def phase(name, fn):
        say(f"== phase {name}")
        setup0, compiles0, hits0 = built()
        t0 = time.perf_counter()
        detail = fn()
        seconds = time.perf_counter() - t0
        setup1, compiles1, hits1 = built()
        phases[name] = {
            "seconds": round(seconds, 2),
            "setup_seconds": round(setup1 - setup0, 2),
            "run_seconds": round(seconds - (setup1 - setup0), 2),
            "compiles": compiles1 - compiles0,
            "cache_hits": hits1 - hits0,
            **detail,
        }
        say(f"== phase {name} passed: {phases[name]['seconds']} s "
            f"({phases[name]['setup_seconds']} s set-up, {phases[name]['compiles']} "
            f"compiles of which {phases[name]['cache_hits']} from the cache)")

    phase("kernels", lambda: phase_kernels(cfg, on_tpu))

    held = {}

    def train():
        state, held["ckpt"], detail = run_fit(cfg, out_dir, "fit", {})
        state, _, timing = time_step(cfg, out_dir, state, {}, on_tpu)
        del state
        return {**detail, **timing}

    phase("train", train)
    phase("serve", lambda: run_serve(cfg, out_dir, "serve", held["ckpt"], {}, on_tpu))

    if jax.device_count() >= 4:
        def mesh():
            axes = {"data": 2, "fsdp": 2}
            state, _, detail = run_fit(cfg, out_dir, "fit_mesh", axes)
            state, batch, timing = time_step(cfg, out_dir, state, axes, on_tpu)
            for what, tree in (("parameters", state.params), ("batch", batch)):
                devices = set()
                for leaf in jax.tree_util.tree_leaves(tree):
                    devices |= {s.device for s in leaf.addressable_shards}
                assert len(devices) >= 4, f"{what} live on {len(devices)} devices"
            fit_live = [
                (d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices()[:4]
            ]
            assert not on_tpu or min(fit_live) >= MIN_LIVE_BYTES, fit_live
            del state, batch
            served = run_serve(
                cfg, out_dir, "serve_mesh", held["ckpt"], {"data": 2, "model": 2}, on_tpu
            )
            serve_live = served["live_bytes_per_device"][:4]
            assert not on_tpu or min(serve_live) >= MIN_LIVE_BYTES, serve_live
            say(f"mesh: shards on four devices; live bytes per device: fit {fit_live}, "
                f"serve {serve_live}")
            return {"fit": {**detail, **timing, "live_bytes_per_device": fit_live},
                    "serve": served}

        phase("mesh", mesh)

    setup_s, compiles, hits = built()
    total = time.monotonic() - _T0
    summary = json.dumps({
        "device": info,
        "seconds": round(total, 1),
        "setup_seconds": round(setup_s, 1),
        "run_seconds": round(total - setup_s, 1),
        "compile_count": compiles,
        "compile_cache_hits": hits,
        "compile_cache_dir": cache_dir,
        "step_ms": {
            "block_until_ready": phases["train"]["step_ms_block_until_ready"],
            "value_fetch": phases["train"]["step_ms_value_fetch"],
        },
        "phases": phases,
    })
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        f.write(summary + "\n")
    print(f"summary: {summary}", flush=True)
    if args.debug_tiny:
        say(f"debug run finished on platform={device.platform} without a failed check; "
            "this is not a chip result: no verdict line, exit 2")
        sys.exit(2)
    print(verdict_line(info), flush=True)


if __name__ == "__main__":
    main()

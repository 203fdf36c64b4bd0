"""Benchmark: Perceiver AR 8k-context training throughput on one chip, plus
the Perceiver IO MLM training config, cached-decode throughput, a
mixed-length bucketed-serving probe (``extras.serve``: tokens/s,
compile_count, p50/p95 queue wait — the serving-layer trajectory), and an
instrumented telemetry probe (``extras.observability``: per-phase latency
histograms, goodput, MFU gauges; docs/observability.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} with
secondary metrics under "extras"; the record also carries the process-wide
registry snapshot (``metrics_snapshot``) and the device-cost ledger
(``compile_ledger``: per-executor compile time, XLA cost/memory analysis,
retrace attribution — docs/observability.md) so BENCH_* files ship
telemetry and are ``obs report``-able offline.

The reference publishes no throughput numbers (BASELINE.md), so the baseline
is the north star from BASELINE.json: **0.8× an A100 on the same step**. The
A100 step time is estimated analytically: training FLOPs (fwd + 2× bwd) on
the same configuration at 312 bf16 TFLOP/s × 40% MFU — a generous MFU for
the reference's eager torch implementation (no flash attention, no fusion;
measured MFUs for it would be lower, making this baseline conservative).
``vs_baseline`` > 1.0 means this framework beats that target.

Timing methodology:

- JAX returns before the device finishes, so every timed region ends in a
  fence. The primary number is **chained** timing: N train steps whose
  TrainState is donated, so step k+1's inputs are step k's outputs and
  device execution serializes, with one host value fetch (``float(loss)``)
  at the end. This matches real training (loss is not fetched every step)
  and amortizes the per-call dispatch over the chain. The per-step-fetch
  median is also recorded (``step_time_ms_synced``) as the conservative
  upper bound. ``chip_smoke.py`` prints the same step fenced both ways
  (``jax.block_until_ready`` and a value fetch) side by side.
- MFU is validated: a record with mfu outside (0, 1) is refused, and peak
  FLOPs come from the detected device kind; a kind missing from the table
  is an error, not a default.
- The Pallas flash path is cross-checked against the XLA einsum path every
  run (same params, same batch, same dropout rng): the loss difference and
  both forward times land in the record, and a mismatch beyond tolerance
  aborts the run without a record.

Config: the 8k-context north-star shape (BASELINE.json `configs`): Perceiver
AR, vocab 262 (UTF-8 bytes), 8192 ctx / 1024 latents, 512 channels, 8 layers
— the reference's WikiText-103 model (reference
``examples/training/clm/train.py``) widened to the 8k context it targets for
long-context work (``docs/training-examples.md:158-162`` scale). The MLM
extra uses the ``deepmind/language-perceiver`` shape (201M params: d_model
768, 256×1280 latents, 26 layers, ctx 2048) the reference fine-tunes in
``docs/training-examples.md:90-118``.

One process holds the chip: ``main`` places the compile cache
(``perceiver_io_tpu/utils/compile_cache.py``), checks that JAX found a TPU and
runs every stage itself. With no chip it exits non-zero and prints no record;
there is no CPU fallback. Stage progress goes to stderr; budget knob:
``BENCH_DEADLINE_S`` (default 900) gates the optional extras.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

GLOBAL_DEADLINE_S = float(os.environ.get("BENCH_DEADLINE_S", "900"))
_T0 = time.monotonic()

METRIC = "perceiver_ar_8k_train_tokens_per_sec_per_chip"

A100_BF16_FLOPS = 312e12
A100_ASSUMED_MFU = 0.40
BASELINE_FACTOR = 0.8  # north star: >= 0.8x A100 step time

# (batch, seq, latents, channels, heads, layers)
FULL_SHAPE = (8, 8192, 1024, 512, 8, 8)
# the same model reduced for the `make *-bench` CPU drills, which count and
# check but publish no device number
DRILL_SHAPE = (1, 2048, 256, 256, 8, 4)

# bf16 peak FLOP/s by device kind substring (lowercased match, first hit wins).
_PEAK_BY_KIND = (
    ("v5 lite", 197e12),   # v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12),        # Trillium
    ("v4", 275e12),
    ("v3", 123e12),
)


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------- stages


def _mk_config(shape):
    from perceiver_io_tpu.models.text.clm import CausalLanguageModelConfig

    batch, seq, latents, channels, heads, layers = shape
    return CausalLanguageModelConfig(
        vocab_size=262,
        max_seq_len=seq,
        max_latents=latents,
        num_channels=channels,
        num_heads=heads,
        num_self_attention_layers=layers,
        cross_attention_dropout=0.5,
    )


def ar_train_flops(cfg, batch: int) -> float:
    """fwd+bwd FLOPs of one AR train step via the shared scaling-study
    estimator (utils/flops.py; VERDICT r2 ask #1e — no duplicate math here).
    prefix_dropout=0 counts the full prefix: the upper bound, so MFU is not
    flattered by the dropped-prefix steps."""
    from perceiver_io_tpu.utils.flops import ComputeEstimator, training_flops_per_step

    est = ComputeEstimator(
        vocab_size=cfg.vocab_size,
        max_seq_len=cfg.max_seq_len,
        num_latents=cfg.max_latents,
    )
    return float(
        training_flops_per_step(
            est,
            num_channels=cfg.num_channels,
            num_layers=cfg.num_self_attention_layers + 1,  # + hybrid cross layer
            batch_size=batch,
            prefix_dropout=0.0,
        )
    )


def peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for sub, peak in _PEAK_BY_KIND:
        if sub in kind:
            return peak
    raise ValueError(
        f"no bf16 peak FLOP/s on record for device kind {device.device_kind!r}; "
        "add it to _PEAK_BY_KIND with its source"
    )


def _fetch(x) -> float:
    """Host value fetch: waits for the device to produce the value."""
    return float(x)


def _matmul_ceiling_tflops(dim: int = 4096) -> float:
    """Measured bf16 matmul throughput — the chip's *practical* ceiling,
    recorded so the MFU figure is interpretable against what this device
    actually delivers rather than only the nominal peak.

    Methodology: K matmuls chained inside ONE jitted ``fori_loop`` (one
    dispatch, one value-fetch fence), at two different K; the differenced
    time cancels both the dispatch and the fetch constants."""
    import functools

    import jax
    import jax.numpy as jnp

    w = jnp.ones((dim, dim), jnp.bfloat16)

    @functools.partial(jax.jit, static_argnums=1)
    def chain(x, k):
        return jax.lax.fori_loop(0, k, lambda _, y: jax.lax.dot(y, w), x)

    s = jax.jit(lambda t: jnp.sum(t.astype(jnp.float32)))

    def run(k):
        x = jnp.ones((dim, dim), jnp.bfloat16)
        _fetch(s(chain(x, k)))  # compile + warm
        t0 = time.perf_counter()
        _fetch(s(chain(x, k)))
        return time.perf_counter() - t0

    k1, k2 = 16, 144
    dt = run(k2) - run(k1)
    if dt <= 0:
        raise RuntimeError("ceiling measurement non-monotonic — backend timing broken")
    return 2 * dim**3 * (k2 - k1) / dt / 1e12


class MetricWithdrawn(RuntimeError):
    """Deliberate refusal to publish (kernel mismatch, impossible MFU)."""


def _build_ar(cfg, mesh, impl):
    import jax
    import jax.numpy as jnp
    import optax

    from perceiver_io_tpu.models.text.clm import CausalLanguageModel
    from perceiver_io_tpu.parallel import create_train_state, make_train_step
    from perceiver_io_tpu.training.tasks import clm_loss_fn

    model = CausalLanguageModel(cfg, dtype=jnp.bfloat16, attention_impl=impl)
    prefix_len = cfg.max_seq_len - cfg.max_latents

    def init():
        return model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), prefix_len
        )["params"]

    state, shardings = create_train_state(init, optax.adamw(3e-4), mesh)
    step = make_train_step(clm_loss_fn(model, cfg.max_latents), mesh, shardings)
    return model, state, step, shardings


def _time_train(step, state, sharded, key, *, n_chain: int, n_sync: int):
    """(chained ms/step, per-step-fetch median ms, final state, final loss)."""
    import jax
    import numpy as np

    for i in range(4):  # warm past the slow first post-compile steps
        state, metrics = step(state, sharded, jax.random.fold_in(key, i))
    _fetch(metrics["loss"])

    t0 = time.perf_counter()
    for i in range(n_chain):
        state, metrics = step(state, sharded, jax.random.fold_in(key, 100 + i))
    loss = _fetch(metrics["loss"])
    chained_ms = (time.perf_counter() - t0) / n_chain * 1e3

    ts = []
    for i in range(n_sync):
        t0 = time.perf_counter()
        state, metrics = step(state, sharded, jax.random.fold_in(key, 200 + i))
        _fetch(metrics["loss"])
        ts.append(time.perf_counter() - t0)
    synced_ms = float(np.median(ts)) * 1e3 if ts else None
    return chained_ms, synced_ms, state, loss


def run(shape, deadline_s: float) -> dict:
    """Every stage, in this process, on the chip; returns the record. Raises
    (and so publishes nothing) when JAX found no TPU, when the flash kernel
    disagrees with the einsum path, or when the MFU is impossible."""
    t_start = time.monotonic()

    def left() -> float:
        return deadline_s - (time.monotonic() - t_start)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.parallel import shard_batch, single_device_mesh

    device = jax.devices()[0]
    platform = device.platform
    log(f"run: platform={platform} kind={device.device_kind} "
        f"count={jax.device_count()} shape={shape}")
    if platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the TPU; JAX found {platform!r} "
            f"({device.device_kind}). No record."
        )
    batch_size = shape[0]
    cfg = _mk_config(shape)
    mesh = single_device_mesh(device)
    res: dict = {}

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(batch_size, cfg.max_seq_len + 1), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    flops = ar_train_flops(cfg, batch_size)
    peak = peak_flops(device)

    with mesh:
        sharded = shard_batch(batch, mesh)
        key = jax.random.PRNGKey(1)

        # ---- primary: AR train step, flash path (auto = flash on TPU) ----
        n_chain = 20
        log("run: building AR train step (flash/auto)")
        model, state, step, shardings = _build_ar(cfg, mesh, "auto")
        chained_ms, synced_ms, state, loss = _time_train(
            step, state, sharded, key, n_chain=n_chain, n_sync=4
        )
        dt = chained_ms / 1e3
        tokens_per_sec = batch_size * cfg.max_seq_len / dt
        a100_step_time = flops / (A100_BF16_FLOPS * A100_ASSUMED_MFU)
        baseline_step_time = a100_step_time / BASELINE_FACTOR
        mfu = flops / dt / peak
        if not 0.0 < mfu < 1.0:
            raise RuntimeError(
                f"refusing to emit physically impossible MFU {mfu:.4f} "
                f"(flops={flops:.3e}, step={dt * 1e3:.2f} ms, peak={peak:.3e}) — "
                "timing or accounting is broken"
            )
        log(
            f"run: AR train {chained_ms:.1f} ms/step chained, "
            f"{synced_ms:.1f} ms synced, loss {loss:.4f}, mfu {mfu:.4f}"
        )
        res.update(
            metric=METRIC,
            value=round(tokens_per_sec, 1),
            unit="tokens/s",
            vs_baseline=round(baseline_step_time / dt, 3),
            platform=platform,
            device_kind=device.device_kind,
            device_count=jax.device_count(),
            attention_impl="flash",
            step_time_ms=round(chained_ms, 2),
            step_time_ms_synced=round(synced_ms, 2),
            train_loss=round(loss, 4),
            mfu=round(mfu, 4),
            peak_flops=peak,
            flops_per_step=flops,
            shape=list(shape),
            timing=f"chained-{n_chain}-donated-steps + host value fetch (see bench.py docstring)",
            extras={},
        )

        # ---- extra: fused multi-step block (zero host dispatch per step) ----
        # 10 optimizer steps in ONE device program (lax.scan; the trainer's
        # steps_per_execution path): per-step time with the host entirely out
        # of the loop — the deployment-mode number for long training runs.
        if left() > 150.0:
            log("run: fused 10-step block")
            fstate = fused = stacked = None
            try:
                from perceiver_io_tpu.parallel import make_train_step
                from perceiver_io_tpu.training.tasks import clm_loss_fn

                K = 10
                # donate=False: reuses the live primary state without
                # consuming it (the cross-check/decode stages still need it)
                fused = make_train_step(
                    clm_loss_fn(model, cfg.max_latents), mesh, shardings,
                    multi_steps=K, donate=False,
                )
                stk = {
                    k2: np.broadcast_to(np.asarray(v)[None], (K, *np.shape(v))).copy()
                    for k2, v in batch.items()
                }
                stacked = shard_batch(stk, mesh, stacked_steps=True)
                keys = jax.random.split(jax.random.PRNGKey(3), K)
                fstate, fm = fused(state, stacked, keys)  # compile + warm
                _fetch(fm["loss"][-1])
                t0 = time.perf_counter()
                fstate, fm = fused(state, stacked, keys)
                _fetch(fm["loss"][-1])
                fused_ms = (time.perf_counter() - t0) / K * 1e3
                res.update(extras={**res["extras"], "fused_multi_step": {
                    "per_step_ms": round(fused_ms, 2),
                    "tokens_per_sec": round(
                        batch_size * cfg.max_seq_len / (fused_ms / 1e3), 1),
                    "block_steps": K,
                }})
                log(f"run: fused block {fused_ms:.1f} ms/step")
            except Exception as e:
                log(f"run: fused block failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "fused_multi_step": {
                    "error": f"{type(e).__name__}: {e}"}})
            finally:
                fstate = fused = stacked = None  # release HBM for later stages

        # ---- extra: practical matmul ceiling (contextualizes MFU) ----
        if left() > 150.0:
            log("run: matmul ceiling")
            try:
                ceiling = round(_matmul_ceiling_tflops(), 1)
                res.update(measured_matmul_tflops=ceiling)
                log(f"run: matmul ceiling {ceiling} TF/s")
            except Exception as e:
                log(f"run: ceiling measurement skipped ({type(e).__name__}: {e})")

        # ---- cross-check: flash vs xla loss on identical params/batch ----
        # Uses the live post-timing params (the timed state was donated away
        # step by step; state.params is the current generation).
        if left() > 120.0:
            log("run: flash-vs-xla cross-check")
            try:
                from perceiver_io_tpu.training.tasks import clm_loss_fn
                from perceiver_io_tpu.models.text.clm import CausalLanguageModel

                xmodel = CausalLanguageModel(cfg, dtype=jnp.bfloat16, attention_impl="xla")
                xloss_fn = jax.jit(clm_loss_fn(xmodel, cfg.max_latents))
                floss_fn = jax.jit(clm_loss_fn(model, cfg.max_latents))
                ckey = jax.random.PRNGKey(7)
                live = state.params

                def timed_loss(fn):
                    _fetch(fn(live, sharded, ckey)[0])  # compile + warm
                    t0 = time.perf_counter()
                    value = _fetch(fn(live, sharded, ckey)[0])
                    return value, (time.perf_counter() - t0) * 1e3

                lf, fwd_flash_ms = timed_loss(floss_fn)
                lx, fwd_xla_ms = timed_loss(xloss_fn)
                diff = abs(lf - lx)
                ok = diff <= 5e-3
                log(f"run: cross-check loss flash={lf:.6f} xla={lx:.6f} diff={diff:.2e} ok={ok}")
                res.update(extras={**res["extras"], "flash_vs_xla": {
                    "loss_flash": lf, "loss_xla": lx, "loss_diff": diff, "ok": ok,
                    "fwd_flash_ms": round(fwd_flash_ms, 2),
                    "fwd_xla_ms": round(fwd_xla_ms, 2),
                }})
                if not ok:
                    # a mismatched kernel must not publish a record at all
                    raise MetricWithdrawn(
                        f"flash/xla loss mismatch {diff:.2e} — "
                        "kernel correctness regression; no record"
                    )
            except MetricWithdrawn:
                raise
            except Exception as e:  # backend failure here is not a verdict
                log(f"run: cross-check skipped ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "flash_vs_xla": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: MLM samples/sec (BASELINE.json metric, second half) ----
        if left() > 150.0:
            log("run: MLM samples/sec (language-perceiver 201M shape)")
            try:
                mlm_sps = _bench_mlm(mesh)
                res.update(extras={**res["extras"], "mlm": mlm_sps})
                log(f"run: MLM {mlm_sps['samples_per_sec']} samples/s")
            except Exception as e:
                log(f"run: MLM bench failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "mlm": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: cached vs recompute decode throughput ----
        if left() > 150.0:
            log("run: decode throughput (cached vs recompute)")
            try:
                dec = _bench_decode(model, state.params, cfg)
                res.update(extras={**res["extras"], "decode": dec})
                log(f"run: decode cached {dec['cached_tokens_per_sec']} tok/s, "
                    f"recompute {dec['recompute_tokens_per_sec']} tok/s "
                    f"(latent phase {dec['latent']['speedup']}x, boundary "
                    f"phase {dec['boundary']['speedup']}x cached-vs-recompute)")
            except Exception as e:
                log(f"run: decode bench failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "decode": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: bucketed serving probe (mixed-length traffic) ----
        if left() > 120.0:
            log("run: serving probe (shape-bucketed micro-batching)")
            try:
                # the slots-vs-bucket A/B runs ~2 min at the CPU shape;
                # skip it when the remaining budget couldn't also fit the
                # chaos + observability probes
                srv = _bench_serve(model, state.params, cfg, with_ab=left() > 300.0)
                res.update(extras={**res["extras"], "serve": srv})
                log(f"run: serve {srv['tokens_per_sec']} tok/s, "
                    f"{srv['compile_count']} compiles for "
                    f"{srv['distinct_prompt_lens']} distinct prompt lengths")
                ab = srv.get("slots_vs_bucket", {})
                if ab:
                    log(f"run: serve A/B slots {ab['slots']['tokens_per_sec']} "
                        f"vs bucket {ab['bucket']['tokens_per_sec']} tok/s "
                        f"(speedup {ab['slots_vs_bucket_speedup']}x, slot "
                        f"occupancy {ab['slots']['slot_occupancy']})")
            except Exception as e:
                log(f"run: serving probe failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "serve": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: chunked-prefill A/B (resident latency under long admit) ----
        if left() > 150.0:
            log("run: chunked-prefill A/B (p95 resident inter-token latency)")
            try:
                pc = _bench_prefill_chunk_ab(cfg)
                res.update(extras={**res["extras"], "prefill_chunk": pc})
                log(f"run: prefill-chunk A/B p95 without="
                    f"{pc['without_chunking']['p95_inter_token_ms']}ms "
                    f"with={pc['with_chunking']['p95_inter_token_ms']}ms "
                    f"(lower with chunking: {pc['chunking_lowers_p95']})")
            except Exception as e:
                log(f"run: chunked-prefill A/B failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "prefill_chunk": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: paged-KV A/B (long-tail residents at a fixed HBM budget) ----
        if left() > 150.0:
            log("run: paged-KV A/B (dense vs block-paged residents at one budget)")
            try:
                pkv = _bench_paged_kv(model, state.params, cfg)
                res.update(extras={**res["extras"], "paged_kv": pkv})
                log(f"run: paged-KV residents {pkv['paged']['max_residents']} "
                    f"vs dense {pkv['dense']['max_residents']} at the same "
                    f"budget ({pkv['max_residents_ratio']}x, token_identical="
                    f"{pkv['token_identical']}, paged "
                    f"{pkv['paged']['tokens_per_sec']} tok/s)")
            except Exception as e:
                log(f"run: paged-KV A/B failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "paged_kv": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: preemption A/B (strict vs optimistic admission) ----
        if left() > 150.0:
            log("run: preemption A/B (strict vs optimistic admission at "
                "one budget)")
            try:
                pmt = _bench_preemption(model, state.params, cfg)
                res.update(extras={**res["extras"], "preemption": pmt})
                log(f"run: preemption residents "
                    f"{pmt['optimistic']['max_residents']} vs strict "
                    f"{pmt['strict']['max_residents']} at the same budget "
                    f"({pmt['max_residents_ratio']}x, goodput_under_slo "
                    f"{pmt['optimistic']['goodput_under_slo']} vs "
                    f"{pmt['strict']['goodput_under_slo']}, "
                    f"{pmt['optimistic']['preemptions']} preemptions, "
                    f"token_identical={pmt['token_identical']})")
                pm = pmt["optimistic"]["postmortems"]
                if pm["count"]:
                    log(f"run: preemption post-mortems {pm['count']} victims, "
                        f"{pm['tokens_discarded']} tokens replayed, recompute "
                        f"{pm['recompute_est_ms']}ms vs swap "
                        f"{pm['swap_est_ms']}ms at {pm['swap_link_gbps']}GB/s "
                        f"(swap_advantage {pm['swap_advantage_ms']}ms)")
            except Exception as e:
                log(f"run: preemption A/B failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "preemption": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: host-swap A/B (recompute vs swap vs auto over length) ----
        if left() > 150.0:
            log("run: host-swap A/B (recompute vs swap vs auto preemption "
                "over a generated-length sweep)")
            try:
                swp = _bench_swap(model, state.params, cfg)
                res.update(extras={**res["extras"], "swap": swp})
                last = swp["sweep"][-1] if swp["sweep"] else {}
                log(f"run: host-swap crossover_length="
                    f"{swp['crossover_length']} (longest point: recompute "
                    f"{last.get('recompute', {}).get('wall_s')}s vs swap "
                    f"{last.get('swap', {}).get('wall_s')}s, realized "
                    f"advantage {last.get('realized_advantage_ms')}ms, "
                    f"predicted {last.get('predicted_advantage_ms')}ms), "
                    f"token_identical={swp['token_identical']}, "
                    f"auto_agrees={swp['auto_agrees']}, sign_agrees="
                    f"{swp['advantage_sign_agrees']}")
            except Exception as e:
                log(f"run: host-swap A/B failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "swap": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: quantized-KV A/B (exact vs int8 pool at one budget) ----
        if left() > 150.0:
            log("run: quant-KV A/B (exact vs int8 paged pool at one budget)")
            try:
                qkv = _bench_quant_kv(model, state.params, cfg)
                res.update(extras={**res["extras"], "quant_kv": qkv})
                log(f"run: quant-KV residents {qkv['int8']['max_residents']} "
                    f"vs exact {qkv['exact']['max_residents']} at the same "
                    f"budget ({qkv['residents_per_hbm_byte_ratio']}x, "
                    f"token_match={qkv['token_match_rate']}, quality gate "
                    f"passed={qkv['quality_gate']['passed']})")
            except Exception as e:
                log(f"run: quant-KV A/B failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "quant_kv": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: prefix-cache A/B (Zipf shared prefixes, COW sharing) ----
        if left() > 150.0:
            log("run: prefix-cache A/B (Zipf shared prefixes, unshared vs COW-shared)")
            try:
                pfx = _bench_prefix_cache(model, state.params, cfg)
                res.update(extras={**res["extras"], "prefix_cache": pfx})
                log(f"run: prefix-cache TTFT p95 ratio {pfx['ttft_p95_ratio']}x, "
                    f"residents/byte ratio {pfx['residents_per_hbm_byte_ratio']}x, "
                    f"hit_ratio={pfx['hit_ratio']}, token_identical="
                    f"{pfx['token_identical']}")
            except Exception as e:
                log(f"run: prefix-cache A/B failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "prefix_cache": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: speculative-decoding A/B (self-draft vs one-token steps) ----
        if left() > 120.0:
            log("run: speculative A/B (self-draft k+1-token rounds vs "
                "one-token steps, plus the autotune pays/declines pins)")
            try:
                spc = _bench_speculative(model, state.params, cfg)
                res.update(extras={**res["extras"], "speculative": spc})
                log(f"run: speculative {spc['spec']['tokens_per_sec']} tok/s vs "
                    f"off {spc['off']['tokens_per_sec']} tok/s (speedup "
                    f"{spc['speedup']}x, acceptance {spc['acceptance_rate']}, "
                    f"{spc['tokens_per_round']} tok/round, token_identical="
                    f"{spc['token_identical']}; autotune pays="
                    f"{spc['autotune']['pays']['speculation']}, declines="
                    f"{spc['autotune']['decline']['speculation']})")
            except Exception as e:
                log(f"run: speculative A/B failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "speculative": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: chaos drill (fault-injected serving, deterministic) ----
        if left() > 60.0:
            log("run: chaos probe (backpressure / deadlines / fault isolation)")
            try:
                chs = _bench_chaos(model, state.params, cfg)
                res.update(extras={**res["extras"], "chaos": chs})
                log(f"run: chaos survived={chs['survived']} "
                    f"(shed {chs['shed']}, timed_out {chs['timed_out']}, "
                    f"failed {chs['failed']}, completed {chs['completed']})")
            except Exception as e:
                log(f"run: chaos probe failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "chaos": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: fleet chaos drill (mid-decode replica kill) ----
        if left() > 90.0:
            log("run: fleet-chaos probe (replica kill / failover / exactly-once)")
            try:
                flc = _bench_fleet_chaos(model, state.params, cfg)
                res.update(extras={**res["extras"], "fleet_chaos": flc})
                log(f"run: fleet-chaos completion_ratio={flc['completion_ratio']} "
                    f"token_identical={flc['token_identical']} "
                    f"(failovers {flc['failovers']}, redispatches "
                    f"{flc['redispatches']}, goodput "
                    f"{flc['goodput_tokens_per_sec']} tok/s)")
            except Exception as e:
                log(f"run: fleet-chaos probe failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "fleet_chaos": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: elasticity A/B (autoscaled vs static fleet flash crowd) ----
        if left() > 120.0:
            log("run: elasticity probe (flash crowd: breach -> scale-up -> "
                "recover -> scale-down, vs a static fleet)")
            try:
                ela = _bench_elasticity(model, state.params, cfg)
                res.update(extras={**res["extras"], "elasticity": ela})
                log(f"run: elasticity goodput-under-SLO "
                    f"{ela['autoscaled']['goodput_under_slo']} autoscaled vs "
                    f"{ela['static']['goodput_under_slo']} static "
                    f"(beats={ela['elastic_beats_static']}, scale_ups "
                    f"{ela['autoscaled']['scale_ups']}, scale_downs "
                    f"{ela['autoscaled']['scale_downs']}, zero_dropped="
                    f"{ela['zero_dropped']}, token_identical="
                    f"{ela['token_identical']})")
            except Exception as e:
                log(f"run: elasticity probe failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "elasticity": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: observability probe (telemetry layer end to end) ----
        if left() > 60.0:
            log("run: observability probe (histograms / goodput / MFU gauges)")
            try:
                obs = _bench_observability(model, state.params, cfg)
                res.update(extras={**res["extras"], "observability": obs})
                log(f"run: observability goodput={obs['goodput']} "
                    f"mfu={obs['mfu']} span_accounting_closed="
                    f"{obs['span_accounting_closed']}")
            except Exception as e:
                log(f"run: observability probe failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "observability": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: goodput-under-SLO sweep (offered load vs p95 TTFT/ITL) ----
        if left() > 120.0:
            log("run: slo-goodput sweep (offered load vs p95 TTFT / inter-token)")
            try:
                slo = _bench_slo_goodput(model, state.params, cfg)
                res.update(extras={**res["extras"], "slo_goodput": slo})
                log(f"run: slo-goodput knee at {slo['knee']['offered_rps']} rps "
                    f"offered ({slo['knee']['goodput_rps']} rps good, factor "
                    f"{slo['knee']['rate_factor']}x; report matches registry: "
                    f"{slo['report_percentiles_match_registry']})")
            except Exception as e:
                log(f"run: slo-goodput sweep failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "slo_goodput": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: streaming abandonment drill (gateway cancellation path) ----
        if left() > 90.0:
            log("run: streaming probe (mid-stream mass abandonment, zero-leak)")
            try:
                stm = _bench_streaming(model, state.params, cfg)
                res.update(extras={**res["extras"], "streaming": stm})
                log(f"run: streaming abandoned {stm['abandoned']}/{stm['requests']} "
                    f"mid-stream — survivors token_identical="
                    f"{stm['token_identical']}, pool leak {stm['pool']['leaked']} "
                    f"blocks, reclaim p95 {stm['reclaim']['p95_ms']} ms "
                    f"(accounting_closed={stm['accounting_closed']})")
            except Exception as e:
                log(f"run: streaming probe failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "streaming": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: incident flight-recorder chaos drill ----
        if left() > 60.0:
            log("run: incident probe (replica crash during SLO breach -> "
                "bundle -> analyzer joins)")
            try:
                inc = _bench_incident(model, state.params, cfg)
                res.update(extras={**res["extras"], "incident": inc})
                log(f"run: incident bundles={inc['bundles']} "
                    f"(kinds={inc['bundle_kinds']}, suppressed="
                    f"{inc['suppressed']}), trace_join={inc['trace_join']}, "
                    f"decomposition_exact={inc['decomposition_exact']}, "
                    f"nonok_traces_kept={inc['nonok_traces_kept']} at "
                    f"{inc['sample_rate']} sampling (span accounting closed="
                    f"{inc['span_accounting_closed']})")
            except Exception as e:
                log(f"run: incident probe failed ({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "incident": {
                    "error": f"{type(e).__name__}: {e}"}})

        # ---- extra: sharded serving A/B (1-device vs 8-virtual-device mesh) ----
        if left() > 150.0:
            log("run: sharded serving probe (1-device vs 2x4 CPU mesh A/B)")
            try:
                shd = _bench_sharded_serving(budget_s=min(240.0, left() - 30.0))
                res.update(extras={**res["extras"], "sharded_serving": shd})
                log(f"run: sharded serving {shd['sharded']['mesh']['data']}x"
                    f"{shd['sharded']['mesh']['model']} mesh "
                    f"{shd['sharded']['tokens_per_s']} tok/s vs single "
                    f"{shd['single']['tokens_per_s']} tok/s "
                    f"(speedup {shd['speedup']}, token_identical="
                    f"{shd['token_identical']}, per-shard resident "
                    f"{shd['sharded']['per_shard_resident_bytes']} B)")
            except Exception as e:
                log(f"run: sharded serving probe failed "
                    f"({type(e).__name__}: {e})")
                res.update(extras={**res["extras"], "sharded_serving": {
                    "error": f"{type(e).__name__}: {e}"}})

        # BENCH_* records carry the process-wide telemetry snapshot AND the
        # device-cost ledger (per-executor compile/memory/retrace table;
        # docs/observability.md) — every BENCH_* file is `obs report`-able.
        try:
            from perceiver_io_tpu.observability import default_ledger, default_registry

            default_ledger().update_device_gauges()  # hbm_bytes_in_use on TPU
            res.update(
                metrics_snapshot=default_registry().snapshot(),
                compile_ledger=default_ledger().snapshot(),
            )
        except Exception as e:
            log(f"run: metrics snapshot skipped ({type(e).__name__}: {e})")

    return res


def _bench_mlm(mesh):
    """Perceiver IO MLM train step, deepmind/language-perceiver shape
    (201M params; reference fine-tunes it in docs/training-examples.md:90-118)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from perceiver_io_tpu.models.text.common import TextEncoderConfig
    from perceiver_io_tpu.models.text.mlm import (
        MaskedLanguageModel,
        MaskedLanguageModelConfig,
        TextDecoderConfig,
    )
    from perceiver_io_tpu.parallel import create_train_state, make_train_step, shard_batch
    from perceiver_io_tpu.training.tasks import mlm_loss_fn

    # deepmind/language-perceiver: qk 256 / v 1280, widening 1 (the HF
    # PerceiverConfig defaults) — 201M params exactly, not the reference
    # library's widening-4 defaults.
    seq, vocab, batch = 2048, 262, 8
    channels, latents, latent_channels, layers = 768, 256, 1280, 26
    qk, widen = 256, 1
    config_note = "deepmind/language-perceiver 201M (768ch, 256x1280 latents, 26 layers)"
    cfg = MaskedLanguageModelConfig(
        encoder=TextEncoderConfig(
            vocab_size=vocab,
            max_seq_len=seq,
            num_input_channels=channels,
            num_cross_attention_qk_channels=qk,
            num_cross_attention_v_channels=latent_channels,
            num_cross_attention_heads=8,
            num_self_attention_qk_channels=qk,
            num_self_attention_v_channels=latent_channels,
            num_self_attention_heads=8,
            num_self_attention_layers_per_block=layers,
            num_self_attention_blocks=1,
            cross_attention_widening_factor=widen,
            self_attention_widening_factor=widen,
        ),
        decoder=TextDecoderConfig(
            vocab_size=vocab,
            max_seq_len=seq,
            num_cross_attention_qk_channels=qk,
            num_cross_attention_v_channels=channels,
            num_cross_attention_heads=8,
            cross_attention_widening_factor=widen,
            cross_attention_residual=False,
        ),
        num_latents=latents,
        num_latent_channels=latent_channels,
    )
    model = MaskedLanguageModel(cfg, dtype=jnp.bfloat16)

    def init():
        return model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))["params"]

    state, shardings = create_train_state(init, optax.adamw(3e-4), mesh)
    step = make_train_step(mlm_loss_fn(model), mesh, shardings)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq), dtype=np.int32)
    labels = np.where(rng.random((batch, seq)) < 0.15, ids, -100).astype(np.int32)
    batch_d = shard_batch({"input_ids": ids, "labels": labels}, mesh)

    key = jax.random.PRNGKey(1)
    chained_ms, synced_ms, _, loss = _time_train(
        step, state, batch_d, key, n_chain=10, n_sync=2
    )
    return {
        "metric": "perceiver_io_mlm_train_samples_per_sec",
        "samples_per_sec": round(batch / (chained_ms / 1e3), 2),
        "step_time_ms": round(chained_ms, 2),
        "step_time_ms_synced": round(synced_ms, 2),
        "batch": batch,
        "seq": seq,
        "train_loss": round(loss, 4),
        "config": config_note,
    }


def _bench_decode(model, params, cfg):
    """Cached vs windowed-recompute decode tokens/s at the 8k-ctx shape —
    the KV cache's reason to exist (VERDICT r2 ask #4a). Weights are stored
    bf16 (cast_float_params): the deployment config — decode is HBM-bandwidth
    bound at small batch, and fp32 weight reads would double that traffic."""
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig, generate

    params = cast_float_params(params, jnp.bfloat16)

    b, new_tokens = 4, 32
    prompt_len = cfg.max_seq_len // 2  # latent-growth + prefix-growth phases
    num_latents = cfg.max_latents
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(1, cfg.vocab_size, size=(b, prompt_len), dtype=np.int32)
    )
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)

    out = {}
    for label, use_cache in (("cached", True), ("recompute", False)):
        ids = generate(model, params, prompt, gcfg, use_cache=use_cache)
        _fetch(ids[0, -1])  # warm (compile included above; fence here)
        t0 = time.perf_counter()
        ids = generate(model, params, prompt, gcfg, use_cache=use_cache)
        _fetch(ids[0, -1])
        dt = time.perf_counter() - t0
        out[f"{label}_tokens_per_sec"] = round(b * new_tokens / dt, 1)
        out[f"{label}_ms_per_token"] = round(dt / new_tokens * 1e3, 2)
    out["speedup"] = round(
        out["cached_tokens_per_sec"] / out["recompute_tokens_per_sec"], 2
    )
    out.update(batch=b, prompt_len=prompt_len, new_tokens=new_tokens)
    out["boundary_strategy"] = _bench_decode_boundary(model, params, cfg)
    # per-phase split (the decode_scaling.py pins): the blended probe above
    # mixes latent-growth and prefix-growth steps, which hides that the
    # cache's win is phase-dependent — report each phase's tok/s on its own
    # pin. Boundary numbers come free from the strategy probe (same pin).
    bs = out["boundary_strategy"]
    out["boundary"] = {
        "cached_tokens_per_sec": bs["cached_tokens_per_sec"],
        "recompute_tokens_per_sec": bs["recompute_tokens_per_sec"],
        "speedup": round(
            bs["cached_tokens_per_sec"] / bs["recompute_tokens_per_sec"], 2
        ),
        "prompt_len": bs["prompt_len"],
        "new_tokens": bs["new_tokens"],
        "start_latents": cfg.max_latents,
    }
    out["latent"] = _bench_decode_latent(model, params, cfg)
    return out


def _bench_decode_latent(model, params, cfg, *, new_tokens: int = 8):
    """Latent-growth phase pin (``examples/perf/decode_scaling.py --phase
    latent``): latents start ``new_tokens`` below max so every generated
    token lands in latent growth — the cached step runs O(1) tokens of
    compute per step while the recompute path pays the full window, the
    phase where the cache's advantage is largest. Requires ``new_tokens <
    max_latents`` (clamped). ``params`` arrive bf16-cast from the
    caller."""
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference.generate import GenerationConfig, generate

    b = 1
    new_tokens = max(1, min(
        new_tokens, cfg.max_latents - 1, cfg.max_seq_len - cfg.max_latents
    ))
    prompt_len = cfg.max_seq_len - new_tokens
    start_latents = cfg.max_latents - new_tokens
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(1, cfg.vocab_size, size=(b, prompt_len), dtype=np.int32)
    )
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=start_latents)

    out = {}
    for label, use_cache in (("cached", True), ("recompute", False)):
        ids = generate(model, params, prompt, gcfg, use_cache=use_cache)
        _fetch(ids[0, -1])  # compile + fence
        t0 = time.perf_counter()
        ids = generate(model, params, prompt, gcfg, use_cache=use_cache)
        _fetch(ids[0, -1])
        dt = time.perf_counter() - t0
        out[f"{label}_tokens_per_sec"] = round(b * new_tokens / dt, 1)
    out["speedup"] = round(
        out["cached_tokens_per_sec"] / out["recompute_tokens_per_sec"], 2
    )
    out.update(
        prompt_len=prompt_len, new_tokens=new_tokens,
        start_latents=start_latents,
    )
    return out


def _bench_decode_boundary(model, params, cfg, *, new_tokens: int = 8):
    """Boundary-phase strategy probe (ISSUE 5 acceptance): pin every
    generated token into the prefix-growth phase (latents start maxed, the
    prompt fills the window minus ``new_tokens``), measure the cached and
    recompute implementations, record the winner in the strategy registry
    from those same timings, then measure ``decode_strategy="auto"`` —
    which resolves to the recorded winner and reuses its compiled executor, so the
    effective throughput must sit within noise of max(cached, recompute)
    (``auto_vs_best``; the acceptance bar is >= 0.98). ``params`` arrive
    bf16-cast from the caller."""
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import decode_strategy as strategy_mod
    from perceiver_io_tpu.inference.generate import GenerationConfig, generate

    b = 1
    new_tokens = max(1, min(new_tokens, cfg.max_seq_len - cfg.max_latents))
    prompt_len = cfg.max_seq_len - new_tokens
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(1, cfg.vocab_size, size=(b, prompt_len), dtype=np.int32)
    )
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=cfg.max_latents)

    def measure(mode):
        ids = generate(model, params, prompt, gcfg, decode_strategy=mode)
        _fetch(ids[0, -1])  # compile + fence
        t0 = time.perf_counter()
        ids = generate(model, params, prompt, gcfg, decode_strategy=mode)
        _fetch(ids[0, -1])
        return b * new_tokens / (time.perf_counter() - t0)

    out = {}
    for mode in ("cached", "recompute"):
        out[f"{mode}_tokens_per_sec"] = round(measure(mode), 1)
    # record the winner from the timings just taken (the decode_scaling.py
    # pattern) rather than re-running autotune's identical probe — the
    # deadline-budgeted child_run can't afford four redundant fenced passes
    # at the near-full-window shape (tie -> cached, matching the autotuner)
    winner = (
        "cached"
        if out["cached_tokens_per_sec"] >= out["recompute_tokens_per_sec"]
        else "recompute"
    )
    strategy_mod.record(
        model, winner,
        cached_ms_per_token=round(1e3 / out["cached_tokens_per_sec"], 4),
        recompute_ms_per_token=round(1e3 / out["recompute_tokens_per_sec"], 4),
        batch=b, new_tokens=new_tokens, source="bench",
    )
    out["auto_tokens_per_sec"] = round(measure("auto"), 1)
    best = max(out["cached_tokens_per_sec"], out["recompute_tokens_per_sec"])
    out.update(
        strategy=winner,
        auto_vs_best=round(out["auto_tokens_per_sec"] / best, 4),
        new_tokens=new_tokens,
        prompt_len=prompt_len,
    )
    return out


def _bench_serve(model, params, cfg, *, n_requests: int = 24, new_tokens: int = 8,
                 with_ab: bool = True):
    """Mixed-length serving probe: a ragged prompt distribution (>= 8
    distinct lengths when the context allows) through the shape-bucketed
    ``ServingEngine`` (docs/serving.md). Two passes over the same traffic:
    the first pays every bucket compile (``compile_count`` — bounded by the
    bucket grid, not by the number of distinct shapes), the second measures
    steady-state serving throughput plus queue-wait percentiles. Shapes are
    derived from ``cfg`` so the probe also runs at the reduced drill
    shape — the serving trajectory gets a real number without hardware."""
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.serving import BucketTable, ServingEngine

    params = cast_float_params(params, jnp.bfloat16)
    num_latents = min(16, cfg.max_latents)
    max_prefix = cfg.max_seq_len - cfg.max_latents
    max_len = min(256, cfg.max_seq_len // 2, max_prefix + num_latents)
    lens_grid = sorted({max(num_latents, max_len // 4), max(num_latents, max_len // 2), max_len})
    table = BucketTable(prompt_lens=tuple(lens_grid), batch_sizes=(2, 4, 8))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)

    rng = np.random.default_rng(0)
    lo = max(1, max_len // 8)
    prompt_lens = rng.integers(lo, max_len + 1, size=n_requests)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=int(n), dtype=np.int32)
        for n in prompt_lens
    ]

    compile_engine = ServingEngine(model, params, gcfg, table)
    compile_engine.serve(prompts)  # pays every bucket compile
    compile_count = compile_engine.stats()["compiles"]

    engine = ServingEngine(model, params, gcfg, table)
    t0 = time.perf_counter()
    outs = engine.serve(prompts)
    _fetch(outs[-1][-1])
    dt = time.perf_counter() - t0
    stats = engine.stats()
    out = {
        "tokens_per_sec": round(n_requests * new_tokens / dt, 1),
        "compile_count": compile_count,
        "steady_state_compiles": stats["compiles"],
        "p50_queue_wait_ms": stats["queue_wait_ms"]["p50"],
        "p95_queue_wait_ms": stats["queue_wait_ms"]["p95"],
        "requests": n_requests,
        "new_tokens": new_tokens,
        "batches": stats["batches"],
        "distinct_prompt_lens": int(len(set(int(n) for n in prompt_lens))),
        "bucket_grid": stats["bucket_grid"],
        "prompt_padding_efficiency": stats["prompt_padding_efficiency"],
    }
    if with_ab:  # the tier-1 probe test skips this (suite-budget control)
        out["slots_vs_bucket"] = _bench_serve_ab(model, params, cfg)
    return out


def _bench_serve_ab(model, params, cfg, *, n_requests: int = 16, slots: int = 8):
    """Slots-vs-bucket A/B on the workload that exposes generation-granular
    batching's two wastes (ISSUE 4 / the ragged-batch TPU-serving papers):
    ragged prompt lengths AND heterogeneous ``max_new_tokens``. The bucket
    engine can only pack identical-config requests, so mixed decode lengths
    fragment into underfilled micro-batches padded to the batch bucket —
    filler rows burn real decode compute. The slot engine's persistent
    ``S``-slot decode state retires each row the token it finishes and
    refills the freed slot from the queue mid-generation, so its padded-row
    fraction is just the drain tail.

    The primary comparison fixes BOTH engines to one resident batch shape
    (``batch_sizes=(slots,)``) — the TPU-serving configuration the papers
    target, where the hardware runs one compiled decode shape and filler
    rows cost real compute (this CPU probe prices filler rows linearly,
    standing in for the TPU's fixed-shape executor). Because an operator
    COULD instead give the bucket engine a full batch grid and let small
    batches pack exactly, the record also carries a ``bucket_exact``
    variant (grid ``1,2,4,...,slots``, 4x the executor count) so the
    scheduling-granularity and table effects are separable. All engines
    run the identical request list after a compile pass; tokens/s counts
    USEFUL tokens (sum of each request's own ``max_new_tokens``).
    ``params`` arrive bf16-cast from :func:`_bench_serve`; shapes derive
    from ``cfg``, so the probe is CPU-runnable at the reduced fallback
    shape."""
    import dataclasses

    import numpy as np

    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.serving import BucketTable, ServingEngine, SlotServingEngine

    n = cfg.max_seq_len
    num_latents = min(4, cfg.max_latents)
    max_len = min(64, n // 2, cfg.max_seq_len - cfg.max_latents + num_latents)
    # decode-length pool: ~8 distinct values (real traffic rarely shares a
    # max_new_tokens, and the bucket engine can only pack identical-config
    # requests), capped so the probe stays seconds-scale on CPU and every
    # request fits the slot window
    cap = min(n - max_len, 32)
    pool = tuple(sorted({max(1, cap * f // 32) for f in (2, 3, 4, 6, 8, 12, 16, 32)}))
    base = GenerationConfig(max_new_tokens=pool[-1], num_latents=num_latents)
    cfgs = [
        dataclasses.replace(base, max_new_tokens=pool[i % len(pool)])
        for i in range(n_requests)
    ]
    rng = np.random.default_rng(0)
    sizes = rng.integers(num_latents, max_len + 1, size=n_requests)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=int(s), dtype=np.int32) for s in sizes
    ]
    useful_tokens = sum(c.max_new_tokens for c in cfgs)
    grid = tuple(sorted({max(num_latents, max_len // 2), max_len}))

    def run(make_engine):
        compile_engine = make_engine()
        for p, c in zip(prompts, cfgs):
            compile_engine.submit(p, config=c)
        compile_engine.run_until_idle()
        engine = make_engine()
        t0 = time.perf_counter()
        for p, c in zip(prompts, cfgs):
            engine.submit(p, config=c)
        engine.run_until_idle()
        dt = time.perf_counter() - t0
        return engine, dt

    def row_waste(engine):
        counts = engine.registry.counters()
        return round(
            counts.get("serving_decode_rows_padded_total", 0.0)
            / max(1.0, counts.get("serving_decode_rows_total", 0.0)), 4,
        )

    table = BucketTable(prompt_lens=grid, batch_sizes=(slots,))
    exact_sizes = tuple(sorted({2 ** i for i in range(slots.bit_length())} | {slots}))
    table_exact = BucketTable(prompt_lens=grid, batch_sizes=exact_sizes)
    bucket_engine, bucket_dt = run(
        lambda: ServingEngine(model, params, base, table)
    )
    bucket_exact_engine, bucket_exact_dt = run(
        lambda: ServingEngine(model, params, base, table_exact)
    )
    slot_engine, slot_dt = run(
        lambda: SlotServingEngine(model, params, base, table, slots=slots)
    )
    slot_stats = slot_engine.stats()
    bucket_tps = useful_tokens / bucket_dt
    bucket_exact_tps = useful_tokens / bucket_exact_dt
    slot_tps = useful_tokens / slot_dt
    return {
        "workload": {
            "requests": n_requests,
            "useful_tokens": useful_tokens,
            "max_new_pool": list(pool),
            "distinct_prompt_lens": int(len(set(int(s) for s in sizes))),
            "slots": slots,
        },
        "bucket": {
            "tokens_per_sec": round(bucket_tps, 1),
            "batches": bucket_engine.stats()["batches"],
            "decode_rows_padding_waste": row_waste(bucket_engine),
        },
        "bucket_exact": {
            "tokens_per_sec": round(bucket_exact_tps, 1),
            "batches": bucket_exact_engine.stats()["batches"],
            "decode_rows_padding_waste": row_waste(bucket_exact_engine),
            "batch_sizes": list(exact_sizes),
        },
        "slots": {
            "tokens_per_sec": round(slot_tps, 1),
            "decode_steps": slot_stats["decode_steps"],
            "prefills": slot_stats["prefills"],
            "slot_occupancy": slot_stats["slot_occupancy"],
            "decode_rows_padding_waste": slot_stats["decode_rows_padding_waste"],
            "p50_decode_step_ms": slot_stats["decode_step_ms"]["p50"],
        },
        "slots_vs_bucket_speedup": round(slot_tps / bucket_tps, 2),
        "slots_vs_bucket_exact_speedup": round(slot_tps / bucket_exact_tps, 2),
    }


def _bench_paged_kv(model, params, cfg, *, dense_slots: int = 4,
                    paged_slots: int = 12, n_requests: int = 24,
                    block_size: int = None):
    """Dense-vs-paged KV layout A/B on a long-tail mixed-context workload
    (ISSUE 9 acceptance; docs/serving.md "Block-paged KV"). The dense slot
    engine sizes every resident's cross-KV cache at the FULL context, so a
    simulated HBM budget of ``dense_slots`` context-lengths of KV caps it
    at ``dense_slots`` residents no matter how short the requests are. The
    paged engine gets the SAME budget as a block pool
    (``kv_blocks = dense_slots * pages_per_slot``) behind more slots: each
    resident consumes only its own ``ceil((prompt + max_new)/block)``
    blocks, so the mostly-short long-tail traffic packs strictly more
    concurrent residents into the same bytes — ``max_residents`` and the
    ratio are the recorded acceptance numbers, alongside tokens/s, the
    pool's page-utilization stats, and a token-identity check between the
    two layouts' outputs (the exactness invariant, also pinned by
    ``tests/test_paged_kv.py``).

    Shapes derive from ``cfg``, so the probe runs at the reduced
    reduced drill shape; prompt lengths are capped the way the other serve
    probes cap them (the dense layout's per-resident cost is
    context-sized regardless of prompt length, so the capacity comparison
    is unaffected)."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

    params = cast_float_params(params, jnp.bfloat16)
    n = cfg.max_seq_len
    num_latents = min(4, cfg.max_latents)
    if block_size is None:
        block_size = max(4, n // 32)
    pages_per_slot = -(-n // block_size)
    short_new = max(2, min(8, cfg.max_latents - num_latents))
    long_new = 2
    short_len = max(num_latents, min(64, n // 8))
    long_len = max(short_len, min(256, n // 2, model.max_prefix_len + num_latents,
                                  n - long_new))
    rng = np.random.default_rng(0)
    from perceiver_io_tpu.inference.samplers import SamplingConfig

    # greedy: the token-identity check must not depend on the two arms'
    # PRNG streams lining up
    base = GenerationConfig(
        max_new_tokens=short_new, num_latents=num_latents,
        sampling=SamplingConfig(temperature=0.0),
    )
    long_cfg = dataclasses.replace(base, max_new_tokens=long_new)
    reqs = []
    for i in range(n_requests):
        if i % 6 == 1:  # the long tail: ~1 in 6 requests near the cap
            reqs.append((
                rng.integers(1, cfg.vocab_size, size=long_len, dtype=np.int32),
                long_cfg,
            ))
        else:
            reqs.append((
                rng.integers(1, cfg.vocab_size, size=short_len, dtype=np.int32),
                base,
            ))
    useful_tokens = sum(c.max_new_tokens for _, c in reqs)
    table = BucketTable(
        prompt_lens=tuple(sorted({short_len, long_len})), batch_sizes=(1,)
    )
    budget_blocks = dense_slots * pages_per_slot  # the simulated HBM budget

    def run(make_engine):
        compile_engine = make_engine()
        for p, c in reqs:
            compile_engine.submit(p, config=c)
        compile_engine.run_until_idle()
        engine = make_engine()
        handles = []
        for p, c in reqs:
            handles.append(engine.submit(p, config=c))
        max_residents = 0
        t0 = time.perf_counter()
        while engine.pending():
            engine.step()
            active = sum(1 for s in engine._slots if s is not None)
            if engine._admitting is not None:
                active += 1
            max_residents = max(max_residents, active)
        dt = time.perf_counter() - t0
        return engine, dt, max_residents, [h.result for h in handles]

    dense_engine, dense_dt, dense_res, dense_outs = run(
        lambda: SlotServingEngine(
            model, params, base, table, slots=dense_slots, kv_layout="dense"
        )
    )
    paged_engine, paged_dt, paged_res, paged_outs = run(
        lambda: SlotServingEngine(
            model, params, base, table, slots=paged_slots, kv_layout="paged",
            kv_block_size=block_size, kv_blocks=budget_blocks,
        )
    )
    token_identical = all(
        a is not None and b is not None and bool(np.array_equal(a, b))
        for a, b in zip(dense_outs, paged_outs)
    )
    pool = paged_engine.stats()["kv_pool"]
    token_bytes = paged_engine._kv_token_bytes
    return {
        "workload": {
            "requests": n_requests,
            "useful_tokens": useful_tokens,
            "short_len": short_len,
            "long_len": long_len,
            "long_fraction": round(sum(1 for _, c in reqs if c is long_cfg)
                                   / n_requests, 3),
            "block_size": block_size,
            "hbm_budget_blocks": budget_blocks,
            "hbm_budget_bytes": budget_blocks * block_size * token_bytes,
        },
        "dense": {
            "slots": dense_slots,
            "max_residents": dense_res,
            "tokens_per_sec": round(useful_tokens / dense_dt, 1),
            "kv_resident_bytes": dense_slots * n * token_bytes,
        },
        "paged": {
            "slots": paged_slots,
            "max_residents": paged_res,
            "tokens_per_sec": round(useful_tokens / paged_dt, 1),
            "blocks_high_water": pool["high_water"],
            "page_utilization_high_water": round(
                pool["high_water"] / max(1, pool["blocks"]), 4
            ),
            "admit_waits": pool["admit_waits"],
            "block_allocs": pool["allocs_total"],
            "block_frees": pool["frees_total"],
        },
        "max_residents_ratio": round(paged_res / max(1, dense_res), 2),
        "paged_vs_dense_tokens_ratio": round(
            (useful_tokens / paged_dt) / (useful_tokens / dense_dt), 2
        ),
        "token_identical": token_identical,
    }


def _bench_preemption(model, params, cfg, *, budget_slots: int = 3,
                      engine_slots: int = 10, n_requests: int = 24,
                      block_size: int = None):
    """Strict-reservation vs optimistic-admission A/B at ONE simulated HBM
    budget (ISSUE 17 acceptance; docs/serving.md "Preemption &
    priorities") on a long-tail ``max_new`` workload: most requests decode
    a couple of tokens, ~1 in 6 declares a near-context ``max_new`` cap.
    The strict arm (``preemption=off``) reserves every resident's WORST
    CASE up front, so each long-tail request pins near a context-length of
    pool blocks it mostly never maps, and short requests queue behind that
    paper debt. The optimistic arm (``preemption="recompute"``) admits on
    prompt pages + headroom and reclaims real pages by preempting victims
    (recompute-from-prompt replay) only on genuine exhaustion — packing
    strictly more concurrent residents into the SAME bytes.

    Recorded acceptance numbers: ``max_residents_ratio`` and
    ``residents_per_hbm_byte`` per arm (the packing win),
    ``goodput_under_slo`` per arm — the fraction of requests completing
    within an SLO pinned at the STRICT arm's p50 completion latency, so
    the strict arm scores ~0.5 by construction and the optimistic arm
    beats it by finishing the short tail sooner — the preemption /
    readmission counts actually exercised, and the greedy token-identity
    check between the arms (preempt/replay must be invisible in the token
    stream, the bar pinned by ``tests/test_kv_preemption.py``)."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.inference.samplers import SamplingConfig
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

    params = cast_float_params(params, jnp.bfloat16)
    n = cfg.max_seq_len
    num_latents = min(4, cfg.max_latents)
    if block_size is None:
        block_size = max(4, n // 32)
    pages_per_slot = -(-n // block_size)
    short_new = max(2, min(4, cfg.max_latents - num_latents))
    short_len = max(num_latents, min(64, n // 8))
    # the long tail declares a near-context max_new CAP — the strict arm
    # reserves it all up front; actual decode still stops at the cap
    long_len = short_len
    long_new = max(short_new + 1, min(n - long_len, model.max_prefix_len))
    rng = np.random.default_rng(0)
    base = GenerationConfig(
        max_new_tokens=short_new, num_latents=num_latents,
        sampling=SamplingConfig(temperature=0.0),  # greedy: identity check
    )
    long_cfg = dataclasses.replace(base, max_new_tokens=long_new)
    reqs = []
    for i in range(n_requests):
        cfg_i = long_cfg if i % 3 == 1 else base
        reqs.append((
            rng.integers(1, cfg.vocab_size, size=short_len, dtype=np.int32),
            cfg_i,
        ))
    useful_tokens = sum(c.max_new_tokens for _, c in reqs)
    table = BucketTable(prompt_lens=(short_len,), batch_sizes=(1,))
    budget_blocks = budget_slots * pages_per_slot  # the simulated budget

    def run(preemption):
        def make_engine():
            return SlotServingEngine(
                model, params, base, table, slots=engine_slots,
                kv_layout="paged", kv_block_size=block_size,
                kv_blocks=budget_blocks, preemption=preemption,
                admit_headroom_blocks=1 if preemption else 0,
            )
        compile_engine = make_engine()
        for p, c in reqs:
            compile_engine.submit(p, config=c)
        compile_engine.run_until_idle()
        engine = make_engine()
        handles = [engine.submit(p, config=c) for p, c in reqs]
        done_at = [None] * len(handles)
        max_residents = 0
        t0 = time.perf_counter()
        while engine.pending():
            engine.step()
            now = time.perf_counter() - t0
            active = sum(1 for s in engine._slots if s is not None)
            if engine._admitting is not None:
                active += 1
            max_residents = max(max_residents, active)
            for i, h in enumerate(handles):
                if done_at[i] is None and h.done:
                    done_at[i] = now
        dt = time.perf_counter() - t0
        outs = [h.result for h in handles]
        return engine, dt, max_residents, outs, done_at

    strict_engine, strict_dt, strict_res, strict_outs, strict_done = run(None)
    lazy_engine, lazy_dt, lazy_res, lazy_outs, lazy_done = run("recompute")
    token_identical = all(
        a is not None and b is not None and bool(np.array_equal(a, b))
        for a, b in zip(strict_outs, lazy_outs)
    )
    # SLO pinned at the strict arm's p50 completion latency: the strict
    # arm scores ~0.5 by construction, so goodput_under_slo is directly
    # comparable across arms without picking a magic number
    slo_s = float(np.median([t for t in strict_done if t is not None]))

    def arm(engine, dt, residents, done, preemption):
        pool = engine.stats()["kv_pool"]
        pre = engine.stats().get("preemption") or {}
        token_bytes = engine._kv_token_bytes
        budget_bytes = budget_blocks * block_size * token_bytes
        return {
            "preemption": preemption or "off",
            "max_residents": residents,
            "residents_per_hbm_byte": round(residents / budget_bytes, 12),
            "tokens_per_sec": round(useful_tokens / dt, 1),
            "goodput_under_slo": round(
                sum(1 for t in done if t is not None and t <= slo_s)
                / len(done), 4
            ),
            "preemptions": int(pre.get("preemptions", 0)),
            "readmissions": int(pre.get("readmissions", 0)),
            "blocks_high_water": pool["high_water"],
            "admit_waits": pool["admit_waits"],
            # recompute-vs-swap post-mortem model (ISSUE 18): what each
            # eviction cost in replayed decode steps vs what a host-swap of
            # the victim's pages would have cost at swap_link_gbps — the
            # number that decides whether a swap tier is worth building
            "postmortems": engine.postmortems(),
        }

    return {
        "workload": {
            "requests": n_requests,
            "useful_tokens": useful_tokens,
            "prompt_len": short_len,
            "short_max_new": short_new,
            "long_max_new": long_new,
            "long_fraction": round(sum(1 for _, c in reqs if c is long_cfg)
                                   / n_requests, 3),
            "block_size": block_size,
            "hbm_budget_blocks": budget_blocks,
            "slo_s": round(slo_s, 4),
        },
        "strict": arm(strict_engine, strict_dt, strict_res, strict_done,
                      None),
        "optimistic": arm(lazy_engine, lazy_dt, lazy_res, lazy_done,
                          "recompute"),
        "max_residents_ratio": round(lazy_res / max(1, strict_res), 2),
        "token_identical": token_identical,
    }


def _bench_swap(model, params, cfg, *, budget_slots: int = 3,
                engine_slots: int = 8, n_requests: int = 12,
                block_size: int = None, lengths=None):
    """Recompute vs host-swap vs auto preemption over a generated-length
    sweep at ONE fixed pool budget (ISSUE 20 acceptance; docs/serving.md
    "Host-swap preemption"). Every request declares the same ``max_new``
    per sweep point, so a victim's discarded work grows linearly with the
    sweep axis while its page footprint (the swap transfer) stays bounded
    by the pool — recompute cost scales with generated length, swap cost
    doesn't, and the measured wall-clock crossing is the
    ``crossover_length`` the post-mortem model predicts.

    Recorded acceptance numbers per arm and length: wall-to-drain,
    ``goodput_under_slo`` (SLO pinned at the recompute arm's p50
    completion per length), preemption/swap churn, and greedy
    ``token_identical`` vs an UNPRESSURED baseline. Plus the two model
    honesty bars: ``predicted_advantage_ms`` (recompute arm's post-mortem
    ``swap_advantage_ms``) must agree in sign with
    ``realized_advantage_ms`` (recompute wall - swap wall) at the longest
    length, and the ``auto`` arm's per-victim dispositions must never
    pick the arm its own post-mortem record scores worse
    (``auto_agrees``)."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.inference.samplers import SamplingConfig
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

    params = cast_float_params(params, jnp.bfloat16)
    n = cfg.max_seq_len
    num_latents = min(4, cfg.max_latents)
    if block_size is None:
        block_size = max(4, n // 32)
    pages_per_slot = -(-n // block_size)
    prompt_len = max(num_latents, min(64, n // 8))
    max_len = min(n - prompt_len, model.max_prefix_len)
    if lengths is None:
        lengths = sorted({max(2, max_len // 8), max(3, max_len // 2),
                          max(4, max_len)})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=prompt_len,
                            dtype=np.int32) for _ in range(n_requests)]
    table = BucketTable(prompt_lens=(prompt_len,), batch_sizes=(1,))
    budget_blocks = budget_slots * pages_per_slot
    base = GenerationConfig(
        max_new_tokens=2, num_latents=num_latents,
        sampling=SamplingConfig(temperature=0.0),  # greedy: identity check
    )

    def run(preemption, gen_cfg, kv_blocks, *, warm=True):
        def make_engine():
            return SlotServingEngine(
                model, params, gen_cfg, table, slots=engine_slots,
                kv_layout="paged", kv_block_size=block_size,
                kv_blocks=kv_blocks, preemption=preemption,
                admit_headroom_blocks=1 if preemption else 0,
            )
        if warm:
            compile_engine = make_engine()
            for p in prompts:
                compile_engine.submit(p, config=gen_cfg)
            compile_engine.run_until_idle()
        engine = make_engine()
        handles = [engine.submit(p, config=gen_cfg) for p in prompts]
        done_at = [None] * len(handles)
        t0 = time.perf_counter()
        while engine.pending():
            engine.step()
            now = time.perf_counter() - t0
            for i, h in enumerate(handles):
                if done_at[i] is None and h.done:
                    done_at[i] = now
        dt = time.perf_counter() - t0
        return engine, dt, [h.result for h in handles], done_at

    sweep = []
    crossover = None
    for length in lengths:
        gen_cfg = dataclasses.replace(base, max_new_tokens=int(length))
        # unpressured baseline: enough blocks that nothing preempts
        _, _, ref_outs, _ = run(
            None, gen_cfg, engine_slots * pages_per_slot, warm=True
        )
        arms = {}
        for mode in ("recompute", "swap", "auto"):
            # warmed per arm: the pool size is part of the executor shape,
            # so the baseline's compile pass doesn't cover the budget pool
            engine, dt, outs, done = run(
                mode, gen_cfg, budget_blocks, warm=True
            )
            arms[mode] = (engine, dt, outs, done)
        slo_s = float(np.median(
            [t for t in arms["recompute"][3] if t is not None]
        ))
        point = {"length": int(length), "slo_s": round(slo_s, 4)}
        for mode, (engine, dt, outs, done) in arms.items():
            pre = engine.stats().get("preemption") or {}
            pm = engine.postmortems()
            point[mode] = {
                "wall_s": round(dt, 4),
                "goodput_under_slo": round(
                    sum(1 for t in done if t is not None and t <= slo_s)
                    / len(done), 4
                ),
                "preemptions": int(pre.get("preemptions", 0)),
                "swaps": int(pre.get("swaps", 0)),
                "swap_restores": int(pre.get("swap_restores", 0)),
                "swap_bytes": int(pre.get("swap_bytes", 0)),
                "token_identical": all(
                    a is not None and b is not None
                    and bool(np.array_equal(a, b))
                    for a, b in zip(outs, ref_outs)
                ),
                "postmortems": {
                    k: pm[k] for k in (
                        "count", "swapped", "recompute_est_ms",
                        "swap_est_ms", "swap_advantage_ms",
                        "swap_measured_ms", "swap_link_gbps",
                    )
                },
            }
        point["realized_advantage_ms"] = round(
            (arms["recompute"][1] - arms["swap"][1]) * 1e3, 3
        )
        point["predicted_advantage_ms"] = \
            point["recompute"]["postmortems"]["swap_advantage_ms"]
        # the auto honesty bar: every per-victim disposition matches the
        # cheaper side of its own post-mortem record
        auto_recent = arms["auto"][0].postmortems()["recent"]
        point["auto_agrees"] = all(
            r["mode"] == ("swap" if r["swap_est_ms"] < r["recompute_est_ms"]
                          else "recompute")
            for r in auto_recent
        )
        if crossover is None and point["realized_advantage_ms"] > 0:
            crossover = int(length)
        sweep.append(point)

    last = sweep[-1] if sweep else {}
    return {
        "workload": {
            "requests": n_requests,
            "prompt_len": prompt_len,
            "lengths": [int(x) for x in lengths],
            "block_size": block_size,
            "hbm_budget_blocks": budget_blocks,
        },
        "sweep": sweep,
        "crossover_length": crossover,
        "token_identical": all(
            p[mode]["token_identical"]
            for p in sweep for mode in ("recompute", "swap", "auto")
        ),
        "auto_agrees": all(p["auto_agrees"] for p in sweep),
        "advantage_sign_agrees": (
            bool(last) and
            (last["predicted_advantage_ms"] > 0)
            == (last["realized_advantage_ms"] > 0)
        ),
    }


def _bench_quant_kv(model, params, cfg, *, exact_slots: int = 4,
                    n_requests: int = 32, block_size: int = None,
                    new_tokens: int = 4):
    """Exact-vs-int8 paged KV A/B at ONE simulated HBM budget (ISSUE 16
    acceptance; docs/serving.md "Quantized KV"). The exact arm sizes a
    block pool to ``exact_slots`` context-lengths of KV; the int8 arm gets
    the SAME byte budget, which buys ``~4d/(d+4)`` times the blocks (int8
    entries + f32 per-(position, head) scales vs exact entries) and
    therefore proportionally more concurrent residents on short-request
    traffic — ``residents_per_hbm_byte_ratio`` is the recorded acceptance
    number, alongside tokens/s, the greedy token-match rate between the
    arms, and the autotuner quality probe's logit-delta verdict (the gate
    that decides whether ``kv_layout="auto"`` may ever pick int8).

    Params stay f32 — the CPU probe's computation dtype — so the byte
    ratio is the honest f32-pool-vs-int8-pool one (recorded per arm as
    ``pos_bytes``/``dtype``), not an assumed-bf16 figure."""
    import numpy as np

    from perceiver_io_tpu.inference import decode_strategy as strategy_mod
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.inference.samplers import SamplingConfig
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

    n = cfg.max_seq_len
    num_latents = min(4, cfg.max_latents)
    if block_size is None:
        block_size = max(4, n // 32)
    pages_per_slot = -(-n // block_size)
    prompt_len = max(num_latents, min(24, n // 4))
    rng = np.random.default_rng(0)
    gen = GenerationConfig(
        max_new_tokens=new_tokens, num_latents=num_latents,
        sampling=SamplingConfig(temperature=0.0),  # greedy: comparable arms
    )
    prompts = [
        rng.integers(1, cfg.vocab_size, size=prompt_len, dtype=np.int32)
        for _ in range(n_requests)
    ]
    useful_tokens = n_requests * new_tokens
    table = BucketTable(prompt_lens=(prompt_len,), batch_sizes=(1,))

    # per-position byte costs from the ENGINES' own accounting (satellite:
    # capacity math follows the resolved layout's dtype), read off two
    # 1-slot throwaway engines rather than re-derived here
    def pos_bytes(layout):
        e = SlotServingEngine(
            model, params, gen, table, slots=1, kv_layout=layout,
            kv_block_size=block_size,
        )
        return e._kv_token_bytes + e._kv_scale_token_bytes, str(
            e.stats()["kv_pool"]["dtype"]
        )

    exact_pos_bytes, exact_dtype = pos_bytes("paged")
    int8_pos_bytes, int8_dtype = pos_bytes("paged_int8")
    bpr = -(-(prompt_len + new_tokens) // block_size)  # blocks per request
    # the simulated HBM budget: exactly ``exact_slots`` concurrent
    # residents' worth of exact-pool blocks — scarce enough that BOTH arms
    # are block-bound (not request- or slot-capped), so the resident ratio
    # measures bytes and nothing else
    budget_blocks = exact_slots * bpr
    budget_bytes = budget_blocks * block_size * exact_pos_bytes
    int8_blocks = int(budget_bytes // (block_size * int8_pos_bytes))
    slots_e = max(1, min(n_requests, budget_blocks // bpr))
    slots_q = max(1, min(n_requests, int8_blocks // bpr))

    def run(layout, slots, kv_blocks):
        def make():
            return SlotServingEngine(
                model, params, gen, table, slots=slots, kv_layout=layout,
                kv_block_size=block_size, kv_blocks=kv_blocks,
            )
        compile_engine = make()
        for p in prompts:
            compile_engine.submit(p)
        compile_engine.run_until_idle()
        engine = make()
        handles = [engine.submit(p) for p in prompts]
        max_residents = 0
        t0 = time.perf_counter()
        while engine.pending():
            engine.step()
            active = sum(1 for s in engine._slots if s is not None)
            if engine._admitting is not None:
                active += 1
            max_residents = max(max_residents, active)
        dt = time.perf_counter() - t0
        return engine, dt, max_residents, [h.result for h in handles]

    _, exact_dt, exact_res, exact_outs = run("paged", slots_e, budget_blocks)
    int8_engine, int8_dt, int8_res, int8_outs = run(
        "paged_int8", slots_q, int8_blocks
    )
    ident = total = match = 0
    for a, b in zip(exact_outs, int8_outs):
        if a is None or b is None:
            continue
        a, b = np.asarray(a), np.asarray(b)
        ident += int(np.array_equal(a, b))
        L = min(a.size, b.size)
        total += max(a.size, b.size)
        match += int(np.sum(a[:L] == b[:L]))
    quality = strategy_mod.quant_quality_probe(
        model, params, block_size=min(block_size, 16)
    )
    pool = int8_engine.stats()["kv_pool"]
    return {
        "workload": {
            "requests": n_requests,
            "useful_tokens": useful_tokens,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "block_size": block_size,
            "blocks_per_request": bpr,
            "hbm_budget_bytes": int(budget_bytes),
        },
        "exact": {
            "layout": "paged",
            "dtype": exact_dtype,
            "pos_bytes": int(exact_pos_bytes),
            "slots": slots_e,
            "kv_blocks": budget_blocks,
            "max_residents": exact_res,
            "tokens_per_sec": round(useful_tokens / exact_dt, 1),
        },
        "int8": {
            "layout": "paged_int8",
            "dtype": int8_dtype,
            "pos_bytes": int(int8_pos_bytes),
            "slots": slots_q,
            "kv_blocks": int8_blocks,
            "max_residents": int8_res,
            "tokens_per_sec": round(useful_tokens / int8_dt, 1),
            "block_scale_bytes": pool["block_scale_bytes"],
            "blocks_high_water": pool["high_water"],
        },
        "block_bytes_ratio": round(exact_pos_bytes / int8_pos_bytes, 2),
        "residents_per_hbm_byte_ratio": round(int8_res / max(1, exact_res), 2),
        "int8_vs_exact_tokens_ratio": round(
            (useful_tokens / int8_dt) / (useful_tokens / exact_dt), 2
        ),
        "requests_token_identical": ident,
        "token_match_rate": round(match / max(1, total), 4),
        "quality_gate": quality,
    }


def _bench_prefix_cache(model, params, cfg, *, slots: int = 8,
                        n_requests: int = 24, n_prefixes: int = 2,
                        block_size: int = None, prefix_tokens: int = None,
                        budget_blocks: int = None, new_tokens: int = 4,
                        zipf: float = 2.5):
    """Prefix-sharing A/B (ISSUE 12 acceptance; docs/serving.md "Prefix
    sharing"): a Zipf-distributed shared-prefix workload — the
    :class:`~perceiver_io_tpu.observability.WorkloadSpec` shared-prefix
    distribution, a pool of ``n_prefixes`` long "system prompts" sampled
    by Zipf popularity with short fresh tails — served through the paged
    slot engine twice at ONE simulated HBM budget: ``prefix_cache="off"``
    (every admit re-projects its full prompt and reserves private pages)
    vs ``"on"`` (hot prefixes map by reference, prefill projects only the
    suffix). Recorded acceptance numbers: the TTFT p50/p95 ratio (the
    unshared full-window projection + the deeper queue it causes, vs
    block-table writes + suffix projection), concurrent
    residents-per-HBM-byte (shared blocks are reserved once, not per
    resident), the hit ratio, and ``token_identical`` between the two
    arms' greedy outputs (the exactness bar, also pinned by
    ``tests/test_prefix_cache.py``).

    Like ``_bench_prefill_chunk_ab``, the probe builds its own model at
    ``cfg``'s context/width but with a TIGHT latent segment
    (``max_latents = 2 * num_latents``): admission cost then comes from
    the prefix positions themselves — the full-window embedding +
    cross-k/v projection sharing elides — rather than from the
    latent-segment stack, which every admission pays identically in both
    arms (at ``max_latents=256`` the stack is most of the prefill and
    buries the A/B in shared cost)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.inference.samplers import SamplingConfig
    from perceiver_io_tpu.models.text.clm import (
        CausalLanguageModel,
        CausalLanguageModelConfig,
    )
    from perceiver_io_tpu.observability import MetricsRegistry, WorkloadSpec
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

    n = cfg.max_seq_len
    num_latents = min(4, cfg.max_latents)
    if cfg.max_latents > 2 * num_latents:
        probe_cfg = CausalLanguageModelConfig(
            vocab_size=cfg.vocab_size,
            max_seq_len=n,
            max_latents=2 * num_latents,
            num_channels=cfg.num_channels,
            num_heads=cfg.num_heads,
            num_self_attention_layers=cfg.num_self_attention_layers,
            cross_attention_dropout=0.0,
        )
        model = CausalLanguageModel(probe_cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, n), jnp.int32),
            n - probe_cfg.max_latents,
        )["params"]
        cfg = probe_cfg
    params = cast_float_params(params, jnp.bfloat16)
    if block_size is None:
        block_size = max(4, min(16, n // 32))
    if prefix_tokens is None:
        # long hot prefix, well past the latent budget, bounded by the
        # prefix-capacity scope check
        prefix_tokens = max(
            block_size * 2,
            min(n // 4, model.max_prefix_len - 32, 384) // block_size * block_size,
        )
    tail_lo, tail_hi = 8, 16
    bucket = prefix_tokens + tail_hi
    if bucket + new_tokens > n:
        raise ValueError("prefix-cache probe shape exceeds the context")
    table = BucketTable(prompt_lens=(bucket,), batch_sizes=(1,))
    gcfg = GenerationConfig(
        max_new_tokens=new_tokens, num_latents=num_latents,
        sampling=SamplingConfig(temperature=0.0),  # greedy: cross-arm identity
    )
    workload = WorkloadSpec(
        prompt_len=(tail_lo, tail_hi),
        max_new_tokens=(new_tokens, new_tokens),
        vocab=(1, cfg.vocab_size),
        shared_prefix_pool=n_prefixes,
        shared_prefix_len=(prefix_tokens, prefix_tokens),
        shared_prefix_zipf=zipf,
    )
    rng = np.random.default_rng(0)
    prompts = [workload.sample_prompt(rng) for _ in range(n_requests)]
    per_req_blocks = -(-(prefix_tokens + tail_hi + new_tokens) // block_size)
    if budget_blocks is None:
        # fits ~3 unshared residents: the unshared arm serializes on the
        # pool while the shared arm — whose residents reserve only their
        # private suffix pages — packs the cached prefixes plus a full
        # house of slots into the same bytes
        budget_blocks = per_req_blocks * 7 // 2
    token_bytes = None

    def run(pc):
        nonlocal token_bytes
        registry = MetricsRegistry()
        engine = SlotServingEngine(
            model, params, gcfg, table, slots=slots, kv_layout="paged",
            kv_block_size=block_size, kv_blocks=budget_blocks,
            prefix_cache=pc, registry=registry,
        )
        engine.warmup()  # compiles are process-global: measured once
        token_bytes = engine._kv_token_bytes
        handles = [engine.submit(p, config=gcfg) for p in prompts]
        max_residents = 0
        t0 = time.perf_counter()
        while engine.pending():
            engine.step()
            active = sum(1 for s in engine._slots if s is not None)
            if engine._admitting is not None:
                active += 1
            max_residents = max(max_residents, active)
        dt = time.perf_counter() - t0
        stats = engine.stats()
        assert engine._pool.leaked() == 0
        return {
            "outs": [h.result for h in handles],
            "ttft_p50_ms": registry.percentile("serving_ttft_ms", 50.0),
            "ttft_p95_ms": registry.percentile("serving_ttft_ms", 95.0),
            "max_residents": max_residents,
            "tokens_per_sec": round(n_requests * new_tokens / dt, 1),
            "admit_waits": stats["kv_pool"]["admit_waits"],
            "prefix": stats["prefix_cache"],
        }

    off = run("off")
    on = run("on")
    token_identical = all(
        a is not None and b is not None and bool(np.array_equal(a, b))
        for a, b in zip(off["outs"], on["outs"])
    )
    budget_bytes = budget_blocks * block_size * token_bytes

    def arm(r):
        return {
            "ttft_p50_ms": None if r["ttft_p50_ms"] is None else round(r["ttft_p50_ms"], 3),
            "ttft_p95_ms": None if r["ttft_p95_ms"] is None else round(r["ttft_p95_ms"], 3),
            "max_residents": r["max_residents"],
            "residents_per_hbm_gb": round(r["max_residents"] / (budget_bytes / 2**30), 2),
            "tokens_per_sec": r["tokens_per_sec"],
            "admit_waits": r["admit_waits"],
        }

    return {
        "workload": {
            "requests": n_requests,
            "prefixes": n_prefixes,
            "zipf": zipf,
            "prefix_tokens": prefix_tokens,
            "tail_tokens": [tail_lo, tail_hi],
            "block_size": block_size,
            "hbm_budget_blocks": budget_blocks,
            "hbm_budget_bytes": budget_bytes,
        },
        "unshared": arm(off),
        "shared": {**arm(on), "prefix": on["prefix"]},
        "ttft_p50_ratio": round(
            (off["ttft_p50_ms"] or 0.0) / max(1e-9, on["ttft_p50_ms"] or 0.0), 2
        ),
        "ttft_p95_ratio": round(
            (off["ttft_p95_ms"] or 0.0) / max(1e-9, on["ttft_p95_ms"] or 0.0), 2
        ),
        "residents_per_hbm_byte_ratio": round(
            on["max_residents"] / max(1, off["max_residents"]), 2
        ),
        "hit_ratio": on["prefix"]["hit_ratio"],
        "token_identical": token_identical,
    }


def _bench_speculative(model, params, cfg, *, slots: int = 1,
                       n_requests: int = 6, new_tokens: int = 16,
                       speculation: str = "k8d1"):
    """Speculative-decoding A/B (ISSUE 19 acceptance; docs/serving.md
    "Speculative decoding"): the same greedy workload served through the
    slot engine twice — ``speculation="off"`` (one fixed-shape forward per
    token) vs a self-draft geometry (one truncated-stack draft + one
    batched verify per up-to-``k+1``-token round). Recorded acceptance
    numbers: tokens/s per arm and their ratio, the draft acceptance rate,
    accepted tokens per round, and ``token_identical`` between the arms'
    greedy outputs (the exactness bar, also pinned by
    ``tests/test_speculative.py``).

    Speculation pays where decode steps are dispatch-bound, not
    flop-bound — the verify forward batches ``k+1`` lanes, so its FLOPs
    grow with ``k`` while its fixed per-step cost does not. The probe
    therefore builds a deliberately SMALL model (per-step overhead
    dominates, the regime edge TPU serving lives in at batch 1) rather
    than reusing ``cfg``'s width, and serves a SINGLE slot — a lone
    resident pays the full per-pass cost for every one-token step, which
    is exactly what a multi-token round amortizes. The ``autotune`` block pins both
    verdict directions: ``pays`` measures draft geometries on the
    dispatch-bound probe and picks one; ``decline`` offers only a draft
    as deep as the model itself (``d == num_self_attention_layers``), so
    every candidate is skipped and the verdict stays ``"off"``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import decode_strategy as strategy_mod
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.inference.samplers import SamplingConfig
    from perceiver_io_tpu.models.text.clm import (
        CausalLanguageModel,
        CausalLanguageModelConfig,
    )
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

    probe_cfg = CausalLanguageModelConfig(
        vocab_size=cfg.vocab_size,
        max_seq_len=min(cfg.max_seq_len, 32),
        num_channels=min(cfg.num_channels, 16),
        max_latents=8,
        num_heads=2,
        num_self_attention_layers=2,
        cross_attention_dropout=0.0,
    )
    n = probe_cfg.max_seq_len
    model = CausalLanguageModel(probe_cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, n), jnp.int32),
        n - probe_cfg.max_latents,
    )["params"]
    gcfg = GenerationConfig(
        max_new_tokens=new_tokens, num_latents=2,
        sampling=SamplingConfig(temperature=0.0),  # greedy: cross-arm identity
    )
    table = BucketTable(prompt_lens=(16,), batch_sizes=(1,))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, probe_cfg.vocab_size, size=int(m)).astype(np.int32)
        for m in rng.integers(6, 14, size=n_requests)
    ]

    def run(spec):
        engine = SlotServingEngine(
            model, params, gcfg, table, slots=slots, speculation=spec,
        )
        engine.warmup()  # compiles are process-global: measured once
        t0 = time.perf_counter()
        outs = engine.serve(prompts)
        dt = time.perf_counter() - t0
        emitted = sum(len(np.asarray(o)) for o in outs)
        stats = engine.stats()
        return {
            "outs": [np.asarray(o) for o in outs],
            "tokens_per_sec": round(emitted / dt, 1),
            "steps": stats["decode_steps"],
            "speculation": stats["speculation"],
        }

    off = run("off")
    spec = run(speculation)
    token_identical = all(
        bool(np.array_equal(a, b)) for a, b in zip(off["outs"], spec["outs"])
    )

    # the autotuner's two verdict directions, measured on the same probe
    # (force=True: the second run must re-measure, not return the first
    # verdict; entries key on the probe shape so neither pollutes cfg's)
    pays = strategy_mod.autotune_speculation(
        model, params, candidates=("k4d1", "k8d1"), force=True,
    )
    pays_entry = strategy_mod.spec_entry(model) or {"speculation": pays}
    decline = strategy_mod.autotune_speculation(
        model, params,
        candidates=(f"k4d{probe_cfg.num_self_attention_layers}",),
        force=True,
    )
    decline_entry = strategy_mod.spec_entry(model) or {"speculation": decline}

    return {
        "workload": {
            "requests": n_requests,
            "new_tokens": new_tokens,
            "speculation": speculation,
            "probe": {
                "channels": probe_cfg.num_channels,
                "layers": probe_cfg.num_self_attention_layers,
                "context": n,
            },
        },
        "off": {"tokens_per_sec": off["tokens_per_sec"],
                "decode_steps": off["steps"]},
        "spec": {"tokens_per_sec": spec["tokens_per_sec"],
                 "decode_steps": spec["steps"]},
        "speedup": round(
            spec["tokens_per_sec"] / max(1e-9, off["tokens_per_sec"]), 2
        ),
        "acceptance_rate": spec["speculation"]["acceptance_rate"],
        "tokens_per_round": spec["speculation"]["tokens_per_round"],
        "token_identical": token_identical,
        "autotune": {"pays": pays_entry, "decline": decline_entry},
    }


def _bench_prefill_chunk_ab(cfg, *, slots: int = 2,
                            resident_new: int = 48, n_long: int = 5,
                            chunk: int = None, episodes: int = 5):
    """Chunked-prefill A/B (ISSUE 5 acceptance): a resident slot decodes
    while a stream of near-window-length admissions flows through the other
    slot, with and without ``prefill_chunk``. Without chunking each
    admission's full-window prefill runs between two decode steps, so the
    resident request's inter-token latency spikes by the whole prefix's
    cost once per admission; with chunking the prefix cache is built one
    bounded chunk per ``step()``. The reported number is the resident
    request's p95 inter-token gap — lower with chunking is the acceptance
    bar at the reduced drill shape.

    Two deliberate probe choices. (1) A *stream* of admissions, not one: a
    single admission elevates one gap in ~30, which the 95th percentile
    never sees — the metric only speaks when admissions are a steady
    fraction of traffic, which is also the serving regime chunking is for.
    (2) The probe builds its own model at ``cfg``'s context/width but with
    a tight latent segment (``max_latents = 2 * num_latents``): admission
    cost then comes from the prefix positions themselves (embedding +
    cross-k/v over ~``n`` tokens — the part chunking amortizes) rather
    than from the latent-segment stack, which every admission pays
    identically in both arms (at ``max_latents=256`` it is ~85% of the
    prefill, drowning the A/B in shared cost). Both engines warm up first
    (compiles stay out of the gaps) and serve the identical submission
    schedule, repeated for ``episodes`` interleaved passes with the median
    per-episode p95 reported (this host's steal-time spikes are the same
    order as the signal; one spiked pass must not decide the verdict)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.models.text.clm import (
        CausalLanguageModel,
        CausalLanguageModelConfig,
    )
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

    n = cfg.max_seq_len
    num_latents = min(16, cfg.max_latents)
    # 4x headroom: the resident request must stay in the cheap latent-growth
    # phase for its whole lifetime (resident_new <= max_latents -
    # num_latents), or every post-crossing step pays the boundary variant's
    # full-window cost in BOTH arms and buries the admission signal
    probe_cfg = CausalLanguageModelConfig(
        vocab_size=cfg.vocab_size,
        max_seq_len=n,
        max_latents=min(cfg.max_latents, 4 * num_latents),
        num_channels=cfg.num_channels,
        num_heads=cfg.num_heads,
        num_self_attention_layers=cfg.num_self_attention_layers,
        cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(probe_cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, n), jnp.int32),
        n - probe_cfg.max_latents,
    )["params"]
    params = cast_float_params(params, jnp.bfloat16)

    if probe_cfg.max_latents > num_latents:
        # floor of 2: the resident must emit at least two tokens or it has
        # no inter-token gaps to measure (shapes whose latent headroom is 1
        # trade a little boundary-phase noise for a runnable probe)
        resident_new = max(2, min(resident_new, probe_cfg.max_latents - num_latents))
    long_new = 2
    # prefix ~ the whole window, within the bucket feasibility bound
    # (len - num_latents <= max_prefix_len) and the slot scope (len +
    # long_new <= n)
    long_len = min(n - long_new, model.max_prefix_len + num_latents)
    short_len = max(num_latents, min(64, n // 8))
    if chunk is None:
        # ~4 chunk calls per admission: enough to bound each per-step stall
        # well under the one-shot prefill, few enough that the per-call
        # dispatch overhead stays a minority of the chunked arm's gaps
        chunk = max(16, -(-(long_len - num_latents) // 4))
    table = BucketTable(
        prompt_lens=tuple(sorted({short_len, long_len})), batch_sizes=(1,)
    )
    base = GenerationConfig(max_new_tokens=resident_new, num_latents=num_latents)
    rng = np.random.default_rng(0)
    short = rng.integers(1, cfg.vocab_size, size=short_len, dtype=np.int32)
    longs = [
        rng.integers(1, cfg.vocab_size, size=long_len, dtype=np.int32)
        for _ in range(n_long)
    ]
    long_cfg = dataclasses.replace(base, max_new_tokens=long_new)

    def episode(engine) -> "np.ndarray":
        """One measured pass of the workload: a resident decode with a
        steady stream of long admissions; returns the resident's inter-token
        gaps in ms."""
        resident = engine.submit(short)
        gaps = []
        last = None
        emitted = 0
        submitted = 0
        while engine.pending():
            engine.step()
            now = time.perf_counter()
            entry = next(
                (s for s in engine._slots if s is not None and s.req is resident),
                None,
            )
            count = len(entry.emitted) if entry is not None else resident_new
            if count > emitted:
                if last is not None:
                    gaps.append(now - last)
                last = now
                emitted = count
            # steady admission pressure: one long request queued at a time,
            # the next submitted the moment the previous leaves the queue —
            # identical schedule in both arms
            if submitted < n_long and emitted >= 2 and not engine._queue:
                engine.submit(longs[submitted], config=long_cfg)
                submitted += 1
        return np.asarray(gaps) * 1e3

    engines = {
        arm: SlotServingEngine(
            model, params, base, table, slots=slots,
            prefill_chunk=chunk if arm else None,
        )
        for arm in (False, True)
    }
    for engine in engines.values():
        engine.warmup()
    # interleave the arms' episodes so background-noise drift (this host's
    # steal-time spikes) hits both arms equally, and take the median across
    # episodes so one spiked pass cannot decide the verdict
    runs = {False: [], True: []}
    for _ in range(max(1, episodes)):
        for arm in (False, True):
            runs[arm].append(episode(engines[arm]))

    def summarize(arm: bool) -> dict:
        per_ep = runs[arm]
        all_gaps = np.concatenate(per_ep)
        stats = engines[arm].stats()
        return {
            "p95_inter_token_ms": round(float(np.median(
                [np.percentile(g, 95) for g in per_ep])), 3),
            "max_inter_token_ms": round(float(all_gaps.max()), 3),
            "p50_inter_token_ms": round(float(np.percentile(all_gaps, 50)), 3),
            "gaps": int(all_gaps.size),
            "episodes": len(per_ep),
            "prefill_chunks": stats["prefill_chunks"],
            "completed": stats["completed"],
        }

    without = summarize(False)
    with_c = summarize(True)
    return {
        "workload": {
            "slots": slots, "chunk": chunk, "resident_prompt_len": short_len,
            "long_prompt_len": long_len, "long_admissions": n_long,
            "resident_new_tokens": resident_new, "long_new_tokens": long_new,
            "probe_max_latents": probe_cfg.max_latents,
            "probe_ctx": n,
        },
        "without_chunking": without,
        "with_chunking": with_c,
        "p95_ratio_without_over_with": round(
            without["p95_inter_token_ms"] / max(1e-9, with_c["p95_inter_token_ms"]), 2
        ),
        "chunking_lowers_p95": with_c["p95_inter_token_ms"]
        < without["p95_inter_token_ms"],
    }


def _bench_chaos(model, params, cfg, *, n_requests: int = 8, new_tokens: int = 4):
    """Deterministic chaos drill over the serving engine (docs/reliability.md):
    a bounded queue under overload (shed counter), one request hung past its
    deadline (``timed_out``), one request failed at pack time (``failed``) —
    while every other request completes. Faults come from the explicit-hook
    chaos registry on a fake clock, so the probe's outcome is bit-identical
    on every run and every backend; ``survived`` asserts the engine's
    accounting closed (submitted == completed + timed_out + failed + shed)."""
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.reliability import QueueFull
    from perceiver_io_tpu.reliability.chaos import ChaosRegistry, FakeClock
    from perceiver_io_tpu.serving import BucketTable, ServingEngine

    params = cast_float_params(params, jnp.bfloat16)
    num_latents = min(8, cfg.max_latents)
    max_len = min(32, cfg.max_seq_len // 2, cfg.max_seq_len - cfg.max_latents + num_latents)
    table = BucketTable(prompt_lens=(max_len,), batch_sizes=(2,))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)

    chaos = ChaosRegistry()
    chaos.hang_request(1, delay_s=2.0)  # > its 1s deadline, < the others'
    chaos.fail_request(2)
    engine = ServingEngine(
        model, params, gcfg, table,
        max_queue=n_requests - 2, default_deadline_s=60.0,
        clock=FakeClock(), chaos=chaos,
    )

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=max_len, dtype=np.int32)
        for _ in range(n_requests)
    ]
    shed = 0
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        try:
            engine.submit(p, deadline_s=1.0 if i == 1 else None)
        except QueueFull:
            shed += 1
    engine.drain()
    wall_s = time.perf_counter() - t0
    s = engine.stats()
    accounted = s["completed"] + s["timed_out"] + s["failed"] + shed
    return {
        "submitted": n_requests,
        "shed": shed,
        "timed_out": s["timed_out"],
        "failed": s["failed"],
        "completed": s["completed"],
        "batches": s["batches"],
        "survived": accounted == n_requests and s["queued"] == 0,
        "ready_after_drain": engine.health()["ready"],
        "wall_s": round(wall_s, 3),
    }


def _bench_fleet_chaos(model, params, cfg, *, n_requests: int = 8,
                       new_tokens: int = 6, replicas: int = 3):
    """Supervised-fleet chaos drill (docs/serving.md): a FleetRouter over
    ``replicas`` slot-engine replicas serves a mixed workload while a
    scripted fault kills one replica MID-DECODE (``fleet.replica_step.<r>``
    chaos site). The probe reports goodput and completion ratio under the
    kill, and pins the recovery guarantees: every accepted request
    completes exactly once and — greedy decode being deterministic — every
    recovered output is token-identical to a no-fault reference run.
    Scheduling runs on a FakeClock, so the fault script and outcome replay
    bit-identically; only the goodput wall time is real."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.reliability.chaos import ChaosRegistry, FakeClock
    from perceiver_io_tpu.serving import BucketTable, FleetRouter, SlotServingEngine

    params = cast_float_params(params, jnp.bfloat16)
    num_latents = min(4, cfg.max_latents)
    max_len = min(
        16, cfg.max_seq_len - new_tokens,
        cfg.max_seq_len - cfg.max_latents + num_latents,
    )
    table = BucketTable(prompt_lens=(max_len,), batch_sizes=(1,))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=max_len, dtype=np.int32)
        for _ in range(n_requests)
    ]

    def run(chaos):
        clock = FakeClock()

        def factory():
            return SlotServingEngine(
                model, params, gcfg, table, slots=2, clock=clock,
                rng=jax.random.PRNGKey(1),
            )

        fleet = FleetRouter(
            [factory] * replicas, clock=clock, chaos=chaos,
        )
        reqs = [fleet.submit(p) for p in prompts]
        fleet.run_until_idle()
        return fleet, reqs

    _, ref_reqs = run(None)  # no-fault reference (also warms the executors)
    reference = [r.result for r in ref_reqs]

    chaos = ChaosRegistry()
    chaos.crash_replica(0, 3)  # replica 0's 3rd supervised step: mid-decode
    t0 = time.perf_counter()
    fleet, reqs = run(chaos)
    wall_s = time.perf_counter() - t0
    s = fleet.stats()
    completed = sum(1 for r in reqs if r.status == "ok")
    token_identical = all(
        r.status == "ok" and np.array_equal(r.result, want)
        for r, want in zip(reqs, reference)
    )
    from perceiver_io_tpu.observability import goodput_ratio, offered_load

    fleet_counts = fleet.registry.counters()
    return {
        "replicas": replicas,
        "submitted": n_requests,
        "completed": completed,
        "completion_ratio": round(completed / n_requests, 4),
        # the shared goodput definition (observability/slo.py): completed /
        # offered (accepted + shed + rejected) — same helper as the
        # observability and slo_goodput probes
        "offered": offered_load(fleet_counts, "fleet"),
        "goodput_ratio": round(goodput_ratio(fleet_counts, "fleet"), 4),
        "failovers": s["failovers"],
        "redispatches": s["redispatches"],
        "replica_restarts": s["replica_restarts"],
        "duplicate_results_ignored": s["duplicate_results_ignored"],
        "token_identical": token_identical,
        # exactly-once accounting closes: every submission one disposition
        "survived": (
            s["completed"] + s["timed_out"] + s["failed"] == n_requests
            and s["queued"] == 0 and s["dispatched"] == 0
        ),
        "goodput_tokens_per_sec": round(completed * new_tokens / wall_s, 2),
        "wall_s": round(wall_s, 3),
    }


def _bench_elasticity(model, params, cfg, *, n_requests: int = 24,
                      new_tokens: int = 8, slots: int = 1,
                      max_replicas: int = 3, spike_factor: float = 3.0):
    """Fleet-elasticity A/B (docs/serving.md "Elasticity"): the SAME
    deterministic FakeClock flash crowd — baseline Poisson with a
    ``spike_factor``x step (the loadgen ``spike`` arrival) at ~3x one
    replica's capacity — offered to (a) a STATIC single-replica fleet and
    (b) the same fleet behind a :class:`FleetAutoscaler` bounded at
    ``max_replicas``. Both runs share the SLO targets calibrated from a
    healthy closed-loop pass, and goodput-under-SLO is per-point: a
    request is GOOD when it completed AND its own first-token latency met
    the TTFT target (joined from its ``serving.first_token`` event).

    The probe reports both runs' SLO-goodput, the autoscaled run's
    breach -> scale-up -> recovery -> cooldown-gated scale-down timeline
    (``autoscaler.*`` events), and the acceptance pins: the autoscaled
    fleet's goodput-under-SLO beats the static baseline, NO accepted
    request is dropped across the scale transitions, completed outputs are
    token-identical between the two runs (greedy determinism — scale
    churn adds capacity, not entropy), and the scale-down victim's pool
    accounting is zero-leak with its frees tagged ``scale_down``.
    Everything but wall time replays bit-identically."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.observability import (
        LoadGenerator,
        MetricsRegistry,
        Tracer,
        TTFTProbe,
        WorkloadSpec,
    )
    from perceiver_io_tpu.observability.slo import SLOMonitor, SLOPolicy
    from perceiver_io_tpu.reliability.chaos import FakeClock
    from perceiver_io_tpu.serving import (
        BucketTable,
        FleetAutoscaler,
        FleetRouter,
        SlotServingEngine,
    )

    params = cast_float_params(params, jnp.bfloat16)
    num_latents = min(4, cfg.max_latents)
    max_len = min(
        16, cfg.max_seq_len - new_tokens,
        cfg.max_seq_len - cfg.max_latents + num_latents,
    )
    table = BucketTable(prompt_lens=(max_len,), batch_sizes=(1,))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)
    workload = WorkloadSpec(
        prompt_len=(max(2, max_len // 2), max_len),
        max_new_tokens=(max(2, 3 * new_tokens // 4), new_tokens),
        vocab=(1, cfg.vocab_size),
    )
    step_cost_s = 0.01

    def build(clock, *, autoscale: bool, registry, tracer, monitor):
        def factory():
            return SlotServingEngine(
                model, params, gcfg, table, slots=slots, clock=clock,
                kv_layout="paged", rng=jax.random.PRNGKey(3),
            )

        fleet = FleetRouter(
            [factory], clock=clock, registry=registry, tracer=tracer,
            slo_monitor=monitor,
        )
        scaler = None
        if autoscale:
            scaler = FleetAutoscaler(
                fleet, min_replicas=1, max_replicas=max_replicas,
                up_cooldown_s=0.3, down_cooldown_s=2.0,
                up_evidence=2, down_evidence=25,
                queue_high=1.0, queue_low=0.5,
            )
        return fleet, scaler

    # warm the executor grid once; every later replica (initial or
    # autoscaler-spawned) reuses the process-global caches
    SlotServingEngine(
        model, params, gcfg, table, slots=slots, kv_layout="paged",
    ).warmup()

    # calibration: a healthy closed-loop pass on one static replica sets
    # capacity (completed req/s on the fake clock) and the TTFT target
    cal_clock = FakeClock()
    cal_fleet, _ = build(
        cal_clock, autoscale=False, registry=MetricsRegistry(clock=cal_clock),
        tracer=None, monitor=None,
    )
    cal = LoadGenerator(
        cal_fleet, workload=workload, mode="closed", users=max(1, slots),
        max_requests=max(6, n_requests // 4), rng=0, clock=cal_clock,
        step_cost_s=step_cost_s,
    ).run()
    base_rps = max(cal["completed_rps"], 0.1)
    cal_reg = cal_fleet.registry
    # target floor = a few scheduler passes: an unqueued FakeClock request
    # can see TTFT 0 (tokens materialize before the pass's clock charge),
    # so the calibration p95 alone can undershoot the service floor
    slo_ttft_ms = round(
        3.0 * max(
            cal_reg.percentile("serving_ttft_ms", 95.0) or 0.0,
            step_cost_s * 1e3,
        ), 3,
    )
    spike_start_s = 1.0
    spike_duration_s = 4.0

    def run(autoscale: bool):
        clock = FakeClock()
        registry = MetricsRegistry(clock=clock)
        tracer = Tracer(clock=clock)
        monitor = SLOMonitor(
            SLOPolicy(ttft_p95_ms=slo_ttft_ms), clock=clock,
            registry=registry, tracer=tracer,
            fast_window_s=1.0, slow_window_s=4.0,
            breach_burn_rate=1.5, min_samples=4,
        )
        fleet, scaler = build(
            clock, autoscale=autoscale, registry=registry, tracer=tracer,
            monitor=monitor,
        )
        # client-side per-request TTFT through the on_token sink — the
        # fleet-drill goodput join (the engines' serving.first_token
        # events carry per-replica trace ids, not the fleet handle's)
        probe = TTFTProbe(fleet, clock)
        gen = LoadGenerator(
            probe, workload=workload, mode="open", arrival="spike",
            rate_rps=0.8 * base_rps, spike_factor=spike_factor,
            spike_start_s=spike_start_s, spike_duration_s=spike_duration_s,
            max_requests=n_requests, config=gcfg, rng=1, clock=clock,
            step_cost_s=step_cost_s,
        )
        report = gen.run()
        # settle: keep the control loop polling after the crowd passes so
        # recovery evidence accumulates and the cooldown-gated scale-down
        # fires (bounded — the drill must terminate even if it never does)
        for _ in range(600):
            if scaler is None or len(fleet.replicas) <= scaler.min_replicas:
                break
            fleet.step()
            clock.advance(step_cost_s)
        good = probe.good_under(slo_ttft_ms)
        return {
            "fleet": fleet, "scaler": scaler, "gen": gen, "probe": probe,
            "report": report, "registry": registry, "tracer": tracer,
            "good": good,
            "goodput_under_slo": round(good / max(1, report["offered"]), 4),
        }

    static = run(False)
    auto = run(True)

    # token identity: same rng -> same offered prompt sequence; every
    # request completed in BOTH runs must match bit-for-bit. Pair by the
    # probe's OFFERED index, not positionally — the runs shed differently
    # (that asymmetry is the whole point of the A/B), so the accepted
    # handle lists misalign as soon as one run drops an offer
    def _by_index(r):
        return {
            rec["index"]: rec["handle"] for rec in r["probe"].records
            if rec["handle"] is not None
        }

    auto_h, static_h = _by_index(auto), _by_index(static)
    pairs = [
        (auto_h[i], static_h[i]) for i in sorted(set(auto_h) & set(static_h))
        if auto_h[i].status == "ok" and static_h[i].status == "ok"
    ]
    token_identical = bool(pairs) and all(
        np.array_equal(a.result, s.result) for a, s in pairs
    )
    scaler = auto["scaler"]
    fleet = auto["fleet"]
    counts = auto["registry"].counters()
    live_pools = [
        r.engine._pool for r in fleet.replicas if r.engine._pool is not None
    ]
    retired_pools = [r["pool"] for r in scaler.retired if r["pool"]]
    timeline = [
        {"at_s": round(sp.start_s, 4), "event": sp.name,
         **{k: sp.attrs[k] for k in ("reason", "replica", "rung",
                                     "replicas_after") if k in sp.attrs}}
        for sp in auto["tracer"].spans()
        if sp.name.startswith(("autoscaler.", "slo."))
    ]
    s = fleet.stats()
    return {
        "requests": n_requests,
        "slots": slots,
        "max_replicas": max_replicas,
        "spike_factor": spike_factor,
        "slo_ttft_ms": slo_ttft_ms,
        "capacity_rps": round(base_rps, 4),
        "static": {
            "goodput_under_slo": static["goodput_under_slo"],
            "completed": static["report"]["completed"],
            "p95_ttft_ms": round(
                static["registry"].percentile("serving_ttft_ms", 95.0) or 0.0, 3
            ),
        },
        "autoscaled": {
            "goodput_under_slo": auto["goodput_under_slo"],
            "completed": auto["report"]["completed"],
            "p95_ttft_ms": round(
                auto["registry"].percentile("serving_ttft_ms", 95.0) or 0.0, 3
            ),
            "scale_ups": scaler.scale_ups,
            "scale_downs": scaler.scale_downs,
            "breaches": int(counts.get("slo_breach_total", 0)),
            "replicas_final": len(fleet.replicas),
            "rung_final": scaler.rung,
        },
        "goodput_ratio_vs_static": round(
            auto["goodput_under_slo"] / max(static["goodput_under_slo"], 1e-4), 4
        ),
        # acceptance pins (tests/test_elasticity.py asserts these)
        "elastic_beats_static": (
            auto["goodput_under_slo"] > static["goodput_under_slo"]
        ),
        "zero_dropped": (
            s["completed"] + s["timed_out"] + s["failed"]
            == s["submitted"] and s["queued"] == 0 and s["dispatched"] == 0
            and s["failed"] == 0
        ),
        "token_identical": token_identical,
        "pool_zero_leak": (
            all(p["leaked"] == 0 and p["in_use"] == 0 for p in retired_pools)
            and all(p.leaked() == 0 and p.in_use == 0 for p in live_pools)
        ),
        # a victim that still held in-flight work at removal tags its
        # frees "scale_down"; one already idle freed on ordinary retire —
        # either way every page was returned (tests/test_elasticity.py
        # pins the tag itself on a mid-flight remove_replica)
        "scale_down_clean": (
            None if not retired_pools else all(
                "scale_down" in p["frees_by_cause"]
                or (p["in_use"] == 0 and p["leaked"] == 0)
                for p in retired_pools
            )
        ),
        "retired": scaler.retired,
        "timeline": timeline,
    }


def _bench_observability(model, params, cfg, *, n_requests: int = 12,
                         new_tokens: int = 4):
    """Unified-telemetry probe (docs/observability.md): mixed-length traffic
    through a registry+tracer-instrumented ``ServingEngine``, with one
    deterministic pack-time fault so goodput < 1 is exercised, not assumed.
    Reports the three per-phase latency histograms (queue wait, batch
    assembly, device execute), serving throughput, goodput
    (completed / submitted), and an MFU gauge — decode FLOPs/token from
    ``utils/flops.flops_approx`` (fwd-only ≈ 2N) against the detected device
    peak (None off the TPU, where no utilization is claimable). Also
    asserts span accounting closes: every submission ends in exactly one
    terminal ``serving.request`` span."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.observability import MetricsRegistry, Tracer
    from perceiver_io_tpu.reliability.chaos import ChaosRegistry
    from perceiver_io_tpu.serving import BucketTable, ServingEngine
    from perceiver_io_tpu.utils.flops import flops_approx

    params = cast_float_params(params, jnp.bfloat16)
    num_latents = min(16, cfg.max_latents)
    max_prefix = cfg.max_seq_len - cfg.max_latents
    max_len = min(128, cfg.max_seq_len // 2, max_prefix + num_latents)
    lens_grid = sorted({max(num_latents, max_len // 2), max_len})
    table = BucketTable(prompt_lens=tuple(lens_grid), batch_sizes=(2, 4))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)

    chaos = ChaosRegistry()
    chaos.fail_request(2)  # deterministic non-ok terminal state
    registry = MetricsRegistry()
    tracer = Tracer()
    engine = ServingEngine(
        model, params, gcfg, table, chaos=chaos,
        registry=registry, tracer=tracer,
    )

    rng = np.random.default_rng(0)
    lo = max(1, max_len // 4)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=int(n), dtype=np.int32)
        for n in rng.integers(lo, max_len + 1, size=n_requests)
    ]
    t0 = time.perf_counter()
    for p in prompts:
        engine.submit(p)
    engine.drain()
    wall = time.perf_counter() - t0

    s = engine.stats()
    terminal: dict = {}
    for sp in tracer.spans("serving.request"):
        terminal[sp.status] = terminal.get(sp.status, 0) + 1
    # goodput denominator is OFFERED load (accepted + shed + rejected) —
    # the ONE shared definition (observability/slo.py), also used by the
    # fleet-chaos and slo-goodput probes so the three cannot drift
    from perceiver_io_tpu.observability import goodput_ratio
    goodput = goodput_ratio(registry.counters())
    tokens_per_sec = s["tokens_generated"] / wall

    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    decode_flops_per_token = flops_approx(n_params) // 3  # fwd-only ≈ 2N
    # a utilization is a device metric: claimed on a TPU only
    device = jax.devices()[0]
    mfu = (
        round(tokens_per_sec * decode_flops_per_token / peak_flops(device), 6)
        if device.platform == "tpu" else None
    )
    registry.set_gauge("serving_throughput_tokens_per_sec", tokens_per_sec)
    registry.set_gauge("serving_goodput_ratio", goodput)
    if mfu is not None:
        registry.set_gauge("serving_mfu", mfu)
    snap = registry.snapshot()
    return {
        "tokens_per_sec": round(tokens_per_sec, 1),
        "goodput": round(goodput, 4),
        "mfu": mfu,
        "queue_wait_ms": snap["histograms"].get("serving_queue_wait_ms"),
        "batch_assembly_ms": snap["histograms"].get("serving_batch_assembly_ms"),
        "device_execute_ms": snap["histograms"].get("serving_device_execute_ms"),
        "request_latency_ms": snap["histograms"].get("serving_request_latency_ms"),
        "terminal_spans": terminal,
        "span_accounting_closed": sum(terminal.values()) == n_requests,
        "requests": n_requests,
        "new_tokens": new_tokens,
        "snapshot": snap,
    }


def _bench_streaming(model, params, cfg, *, slots: int = 4, n_requests: int = 10,
                     abandon_every: int = 2, cancel_after_tokens: int = 2,
                     new_tokens: int = 6):
    """Mid-stream mass-abandonment drill (docs/serving.md "Streaming"):
    the gateway's cancellation-safe retirement path, driven deterministically
    under :class:`~perceiver_io_tpu.reliability.FakeClock` — no sockets, so
    the drill replays bit-identically and the numbers are scheduling, not
    network, latency.

    ``n_requests`` streamed requests run through a PAGED slot engine with
    per-request ``on_token`` sinks; every ``abandon_every``-th stream is
    abandoned the scheduler pass after its ``cancel_after_tokens``-th token
    materializes (how a gateway notices a disconnect: between steps). The
    record pins the three acceptance invariants:

    - **reclaim latency** — token-instant → pool-pages-freed, per victim
      (bounded by one scheduler pass; the "within one step()" bar);
    - **zero leak** — ``kv_pool`` blocks in use / reserved / leaked all 0
      at drain, with the cancelled frees separable in ``frees_by_cause``;
    - **survivor token-identity** — unaffected streams' outputs match a
      fault-free engine pass exactly, incrementally-streamed tokens
      included (``completed + cancelled == accepted`` closes accounting).
    """
    import jax
    import numpy as np

    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.observability import MetricsRegistry, Tracer
    from perceiver_io_tpu.reliability import FakeClock
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

    num_latents = min(4, cfg.max_latents)
    max_len = min(
        16, cfg.max_seq_len - new_tokens,
        cfg.max_seq_len - cfg.max_latents + num_latents,
    )
    table = BucketTable(prompt_lens=(max_len,), batch_sizes=(1,))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=int(n)).astype(np.int32)
        for n in rng.integers(max(num_latents, max_len // 2), max_len + 1,
                              size=n_requests)
    ]
    step_cost_s = 0.01

    def make_engine(clock, tracer, registry):
        return SlotServingEngine(
            model, params, gcfg, table, slots=slots, kv_layout="paged",
            clock=clock, tracer=tracer, registry=registry,
            rng=jax.random.PRNGKey(3),
        )

    # warm once; the reference pass and the drill reuse every executor
    make_engine(FakeClock(), None, MetricsRegistry()).warmup()

    # fault-free reference pass: the survivor-identity oracle
    ref_engine = make_engine(FakeClock(), None, MetricsRegistry())
    ref_out = ref_engine.serve(prompts)

    clock = FakeClock()
    tracer = Tracer(clock=clock)
    registry = MetricsRegistry(clock=clock)
    engine = make_engine(clock, tracer, registry)
    streams = {}
    for i, p in enumerate(prompts):
        toks: list = []
        req = engine.submit(
            p, on_token=lambda idx, t, _toks=toks: _toks.append((idx, t))
        )
        streams[req.request_id] = {
            "req": req, "tokens": toks, "victim": i % abandon_every == 0,
            "token_at": None, "reclaim_ms": None,
        }
    abandoned = 0
    reclaims = []
    while engine.pending():
        engine.step()
        clock.advance(step_cost_s)
        for s in streams.values():
            if (
                s["victim"] and not s["req"].done and s["reclaim_ms"] is None
                and len(s["tokens"]) >= cancel_after_tokens
            ):
                if s["token_at"] is None:
                    s["token_at"] = clock()  # noticed between steps
                    continue  # the gateway notices on the NEXT pass
                if engine.cancel(s["req"].request_id):
                    s["reclaim_ms"] = (clock() - s["token_at"]) * 1e3
                    reclaims.append(s["reclaim_ms"])
                    abandoned += 1
    engine.drain()
    pool = engine._pool
    survivors = [s for s in streams.values() if s["reclaim_ms"] is None]
    # request ids are assigned in submit order, so sorted(streams) aligns
    # 1:1 with the reference pass's output order
    identical = all(
        s["req"].status == "ok"
        and np.array_equal(s["req"].result, ref)
        and [t for _, t in s["tokens"]] == [
            int(t) for t in ref[: len(s["tokens"])]
        ]
        for s, ref in (
            (streams[rid], ref_out[j])
            for j, rid in enumerate(sorted(streams))
            if streams[rid]["reclaim_ms"] is None
        )
    )
    counts = registry.counters()
    completed = int(counts.get("serving_requests_completed_total", 0))
    cancelled = int(counts.get("serving_requests_cancelled_total", 0))
    reclaims_sorted = sorted(reclaims)
    return {
        "slots": slots,
        "requests": n_requests,
        "abandoned": abandoned,
        "survivors": len(survivors),
        "cancel_after_tokens": cancel_after_tokens,
        "token_identical": bool(identical),
        "accounting_closed": completed + cancelled == n_requests,
        "completed": completed,
        "cancelled": cancelled,
        "reclaim": {
            "p50_ms": round(
                reclaims_sorted[len(reclaims_sorted) // 2], 3
            ) if reclaims_sorted else None,
            "p95_ms": round(
                reclaims_sorted[
                    min(len(reclaims_sorted) - 1,
                        int(0.95 * len(reclaims_sorted)))
                ], 3
            ) if reclaims_sorted else None,
            "max_ms": round(max(reclaims_sorted), 3) if reclaims_sorted else None,
            "bound_ms": round(step_cost_s * 1e3, 3),  # one scheduler pass
        },
        "pool": {
            "leaked": pool.leaked(),
            "in_use_after_drain": pool.in_use,
            "reserved_after_drain": pool.reserved,
            "frees_by_cause": dict(sorted(pool.frees_by_cause.items())),
            "high_water": pool.high_water,
        },
    }


def _bench_incident(model, params, cfg, *, n_requests: int = 4,
                    new_tokens: int = 4, sample_rate: float = 0.1):
    """Incident flight-recorder chaos drill (docs/observability.md "Flight
    recorder & incident bundles"), deterministic under
    :class:`~perceiver_io_tpu.reliability.FakeClock`: a healthy warm-up
    cohort, then a latency fault (requests age past the TTFT target) with
    a scripted replica crash mid-decode — the SLO breach and the replica
    failure each dump exactly one bounded atomic bundle (per-kind
    cooldown), and the ``obs incident`` analyzer is run over the post-run
    capture to pin the joins:

    - **trace_join** — every trace id the crash bundle names appears in
      the (10%-sampled) events.jsonl, because non-ok terminals are always
      tail-kept;
    - **decomposition_exact** — the analyzer's per-request TTFT
      components telescope to the registry's recorded ``serving_ttft_ms``
      with zero unattributed residue, and the worst decomposed request
      matches the registry max exactly;
    - **nonok_traces_kept** — 100% of non-ok terminal traces reached disk
      despite head sampling, with kept + sampled_out == total closing the
      span accounting.
    """
    import json as _json
    import os
    import tempfile

    import jax
    import numpy as np

    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.observability import (
        FlightRecorder,
        JsonlSpanSink,
        MetricsRegistry,
        SamplingSpanSink,
        SLOMonitor,
        SLOPolicy,
        Tracer,
        read_events_jsonl,
    )
    from perceiver_io_tpu.observability import report as report_mod
    from perceiver_io_tpu.observability.tracing import TAIL_KEEP_STATUSES
    from perceiver_io_tpu.reliability import ChaosRegistry, FakeClock, RetryPolicy
    from perceiver_io_tpu.serving import BucketTable, FleetRouter, SlotServingEngine

    num_latents = min(4, cfg.max_latents)
    max_len = min(
        8, cfg.max_seq_len - new_tokens,
        cfg.max_seq_len - cfg.max_latents + num_latents,
    )
    table = BucketTable(prompt_lens=(max_len,), batch_sizes=(1,))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)
    root = tempfile.mkdtemp(prefix="bench-incident-")
    events_path = os.path.join(root, "events.jsonl")
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    sampler = SamplingSpanSink(
        JsonlSpanSink(events_path), rate=sample_rate, registry=reg
    )
    tracer = Tracer(clock=clock, sink=sampler)
    recorder = FlightRecorder(
        os.path.join(root, "incidents"), tracer=tracer, registry=reg,
        clock=clock, cooldown_s=3600.0, max_bundles=8, keep_spans=256,
        snapshot_every_s=0.5,
    )
    monitor = SLOMonitor(
        SLOPolicy(ttft_p95_ms=50.0), clock=clock, registry=reg,
        tracer=tracer, flight_recorder=recorder,
        fast_window_s=5.0, slow_window_s=20.0, min_samples=3,
    )
    chaos = ChaosRegistry()

    def factory():
        return SlotServingEngine(
            model, params, gcfg, table, slots=2, clock=clock, tracer=tracer,
            rng=jax.random.PRNGKey(3),
        )

    fleet = FleetRouter(
        [factory] * 2, clock=clock, registry=reg, tracer=tracer,
        chaos=chaos, slo_monitor=monitor, flight_recorder=recorder,
        # no redispatch budget: crash victims fail terminally, so their
        # non-ok traces are tail-kept on disk — the join evidence
        redispatch_policy=RetryPolicy(max_retries=0, backoff_base_s=0.0),
    )
    recorder.add_source("health", fleet.health)
    rng = np.random.default_rng(11)

    def prompt():
        return rng.integers(1, cfg.vocab_size, size=max_len).astype(np.int32)

    def drain():
        while fleet.pending():
            fleet.step()
            recorder.maybe_record()
            clock.advance(0.01)
        fleet.step()

    for _ in range(n_requests):  # healthy warm-up: the "before" evidence
        fleet.submit(prompt())
    drain()
    # the incident: the cohort ages past the TTFT target while replica 0's
    # 2nd upcoming supervised step carries a scripted crash (mid-decode)
    steps_so_far = chaos._counters.get("fleet.replica_step.0", 0)
    chaos.crash_replica(0, steps_so_far + 2)
    victims = [fleet.submit(prompt()) for _ in range(n_requests)]
    clock.advance(1.0)
    drain()
    sampler.flush()
    bundle_kinds = sorted(
        os.path.basename(b).split("-", 2)[2] for b in recorder.bundles
    )
    drill_bundles = len(recorder.bundles)
    rows = read_events_jsonl(events_path)
    disk_traces = {r["trace_id"] for r in rows if r.get("trace_id")}
    failed_tids = {r.trace_id for r in victims if r.status == "failed"}
    crash_tids = set()
    for b in recorder.bundles:
        if b.endswith("replica_failure"):
            with open(os.path.join(b, "manifest.json")) as fh:
                crash_tids = set(_json.load(fh)["trigger"]["trace_ids"])
    bad_traces = {
        s.trace_id for s in tracer.finished
        if s.status in TAIL_KEEP_STATUSES and s.trace_id
    }
    final = recorder.trigger("manual", "bench post-drill capture")
    analysis = _json.loads(report_mod.run_incident(final, as_json=True))
    decomp = analysis["decomposition"]
    ttft_max = reg.snapshot()["histograms"]["serving_ttft_ms"]["max"]
    counts = reg.counters()
    return {
        "requests": 2 * n_requests,
        "sample_rate": sample_rate,
        "triggers": int(counts.get("incident_triggers_total", 0)),
        "bundles": drill_bundles,
        "bundle_kinds": bundle_kinds,
        "suppressed": int(counts.get("incident_suppressed_total", 0)),
        "dump_errors": int(counts.get("incident_dump_errors_total", 0)),
        "failed_requests": len(failed_tids),
        "trace_join": bool(crash_tids) and crash_tids == failed_tids
        and crash_tids <= disk_traces,
        "nonok_traces_kept": bool(bad_traces) and bad_traces <= disk_traces,
        "span_accounting_closed": (
            counts.get("tracing_spans_kept_total", 0)
            + counts.get("tracing_spans_sampled_out_total", 0)
            == counts.get("tracing_spans_total", 0)
        ),
        "spans_sampled_out": int(
            counts.get("tracing_spans_sampled_out_total", 0)
        ),
        "decomposition_exact": bool(decomp) and all(
            r["unattributed_ms"] == 0.0
            and round(sum(r["components"].values()), 3) == r["ttft_ms"]
            for r in decomp
        ) and decomp[0]["ttft_ms"] == round(float(ttft_max), 3),
        "worst_request": decomp[0] if decomp else None,
        "timeline_events": len(analysis["timeline"]),
        "bundle_dir": recorder.dir,
    }


def _bench_sharded_serving(*, requests: int = 8, new_tokens: int = 8,
                           slots: int = 4, budget_s: float = 240.0):
    """Sharded-serving A/B (docs/serving.md "Sharded serving"): the
    self-contained probe (``python -m perceiver_io_tpu.serving.sharding``)
    runs twice in child processes — a 1-device single mesh and a
    2 data x 4 model mesh over 8 virtual CPU devices, the device count
    injected per child via ``XLA_FLAGS`` (the same simulation strategy the
    test suite uses) — on identical seeded paged workloads. The record
    A/Bs tokens/s, compile counts, and per-model-shard resident KV bytes,
    and pins ``token_identical``: greedy output must not move when GSPMD
    partitions the executors. ``make shard-bench`` is the one-command
    form; tier-1 pins the same parity in-process (tests/test_sharding.py).
    """
    import json as _json

    repo_root = os.path.dirname(os.path.abspath(__file__))
    base_args = [
        "--slots", str(slots), "--requests", str(requests),
        "--new-tokens", str(new_tokens), "--kv-layout", "paged",
    ]

    def probe(device_count: int, data: int, model_axis: int, timeout: float):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={device_count}"
        ).strip()
        proc = subprocess.run(
            [sys.executable, "-m", "perceiver_io_tpu.serving.sharding",
             "--data", str(data), "--model", str(model_axis), *base_args],
            env=env, cwd=repo_root, stdout=subprocess.PIPE,
            stderr=sys.stderr, text=True, timeout=timeout,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"shard probe ({data}x{model_axis}@{device_count}dev) "
                f"exited rc={proc.returncode}"
            )
        return _json.loads(proc.stdout.strip().splitlines()[-1])

    keep = ("devices", "mesh", "kv_layout", "compile_count",
            "tokens_generated", "tokens_per_s", "wall_s", "resident_bytes",
            "per_shard_resident_bytes")
    single = probe(1, 1, 1, timeout=budget_s / 2)
    sharded = probe(8, 2, 4, timeout=budget_s / 2)
    return {
        "workload": {"requests": requests, "new_tokens": new_tokens,
                     "slots": slots},
        "single": {k: single[k] for k in keep},
        "sharded": {k: sharded[k] for k in keep},
        # tiny CPU shapes are dispatch/collective-bound, so no winner is
        # asserted — the ratio and the per-shard bytes are the record
        "speedup": round(
            sharded["tokens_per_s"] / max(single["tokens_per_s"], 1e-9), 3
        ),
        "token_identical": single["tokens"] == sharded["tokens"],
    }


def _bench_slo_goodput(model, params, cfg, *, requests_per_rate: int = 10,
                       new_tokens: int = 6, slots: int = 4,
                       rate_factors=(0.5, 1.0, 2.0),
                       transport: str = "inproc"):
    """Goodput-under-SLO sweep (docs/observability.md): offered load vs
    p95 TTFT / p95 inter-token latency through the slot engine, driven by
    the open-loop Poisson load generator — the serving-paper measurement
    surface (PAPERS.md [1]) as a bench probe.

    A closed-loop calibration run at full slot concurrency estimates the
    engine's capacity (completed req/s) and the healthy-load latency
    percentiles; the SLO targets are set at 3x those (generous headroom a
    saturated point still blows through). The sweep then offers Poisson
    load at ``rate_factors`` x capacity. Per point: the registry's p95
    TTFT/ITL, completed rate, and **goodput under SLO** — requests/s that
    completed AND met the TTFT target per-request (joined from their
    ``serving.first_token`` events) at a point whose aggregate p95 ITL
    also met target. The knee is the point of max goodput: past it,
    added offered load only grows latency. The probe also cross-checks
    that ``obs report``'s SLO section reproduces the registry's
    nearest-rank percentiles exactly (the acceptance pin).

    All accounting uses the shared offered-load goodput definition
    (``observability/slo.py``) — the same helper the fleet-chaos and
    observability probes use, so the denominators cannot drift.

    ``transport`` is the one-flag in-process/over-sockets switch
    (docs/serving.md "Streaming"): ``"inproc"`` drives the engine
    directly; ``"http"`` runs every point through a real
    :class:`~perceiver_io_tpu.serving.StreamingGateway` socket via
    :class:`~perceiver_io_tpu.observability.GatewayHttpClient`, so the
    sweep's TTFT is socket-anchored and the report gains bytes-on-wire."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.observability import (
        GatewayHttpClient,
        LoadGenerator,
        MetricsRegistry,
        Tracer,
        WorkloadSpec,
        goodput_ratio,
    )
    from perceiver_io_tpu.observability import report as obs_report
    from perceiver_io_tpu.serving import BucketTable, SlotServingEngine, StreamingGateway

    if transport not in ("inproc", "http"):
        raise ValueError(f"transport must be 'inproc' or 'http', got {transport!r}")
    params = cast_float_params(params, jnp.bfloat16)
    num_latents = min(4, cfg.max_latents)
    max_len = min(
        16, cfg.max_seq_len - new_tokens,
        cfg.max_seq_len - cfg.max_latents + num_latents,
    )
    table = BucketTable(prompt_lens=(max_len,), batch_sizes=(1,))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)
    # shared-prefix workload (docs/serving.md "Prefix sharing"): a small
    # pool of fixed system prompts + fresh tails, so the sweep exercises
    # the prefix cache end to end — in-process AND over the HTTP
    # transport. Block size divides the prefix so hot admissions share.
    prefix_tokens = max(num_latents, max_len // 2)
    kv_block = max(2, prefix_tokens // 2)
    workload = WorkloadSpec(
        prompt_len=(2, max_len - prefix_tokens),
        max_new_tokens=(max(2, new_tokens // 2), new_tokens),
        vocab=(1, cfg.vocab_size),
        shared_prefix_pool=3,
        shared_prefix_len=(prefix_tokens, prefix_tokens),
    )

    def run_point(rate_rps, mode, seed):
        registry = MetricsRegistry()
        tracer = Tracer()
        engine = SlotServingEngine(
            model, params, gcfg, table, slots=slots,
            kv_layout="paged", kv_block_size=kv_block, prefix_cache="on",
            registry=registry, tracer=tracer, rng=jax.random.PRNGKey(2),
        )
        gateway = None
        driver = engine
        if transport == "http":
            # the full network path: the gateway drives the engine from
            # its own loop, the load generator offers over real sockets,
            # and TTFT anchors at socket accept (same registry, so the
            # percentile reads below are transport-independent)
            gateway = StreamingGateway(engine, tracer=tracer).run_in_thread()
            driver = GatewayHttpClient(gateway.host, gateway.port)
        gen = LoadGenerator(
            driver, workload=workload, mode=mode, arrival="poisson",
            rate_rps=rate_rps, users=slots, max_requests=requests_per_rate,
            config=gcfg, rng=seed,
        )
        try:
            report = gen.run()
        finally:
            if gateway is not None:
                gateway.close()
        return registry, tracer, gen, report

    # warm every executor once up front — the sweep measures serving, not
    # compiles (caches are process-global, so later engines reuse them)
    SlotServingEngine(
        model, params, gcfg, table, slots=slots,
        kv_layout="paged", kv_block_size=kv_block, prefix_cache="on",
    ).warmup()

    # calibration: closed loop at full slot concurrency = capacity estimate
    reg_c, _, _, rep_c = run_point(1.0, "closed", seed=0)
    base_rps = max(rep_c["completed_rps"], 0.1)
    cal_ttft = reg_c.percentile("serving_ttft_ms", 95.0) or 1.0
    cal_itl = reg_c.percentile("serving_inter_token_ms", 95.0) or 1.0
    slo_ttft_ms = round(3.0 * cal_ttft, 3)
    slo_itl_ms = round(3.0 * cal_itl, 3)

    sweep = []
    report_matches = True
    for factor in rate_factors:
        rate = base_rps * factor
        registry, tracer, gen, rep = run_point(rate, "open", seed=1)
        p95_ttft = registry.percentile("serving_ttft_ms", 95.0)
        p95_itl = registry.percentile("serving_inter_token_ms", 95.0)
        itl_ok = p95_itl is not None and p95_itl <= slo_itl_ms
        ttft_by_trace = {
            sp.trace_id: sp.attrs.get("ttft_ms")
            for sp in tracer.spans("serving.first_token")
        }
        good = sum(
            1 for h in gen.handles
            if h.status == "ok"
            and (ttft_by_trace.get(h.trace_id) or float("inf")) <= slo_ttft_ms
        ) if itl_ok else 0
        # the acceptance pin: obs report's SLO section over this point's
        # own artifacts reproduces the registry's nearest-rank percentiles
        snap = registry.snapshot()
        slo_sec = obs_report.analyze(
            [sp.to_row() for sp in tracer.spans()],
            {"histograms": snap["histograms"], "counters": snap["counters"]},
        )["slo"]
        report_matches = report_matches and (
            slo_sec["ttft"]["p95_ms"] == (
                None if p95_ttft is None else round(p95_ttft, 6)
            )
            and slo_sec["inter_token"]["p95_ms"] == (
                None if p95_itl is None else round(p95_itl, 6)
            )
        )
        sweep.append({
            "rate_factor": factor,
            "offered_rps_target": round(rate, 3),
            "offered_rps": rep["offered_rps"],
            "offered": rep["offered"],
            "completed": rep["completed"],
            "shed": rep["shed"],
            "completed_rps": rep["completed_rps"],
            "p95_ttft_ms": None if p95_ttft is None else round(p95_ttft, 3),
            "p95_inter_token_ms": (
                None if p95_itl is None else round(p95_itl, 3)
            ),
            "slo_met_aggregate": bool(
                itl_ok and p95_ttft is not None and p95_ttft <= slo_ttft_ms
            ),
            "goodput_rps": round(good / rep["span_s"], 4),
            "goodput_ratio": round(goodput_ratio(registry.counters()), 4),
            "bytes_on_wire": rep.get("bytes_on_wire"),
            # shared-prefix workload: sharing is live through this point
            # (in-process or over the HTTP transport alike)
            "prefix_hit_ratio": round(
                registry.counter("kv_prefix_hits_total")
                / max(1, registry.counter("kv_prefix_hits_total")
                      + registry.counter("kv_prefix_misses_total")), 4
            ),
        })
    knee_idx = max(
        range(len(sweep)), key=lambda i: (sweep[i]["goodput_rps"], -i)
    )
    return {
        "slots": slots,
        "requests_per_rate": requests_per_rate,
        "transport": transport,
        "slo": {"ttft_p95_ms": slo_ttft_ms, "inter_token_p95_ms": slo_itl_ms},
        "calibration": {
            "base_rps": round(base_rps, 3),
            "p95_ttft_ms": round(cal_ttft, 3),
            "p95_inter_token_ms": round(cal_itl, 3),
        },
        "sweep": sweep,
        "knee": {
            "index": knee_idx,
            "rate_factor": sweep[knee_idx]["rate_factor"],
            "offered_rps": sweep[knee_idx]["offered_rps"],
            "goodput_rps": sweep[knee_idx]["goodput_rps"],
        },
        "report_percentiles_match_registry": report_matches,
    }


# --------------------------------------------------------------------- main


def main() -> None:
    """One process: place the compile cache, run every stage on the chip,
    print the record as one JSON line. Any failure (no TPU included) is a
    non-zero exit with nothing on stdout."""
    from perceiver_io_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    record = run(FULL_SHAPE, deadline_s=GLOBAL_DEADLINE_S - 30.0)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()

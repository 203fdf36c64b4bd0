"""Cached vs recompute decode throughput as context length grows, with every
generated token pinned into ONE cache phase (``--phase``):

- ``latent`` — latent-growth: the cached step runs O(1) tokens of compute
  per token vs the recompute path's full window (faster on a CPU; on the
  chip: not measured).
- ``boundary`` — prefix-growth: the cache elides the full-window embedding +
  cross-k/v projections (the ``2·n·c²`` matmuls) but recomputes the latent
  stack like the recompute path does (slower than recompute on a CPU at
  256 channels; on the chip: not measured).

Under the static right-aligned window formulation both paths' per-token cost
is a function of the *window* size ``n = max_seq_len`` (left pads are
computed and masked), so the scaling axis is context length, not prompt
length. Runs on the backend ``JAX_PLATFORMS`` selects (bf16 on a TPU, the
default float32 elsewhere) and names it in every point. Prints one JSON line
per point and a markdown table.

Boundary-phase points also feed the decode-strategy registry
(``inference/decode_strategy.py``): each point records the autotuner's
chosen strategy for its shape, the summary reports the cached/recompute
crossing point across context lengths, and ``--emit-strategy PATH`` writes
the same JSON artifact the strategy persistence layer consumes — so a
scaling study doubles as a deployment's warmup measurement.

With ``--speculation`` each boundary point additionally runs the
speculative-decoding autotune probe (docs/serving.md "Speculative
decoding") at its shape: the per-ctx verdict (``off`` or the winning
``k<K>d<D>`` draft geometry), acceptance rate, and per-token timings land
in the point and the registry, and the summary reports the speculation
crossover — the first context length at which drafting stops paying
(verify-lane FLOPs grow with the window; the fixed per-step cost they
amortize does not).

Usage::

    python examples/perf/decode_scaling.py                  # boundary, 1k->8k
    python examples/perf/decode_scaling.py --phase latent   # the cache's win
    python examples/perf/decode_scaling.py --ctxs 1024 2048 # subset
    python examples/perf/decode_scaling.py --emit-strategy strategy.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ctxs", type=int, nargs="+", default=[1024, 2048, 4096, 8192])
    p.add_argument("--num-latents", type=int, default=512)
    p.add_argument("--num-channels", type=int, default=256)
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--new-tokens", type=int, default=8)
    p.add_argument(
        "--phase", choices=["boundary", "latent"], default="boundary",
        help="which cache phase every generated token lands in: 'boundary' "
        "(prefix-growth — latents already maxed; the cache elides only the "
        "full-window embedding + cross-k/v projections) or 'latent' "
        "(latent-growth — the cache runs O(1) tokens of compute per step "
        "vs the recompute path's full window)",
    )
    p.add_argument("--out", default=None, help="also append JSON lines here")
    p.add_argument(
        "--speculation", action="store_true",
        help="also run the speculative-decoding autotune probe per context "
        "length (boundary phase only): records the per-ctx verdict + "
        "acceptance and reports the ctx at which drafting stops paying",
    )
    p.add_argument(
        "--spec-candidates", nargs="+", default=["k4d1", "k8d1"],
        help="draft geometries the per-ctx speculation probe measures",
    )
    p.add_argument(
        "--emit-strategy", default=None,
        help="write the decode-strategy registry JSON artifact here (the "
        "file inference/decode_strategy.py persistence consumes; boundary "
        "phase only)",
    )
    args = p.parse_args()
    if args.phase == "latent" and args.new_tokens >= args.num_latents:
        p.error(
            f"--phase latent pins every generated token into latent growth, "
            f"which requires --new-tokens ({args.new_tokens}) < "
            f"--num-latents ({args.num_latents})"
        )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference import cast_float_params
    from perceiver_io_tpu.inference import decode_strategy as strategy_mod
    from perceiver_io_tpu.inference.generate import GenerationConfig, generate
    from perceiver_io_tpu.models.text.clm import (
        CausalLanguageModel,
        CausalLanguageModelConfig,
    )

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    rows = []
    for ctx in args.ctxs:
        cfg = CausalLanguageModelConfig(
            vocab_size=262,
            max_seq_len=ctx,
            max_latents=args.num_latents,
            num_channels=args.num_channels,
            num_heads=args.num_heads,
            num_self_attention_layers=args.num_layers,
        )
        model = CausalLanguageModel(cfg, dtype=jnp.bfloat16 if on_tpu else None)
        rng = np.random.default_rng(0)
        prefix_len = ctx - args.num_latents
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, ctx), jnp.int32), prefix_len
        )["params"]
        if on_tpu:
            params = cast_float_params(params, jnp.bfloat16)

        # Both phases keep the prompt near the window so the recompute path
        # always pays the full (b, ctx) forward. 'boundary': latents start
        # at max, every token migrates the prefix boundary. 'latent':
        # latents start low enough that all new tokens grow the latent tail
        # — the cached step then runs O(1) tokens of compute vs the
        # recompute path's full window.
        prompt_len = ctx - args.new_tokens
        if args.phase == "boundary":
            start_latents = args.num_latents  # already maxed
        else:
            start_latents = args.num_latents - args.new_tokens
        prompt = jnp.asarray(
            rng.integers(1, cfg.vocab_size, size=(args.batch, prompt_len), dtype=np.int32)
        )
        gcfg = GenerationConfig(
            max_new_tokens=args.new_tokens, num_latents=start_latents
        )

        point = {"ctx": ctx, "phase": args.phase, "platform": platform, "batch": args.batch,
                 "new_tokens": args.new_tokens, "channels": args.num_channels,
                 "layers": args.num_layers, "num_latents": args.num_latents}
        for label, use_cache in (("cached", True), ("recompute", False)):
            ids = generate(model, params, prompt, gcfg, use_cache=use_cache)
            _ = int(np.asarray(jax.device_get(ids))[0, -1])  # warm + fence
            t0 = time.perf_counter()
            ids = generate(model, params, prompt, gcfg, use_cache=use_cache)
            _ = int(np.asarray(jax.device_get(ids))[0, -1])
            dt = time.perf_counter() - t0
            point[f"{label}_tokens_per_sec"] = round(
                args.batch * args.new_tokens / dt, 2)
            point[f"{label}_ms_per_token"] = round(dt / args.new_tokens * 1e3, 2)
        point["speedup"] = round(
            point["cached_tokens_per_sec"] / point["recompute_tokens_per_sec"], 2
        )
        if args.phase == "boundary":
            # record this shape's verdict in the decode-strategy registry —
            # the measurement the warmup autotuner would repeat, reusing the
            # timings just taken instead of re-running the probe
            chosen = (
                "cached"
                if point["cached_ms_per_token"] <= point["recompute_ms_per_token"]
                else "recompute"
            )
            strategy_mod.record(
                model, chosen,
                cached_ms_per_token=point["cached_ms_per_token"],
                recompute_ms_per_token=point["recompute_ms_per_token"],
                batch=args.batch, new_tokens=args.new_tokens,
                source="decode_scaling",
            )
            point["chosen_strategy"] = chosen
            point["cached_over_recompute"] = point["speedup"]
            if args.speculation:
                # the same measure-once discipline for the speculation
                # knob: the probe A/Bs each draft geometry against the
                # plain one-token step at THIS shape and memoizes the
                # verdict (off = drafting doesn't pay here)
                verdict = strategy_mod.autotune_speculation(
                    model, params,
                    candidates=tuple(args.spec_candidates), force=True,
                )
                entry = strategy_mod.spec_entry(model) or {}
                point["speculation"] = verdict
                point["speculation_acceptance"] = entry.get(
                    "acceptance", {}).get(verdict)
                point["speculation_ms_per_token"] = entry.get(
                    "timings_ms_per_token", {})
        rows.append(point)
        print(json.dumps(point), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(point) + "\n")
    if args.emit_strategy and args.phase == "boundary":
        strategy_mod.save_registry(args.emit_strategy)
        print(f"wrote decode-strategy artifact: {args.emit_strategy}",
              file=sys.stderr)

    if args.phase == "boundary":
        spec_col = " speculation |" if args.speculation else ""
        print("\n| ctx | cached tok/s | recompute tok/s | cached ms/tok | recompute ms/tok | speedup | chosen |" + spec_col)
        print("|---|---|---|---|---|---|---|" + ("---|" if args.speculation else ""))
        for r in rows:
            extra = f" {r['speculation']} |" if args.speculation else ""
            print(f"| {r['ctx']} | {r['cached_tokens_per_sec']} | "
                  f"{r['recompute_tokens_per_sec']} | {r['cached_ms_per_token']} | "
                  f"{r['recompute_ms_per_token']} | {r['speedup']}x | "
                  f"{r['chosen_strategy']} |" + extra)
        # the cached/recompute crossing point: the first context length at
        # which the cached boundary step wins (None = recompute everywhere)
        crossover = next(
            (r["ctx"] for r in rows if r["chosen_strategy"] == "cached"), None
        )
        summary = {
            "crossover_ctx": crossover,
            "chosen_by_ctx": {str(r["ctx"]): r["chosen_strategy"] for r in rows},
        }
        if args.speculation:
            # the speculation crossover runs the OTHER way: drafting pays
            # at small windows (per-step cost amortized over the burst)
            # and stops once verify-lane FLOPs dominate
            summary["speculation_by_ctx"] = {
                str(r["ctx"]): r["speculation"] for r in rows
            }
            summary["speculation_stops_paying_ctx"] = next(
                (r["ctx"] for r in rows if r["speculation"] == "off"), None
            )
        print(json.dumps(summary))
    else:
        print("\n| ctx | cached tok/s | recompute tok/s | cached ms/tok | recompute ms/tok | speedup |")
        print("|---|---|---|---|---|---|")
        for r in rows:
            print(f"| {r['ctx']} | {r['cached_tokens_per_sec']} | "
                  f"{r['recompute_tokens_per_sec']} | {r['cached_ms_per_token']} | "
                  f"{r['recompute_ms_per_token']} | {r['speedup']}x |")


if __name__ == "__main__":
    main()

"""Config-driven training CLI — the reference's LightningCLI surface
(``perceiver/scripts/cli.py:13-48``) without Lightning/jsonargparse:

    python -m perceiver_io_tpu.scripts.text.clm fit \
        --data=wikitext --data.max_seq_len=4096 \
        --model.num_latents=512 --optimizer.lr=2e-4 \
        --trainer.max_steps=10000 --trainer.default_root_dir=logs

Flags are generated from dataclass fields (``--model.*`` from the family's
model config, ``--data.*`` from the datamodule constructor, ``--trainer.*``
from :class:`~perceiver_io_tpu.training.trainer.TrainerConfig`, plus
``--optimizer.*`` / ``--lr_scheduler.*``). ``--config file.yaml`` loads
defaults (CLI flags win), mirroring the reference's ``trainer.yaml`` default
config file; ``link`` functions propagate data-derived values into the model
config (``link_arguments`` parity, e.g. vocab_size — reference
``scripts/text/mlm.py:12-16``). Subcommands: ``fit``, ``validate``,
``test``, ``preproc`` (the reference LightningCLI exposes
fit/validate/test, ``perceiver/scripts/cli.py:13-48``); ``validate`` and
``test`` take ``--ckpt <dir>`` to evaluate a saved model; ``serve`` takes
``--ckpt <dir>`` plus ``--serve.*`` flags and runs bucketed text
generation through the serving engine (docs/serving.md) — prompts from a
file or stdin, one JSON completion line each, engine stats at the end.

Model-family entry points are declarative :class:`ModelFamily` records; see
``perceiver_io_tpu/scripts/text/clm.py`` for the pattern.
"""
from __future__ import annotations

import argparse
import dataclasses
import enum
import sys
import typing
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import numpy as np


# -- dataclass <-> flags ---------------------------------------------------
def _unwrap_optional(tp):
    origin = typing.get_origin(tp)
    if origin is typing.Union:
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0], True
    return tp, False


def _parse_value(text: str, tp) -> Any:
    tp, optional = _unwrap_optional(tp)
    if optional and text.lower() in ("none", "null"):
        return None
    origin = typing.get_origin(tp)
    if tp is bool:
        if text.lower() in ("true", "1", "yes"):
            return True
        if text.lower() in ("false", "0", "no"):
            return False
        raise ValueError(f"invalid bool {text!r}")
    if tp in (int, float, str):
        return tp(text)
    if isinstance(tp, type) and issubclass(tp, enum.Enum):
        return tp[text]
    if origin in (tuple, list):
        elem = (typing.get_args(tp) or (str,))[0]
        if elem is Ellipsis:
            elem = str
        items = [t for t in text.replace("(", "").replace(")", "").split(",") if t != ""]
        seq = [_parse_value(t.strip(), elem) for t in items]
        return tuple(seq) if origin is tuple else seq
    # fall back to python literal-ish string
    return text


def _coerce(value: Any, tp) -> Any:
    """Coerce a YAML-loaded value to the field type."""
    if isinstance(value, str):
        return _parse_value(value, tp)
    tp2, _ = _unwrap_optional(tp)
    if value is not None and typing.get_origin(tp2) is tuple:
        elem = (typing.get_args(tp2) or (str,))[0]
        return tuple(value)
    if value is not None and isinstance(tp2, type) and issubclass(tp2, enum.Enum) and not isinstance(value, tp2):
        return tp2[value]
    return value


def flag_specs(cls, prefix: str, nested: Optional[Dict[str, type]] = None) -> Dict[str, Any]:
    """``{dotted_flag: type}`` for a dataclass, recursing into nested
    dataclass fields (``nested`` overrides TypeVar-typed fields with
    concrete classes — PerceiverIOConfig is Generic[E, D])."""
    nested = nested or {}
    specs: Dict[str, Any] = {}
    cls = typing.get_origin(cls) or cls  # unwrap PerceiverIOConfig[E, D]
    hints = typing.get_type_hints(cls)
    for field in dataclasses.fields(cls):
        tp = nested.get(field.name, hints.get(field.name, str))
        if dataclasses.is_dataclass(tp):
            specs.update(flag_specs(tp, f"{prefix}.{field.name}"))
        else:
            specs[f"{prefix}.{field.name}"] = tp
    return specs


def build_dataclass(cls, values: Dict[str, Any], prefix: str,
                    nested: Optional[Dict[str, type]] = None):
    """Instantiate ``cls`` from dotted ``values``."""
    nested = nested or {}
    cls = typing.get_origin(cls) or cls  # unwrap PerceiverIOConfig[E, D]
    hints = typing.get_type_hints(cls)
    kwargs = {}
    for field in dataclasses.fields(cls):
        tp = nested.get(field.name, hints.get(field.name, str))
        key = f"{prefix}.{field.name}"
        if dataclasses.is_dataclass(tp):
            sub_keys = [k for k in values if k.startswith(key + ".")]
            if sub_keys or not _has_default(field):
                kwargs[field.name] = build_dataclass(tp, values, key)
        elif key in values:
            kwargs[field.name] = _coerce(values[key], tp)
    return cls(**kwargs)


def _has_default(field) -> bool:
    return (
        field.default is not dataclasses.MISSING
        or field.default_factory is not dataclasses.MISSING
    )


# -- optimizer / scheduler args -------------------------------------------
@dataclasses.dataclass
class OptimizerArgs:
    """``--optimizer.*`` (reference exposes these via Lightning's optimizer
    wiring, ``scripts/cli.py:37-48``)."""

    lr: float = 1e-3
    optimizer: str = "adamw"
    weight_decay: float = 0.0
    b1: float = 0.9
    b2: float = 0.999


@dataclasses.dataclass
class LRSchedulerArgs:
    """``--lr_scheduler.*`` (reference ``perceiver/scripts/lrs.py:7-38``)."""

    name: str = "cosine"  # cosine | constant | none
    warmup_steps: int = 0
    min_fraction: float = 0.1
    training_steps: Optional[int] = None  # linked to trainer.max_steps


@dataclasses.dataclass
class HttpArgs:
    """``--serve.http.*``: the async HTTP/SSE streaming gateway
    (docs/serving.md "Streaming"). Setting ``--serve.http.port`` switches
    ``serve`` from the prompts-file/stdin batch loop to a network server:
    ``POST /v1/generate`` streams each token as it decodes, ``GET
    /healthz`` is the load-balancer probe, ``GET /metrics`` the Prometheus
    scrape. Client disconnects cancel the request mid-generation (slot +
    KV pool pages freed); TTFT is anchored at socket accept."""

    #: bind port; set it to enable gateway mode (0 = ephemeral, printed to
    #: stderr). None (default) keeps the batch prompts loop.
    port: Optional[int] = None
    host: str = "127.0.0.1"
    #: default wire framing: ``sse`` (Server-Sent Events) or ``jsonl``
    #: (one JSON object per line); per-request override via the body's
    #: ``"stream"`` field
    stream: str = "sse"
    #: shut the gateway down after this many streams reach a terminal
    #: state (scripted runs / tests); None = serve until interrupted
    max_streams: Optional[int] = None


@dataclasses.dataclass
class MeshServeArgs:
    """``--serve.mesh.*``: the sharded serving runtime (docs/serving.md
    "Sharded serving"). Passing any ``--serve.mesh.*`` flag compiles the
    slot engine's executors over a ``data`` × ``model`` device mesh:
    slots/batch shard along ``data`` (``--serve.slots`` must divide
    evenly), attention heads and KV caches — dense per-slot AND the paged
    pool — along ``model`` (the model's head count must divide evenly),
    params get the Megatron TP placement. With ``--serve.replicas=N``
    each replica claims the next disjoint ``data×model`` device group, so
    the fleet scales as N replicas × M-device replicas. Greedy output
    stays token-identical to the unsharded engine; a 1×1 mesh reproduces
    it exactly."""

    #: slot/batch-parallel axis size
    data: int = 1
    #: tensor-parallel axis size (attention heads, KV caches)
    model: int = 1
    #: index of the first claimed device — replica i of a fleet starts at
    #: ``device_offset + i * data * model``
    device_offset: int = 0


@dataclasses.dataclass
class AutoscaleArgs:
    """``--serve.autoscale.*``: SLO-driven fleet elasticity
    (docs/serving.md "Elasticity"). Setting ``--serve.autoscale.max``
    attaches a :class:`~perceiver_io_tpu.serving.FleetAutoscaler` to the
    fleet router (built even at ``--serve.replicas=1``): sustained SLO burn
    (``--obs.slo.*``) or queue pressure scales replicas up to ``max``
    through the degradation ladder; recovery scales back down to ``min``
    with zero dropped in-flight requests (exactly-once failover replay,
    pool pages returned tagged ``scale_down``). Off unless ``max`` set."""

    #: replica ceiling; setting it enables the autoscaler
    max: Optional[int] = None
    #: replica floor — scale-down never goes below it
    min: int = 1
    #: hysteresis: per-direction cooldowns (seconds, on the fleet clock);
    #: the down cooldown gates on the last scale action in EITHER direction
    up_cooldown_s: float = 15.0
    down_cooldown_s: float = 60.0
    #: consecutive control-loop polls of fresh evidence before acting
    up_evidence: int = 2
    down_evidence: int = 5
    #: queue-depth watermarks as multiples of total healthy slot capacity:
    #: depth above high x capacity is a scale-up trigger (even without SLO
    #: targets); depth must fall below low x capacity to count as
    #: scale-down evidence
    queue_high: float = 1.0
    queue_low: float = 0.25
    #: slot count for replicas spawned on the scale-up path (slots engine
    #: only) — applied via the warm-cache resize_slots rebuild before the
    #: replica takes traffic
    scale_up_slots: Optional[int] = None


@dataclasses.dataclass
class ServeArgs:
    """``--serve.*`` flags for the ``serve`` subcommand: bucketed text
    generation over a ``save_pretrained`` checkpoint (docs/serving.md)."""

    #: prompts file, one per line; omitted = read prompts from stdin
    prompts: Optional[str] = None
    max_new_tokens: int = 64
    num_latents: int = 1
    temperature: float = 0.0  # greedy by default — deterministic serving
    #: scheduler: ``bucket`` packs whole micro-batches per compiled
    #: generation; ``slots`` is token-granular continuous batching over a
    #: persistent multi-slot decode state (docs/serving.md — prefer it for
    #: mixed traffic; it requires prompt_len + max_new_tokens <= context)
    engine: str = "bucket"
    #: persistent decode slots for ``--serve.engine=slots``
    slots: int = 8
    #: chunked prefill for the slot engine: split long-prompt admission into
    #: fixed-size chunks interleaved with resident decode steps (None = off;
    #: docs/serving.md)
    prefill_chunk: Optional[int] = None
    #: boundary-phase decode strategy: ``auto`` measures cached-vs-recompute
    #: at warmup and memoizes the winner (inference/decode_strategy.py);
    #: ``cached``/``recompute`` pin it (and beat PERCEIVER_DECODE_STRATEGY,
    #: which ``auto`` defers to). Exact either way — greedy output is
    #: token-identical across settings.
    decode_strategy: str = "auto"
    #: optional JSON path persisting the autotuner's verdicts, so one
    #: deployment measures once (also via PERCEIVER_DECODE_STRATEGY_FILE)
    decode_strategy_file: Optional[str] = None
    #: slot-engine cross-KV layout (docs/serving.md "Block-paged KV"):
    #: ``dense`` = per-slot worst-case caches; ``paged`` = shared block
    #: pool + per-slot block tables (more residents per HBM byte under
    #: long-tail traffic; greedy output identical); ``paged_int8`` = the
    #: paged pool quantized to int8 with per-(position, head) f32 dequant
    #: scales (docs/serving.md "Quantized KV" — ~3-4x residents per HBM
    #: byte; approximate: bounded greedy logit drift, gated by the
    #: autotuner's quality probe); ``auto`` measures at warmup and
    #: memoizes the winner (beaten by an explicit layout, defers to
    #: PERCEIVER_KV_LAYOUT)
    kv_layout: str = "auto"
    #: token positions per KV pool block (paged layout; default
    #: min(16, context))
    kv_block_size: Optional[int] = None
    #: usable KV pool capacity in blocks (paged layout). Default = dense
    #: capacity (slots x pages-per-slot); set it LOWER to serve the same
    #: slot count in less HBM — requests that can't currently fit wait at
    #: the queue head, ones that never could reject at submit. Sizing the
    #: pool requires a paged --serve.kv_layout (a dense resolution would
    #: silently discard the budget, so the engine rejects the combination)
    kv_blocks: Optional[int] = None
    #: cross-request prefix sharing for the paged slot engine
    #: (docs/serving.md "Prefix sharing"): ``on`` maps hot prompt-prefix
    #: blocks by reference with copy-on-write instead of re-projecting
    #: them (greedy output identical; TTFT for a hot system prompt
    #: collapses to the suffix projection); ``auto`` defers to
    #: PERCEIVER_PREFIX_CACHE then the measured registry (off when
    #: unrecorded). ``on`` requires --serve.kv_layout=paged.
    prefix_cache: str = "auto"
    #: self-draft speculative decoding for the slot engine (docs/serving.md
    #: "Speculative decoding"): ``k<K>d<D>`` drafts K candidate tokens per
    #: step with a D-layer truncated latent stack (same checkpoint, no
    #: second model) and verifies all K+1 positions in ONE batched forward
    #: — greedy output stays token-identical to ``off``; throughput
    #: improves when acceptance is high enough that multi-token steps beat
    #: one-token steps. ``auto`` defers to PERCEIVER_SPECULATION, then
    #: measures acceptance x per-step cost at warmup and memoizes the
    #: verdict (falls back to ``off`` when drafting doesn't pay).
    #: Greedy-only: sampling/beams/repetition-penalty reject loudly.
    speculation: str = "auto"
    #: preemption mode for the paged slot engine (docs/serving.md
    #: "Preemption & priorities"): ``recompute`` switches admission to
    #: optimistic lazy paging — requests admit when their PROMPT pages
    #: (plus --serve.admit_headroom_blocks) fit rather than reserving the
    #: worst case up front, and on genuine pool exhaustion the engine
    #: preempts the lowest-priority victim (pages returned, request
    #: requeued, greedy replay token-identical). ``swap`` ships the
    #: victim's mapped KV pages (plus int8 scales) to host memory instead
    #: of discarding them, and restores them into whatever free blocks
    #: exist at readmission — the victim pays transfer instead of
    #: recompute, the win once generated >> prompt. ``auto`` decides
    #: per victim from the live recompute-vs-swap post-mortem model.
    #: ``off`` (default) keeps strict worst-case reservations. Requires a
    #: paged --serve.kv_layout.
    preemption: Optional[str] = None
    #: decode headroom blocks granted beyond the prompt at lazy admission
    #: (--serve.preemption only): higher = fewer early preemptions, lower
    #: = more residents per HBM byte. Default 0.
    admit_headroom_blocks: int = 0
    #: host-swap link prior in GB/s (--serve.preemption=swap|auto only):
    #: seeds the per-victim swap-vs-recompute cost model before the first
    #: measured transfer calibrates it. Unset = the per-platform calibrated
    #: value persisted in --serve.decode_strategy_file, else 16.0.
    swap_gbps: Optional[float] = None
    #: prompt-length bucket grid; default = powers of two up to the context
    prompt_buckets: Optional[typing.Tuple[int, ...]] = None
    #: micro-batch size grid (``bucket`` engine; ignored by ``slots``)
    batch_buckets: typing.Tuple[int, ...] = (1, 2, 4, 8)
    #: compile every bucket before accepting traffic
    warmup: bool = True
    seed: int = 0
    #: append the engine stats JSON line to stdout after the results
    stats: bool = True
    #: bounded queue depth — submissions past it backpressure (the CLI then
    #: drains a micro-batch and resubmits); None = unbounded. With
    #: ``replicas > 1`` this bounds the FLEET (queued + dispatched), not
    #: each engine — admission is lifted to the router.
    max_queue: Optional[int] = None
    #: per-request deadline in seconds; requests that wait longer complete
    #: with a ``timed_out`` record instead of occupying a bucket slot
    deadline_s: Optional[float] = None
    #: engine replicas behind a supervised FleetRouter (docs/serving.md):
    #: load-aware dispatch, per-replica circuit breakers, failover with
    #: exactly-once replay. 1 (default) drives the engine directly — no
    #: fleet layer, no semantic drift.
    replicas: int = 1
    #: with ``replicas > 1``: re-dispatch a failed replica's in-flight
    #: requests to survivors, replayed from their prompts (greedy outputs
    #: stay token-identical). false = a replica failure fails its
    #: in-flight requests terminally.
    failover: bool = True
    #: with ``replicas > 1``: wall-time deadline on one supervised replica
    #: step — a slower (but returning) step marks the replica hung and
    #: fails over its work. None (default) disables hang detection: set it
    #: comfortably above your worst expected step (a cold compile inside
    #: the first unwarmed step would otherwise trip it). A step that never
    #: RETURNS is out of scope for the in-line supervisor — see
    #: docs/serving.md.
    step_timeout_s: Optional[float] = None
    #: the ``--serve.http.*`` sub-group: the async HTTP/SSE streaming
    #: gateway (docs/serving.md "Streaming"); off unless ``http.port`` set
    http: HttpArgs = dataclasses.field(default_factory=HttpArgs)
    #: the ``--serve.autoscale.*`` sub-group: SLO-driven fleet elasticity
    #: (docs/serving.md "Elasticity"); off unless ``autoscale.max`` set
    autoscale: AutoscaleArgs = dataclasses.field(default_factory=AutoscaleArgs)
    #: the ``--serve.mesh.*`` sub-group: sharded serving over the
    #: parallelism mesh (docs/serving.md "Sharded serving"); off unless a
    #: mesh flag is passed (slots engine only)
    mesh: MeshServeArgs = dataclasses.field(default_factory=MeshServeArgs)


def _serve_decode_mode(flag_value: str) -> str:
    """Resolve ``--serve.decode_strategy`` against the process-wide env
    override (docs/serving.md). The flag's ``"auto"`` default must not mask
    ``PERCEIVER_DECODE_STRATEGY`` — ``resolve()`` only consults the env when
    handed ``None``, and the engine always receives an explicit mode so
    warmup knows whether to autotune — so ``auto`` defers to the env var
    while a pinned ``cached``/``recompute`` flag beats it."""
    import os

    from perceiver_io_tpu.inference import decode_strategy as strategy_mod

    if flag_value not in strategy_mod.MODES:
        raise SystemExit(
            "--serve.decode_strategy must be one of "
            f"{'|'.join(strategy_mod.MODES)}, got {flag_value!r}"
        )
    if flag_value != "auto":
        return flag_value
    env_mode = os.environ.get(strategy_mod.ENV_VAR)
    if not env_mode:
        return flag_value
    if env_mode not in strategy_mod.MODES:
        raise SystemExit(
            f"{strategy_mod.ENV_VAR} must be one of "
            f"{'|'.join(strategy_mod.MODES)}, got {env_mode!r}"
        )
    return env_mode


def _serve_kv_layout(flag_value: str) -> str:
    """Resolve ``--serve.kv_layout`` against ``PERCEIVER_KV_LAYOUT`` — the
    same deference rules as :func:`_serve_decode_mode`: an explicit
    ``dense``/``paged`` flag beats the env var; the ``auto`` default
    defers to it (then to the measured registry at engine construction)."""
    import os

    from perceiver_io_tpu.inference import decode_strategy as strategy_mod

    if flag_value not in strategy_mod.KV_LAYOUTS:
        raise SystemExit(
            "--serve.kv_layout must be one of "
            f"{'|'.join(strategy_mod.KV_LAYOUTS)}, got {flag_value!r}"
        )
    if flag_value != "auto":
        return flag_value
    env_mode = os.environ.get(strategy_mod.ENV_KV_LAYOUT)
    if not env_mode:
        return flag_value
    if env_mode not in strategy_mod.KV_LAYOUTS:
        raise SystemExit(
            f"{strategy_mod.ENV_KV_LAYOUT} must be one of "
            f"{'|'.join(strategy_mod.KV_LAYOUTS)}, got {env_mode!r}"
        )
    return env_mode


def _serve_prefix_cache(flag_value: str) -> str:
    """Resolve ``--serve.prefix_cache`` against ``PERCEIVER_PREFIX_CACHE``
    — the same deference rules as :func:`_serve_kv_layout`: an explicit
    ``on``/``off`` flag beats the env var; the ``auto`` default defers to
    it (then to the measured registry at engine construction)."""
    import os

    from perceiver_io_tpu.inference import decode_strategy as strategy_mod

    if flag_value not in strategy_mod.PREFIX_CACHE_MODES:
        raise SystemExit(
            "--serve.prefix_cache must be one of "
            f"{'|'.join(strategy_mod.PREFIX_CACHE_MODES)}, got {flag_value!r}"
        )
    if flag_value != "auto":
        return flag_value
    env_mode = os.environ.get(strategy_mod.ENV_PREFIX_CACHE)
    if not env_mode:
        return flag_value
    if env_mode not in strategy_mod.PREFIX_CACHE_MODES:
        raise SystemExit(
            f"{strategy_mod.ENV_PREFIX_CACHE} must be one of "
            f"{'|'.join(strategy_mod.PREFIX_CACHE_MODES)}, got {env_mode!r}"
        )
    return env_mode


def _serve_speculation(flag_value: str) -> str:
    """Resolve ``--serve.speculation`` against ``PERCEIVER_SPECULATION`` —
    the same deference rules as :func:`_serve_kv_layout`: an explicit
    ``off``/``k<K>d<D>`` flag beats the env var; the ``auto`` default
    defers to it (then to the measured registry at engine construction,
    with an acceptance-probe autotune at warmup when unrecorded)."""
    import os

    from perceiver_io_tpu.inference import decode_strategy as strategy_mod

    if flag_value not in strategy_mod.SPECULATION_MODES:
        raise SystemExit(
            "--serve.speculation must be one of "
            f"{'|'.join(strategy_mod.SPECULATION_MODES)}, got {flag_value!r}"
        )
    if flag_value != "auto":
        return flag_value
    env_mode = os.environ.get(strategy_mod.ENV_SPECULATION)
    if not env_mode:
        return flag_value
    if env_mode not in strategy_mod.SPECULATION_MODES:
        raise SystemExit(
            f"{strategy_mod.ENV_SPECULATION} must be one of "
            f"{'|'.join(strategy_mod.SPECULATION_MODES)}, got {env_mode!r}"
        )
    return env_mode


def _obs_kit(obs, root: str, *, is_main: bool = True,
             passed: Optional[set] = None) -> Dict[str, Any]:
    """Materialize the ``--obs.*`` flag group (docs/observability.md) into
    registry / tracer / snapshot-writer / profiler-trigger objects. Every
    field defaults to off; the events sink, snapshot writer, and profiler
    trigger are all rank-0 only (non-main processes would race the same
    files under a shared root dir). Returns ``{"registry", "tracer",
    "sink", "snapshot_writer", "trigger"}`` — callers must ``close()`` the
    sink when done."""
    import os

    from perceiver_io_tpu.observability import (
        JsonlSpanSink,
        MetricsRegistry,
        ProfilerTrigger,
        SamplingSpanSink,
        SnapshotWriter,
        Tracer,
    )

    def _resolve(path: str) -> str:
        if not os.path.isabs(path):
            path = os.path.join(root, path)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        return path

    # inapplicable-flag convention: the sampling / rotation knobs shape the
    # events.jsonl stream, so asking for them without a stream must not
    # silently do nothing
    if obs.events_path is None:
        for flag, value in (
            ("--obs.trace_sample", obs.trace_sample),
            ("--obs.trace_keep_slow_ms", obs.trace_keep_slow_ms),
            ("--obs.events_max_bytes", obs.events_max_bytes),
        ):
            if value is not None:
                raise SystemExit(
                    f"{flag} shapes the span stream; set --obs.events_path "
                    "to enable it (docs/observability.md)"
                )
    if obs.trace_sample is not None and not 0.0 < obs.trace_sample <= 1.0:
        raise SystemExit(
            f"--obs.trace_sample must be in (0, 1], got {obs.trace_sample}"
        )
    if obs.trace_keep_slow_ms is not None and obs.trace_sample is None:
        raise SystemExit(
            "--obs.trace_keep_slow_ms is a trace-sampling tail-keep rule; "
            "set --obs.trace_sample to enable sampling"
        )
    registry = MetricsRegistry()
    sink = None
    tracer = None
    if obs.events_path is not None and is_main:
        import time

        sink = JsonlSpanSink(
            _resolve(obs.events_path), max_bytes=obs.events_max_bytes
        )
        if obs.trace_sample is not None:
            # deterministic head sampling + tail-keep between tracer and
            # disk (docs/observability.md "Trace sampling"); kit["sink"]
            # is the OUTER sink so close() flushes undecided traces first
            sink = SamplingSpanSink(
                sink, rate=obs.trace_sample,
                keep_slow_ms=obs.trace_keep_slow_ms, registry=registry,
            )
        # per-run ID prefix: the sink appends, and a restarted process would
        # otherwise re-issue t000001... — colliding with the previous run's
        # spans in the same file and breaking the trace-ID join
        tracer = Tracer(
            sink=sink, prefix=f"{os.getpid():x}.{int(time.time()) & 0xFFFFFF:x}."
        )
    snapshot_writer = None
    if (obs.snapshot_every_s is not None or obs.snapshot_path is not None) and is_main:
        from perceiver_io_tpu.observability import default_ledger, default_registry

        snapshot_writer = SnapshotWriter(
            registry,
            _resolve(obs.snapshot_path or "metrics_snapshot.json"),
            every_s=obs.snapshot_every_s,
            # every written snapshot embeds the device-cost ledger table
            # (per-executor compile/memory costs for an offline `obs
            # report`) AND the process-wide registry, where the ledger's
            # counter families, the executor-cache counters, and the
            # hbm/resident gauges live — the run-scoped registry alone
            # would silently drop them
            extra=lambda: {
                "compile_ledger": default_ledger().snapshot(),
                "process_metrics": default_registry().snapshot(),
            },
        )
    slo_monitor = None
    if obs.slo.enabled and is_main:
        from perceiver_io_tpu.observability import SLOMonitor

        # SLO targets (docs/observability.md): burn-rate gauges/counters on
        # the kit registry (single-engine serving shares it; a fleet keeps
        # its fleet_* families there too), breach events on the kit tracer
        # when events are on, and breach -> profiler-trigger arming when a
        # trigger exists. run_serve wires the latency/disposition feeds.
        slo_monitor = SLOMonitor(
            obs.slo.policy(),
            registry=registry,
            tracer=None,  # run_serve swaps in its tracer (always built there)
            fast_window_s=obs.slo.fast_window_s,
            slow_window_s=obs.slo.slow_window_s,
            breach_burn_rate=obs.slo.burn_rate,
        )
    flight_recorder = None
    if obs.incident.dir is not None:
        if is_main:
            from perceiver_io_tpu.observability import FlightRecorder

            # the incident flight recorder (docs/observability.md "Flight
            # recorder & incident bundles"): bundle dir resolved like the
            # other --obs paths; the tracer is attached here when events
            # are on and re-attached by run_serve (which always builds one)
            incident_dir = obs.incident.dir
            if not os.path.isabs(incident_dir):
                incident_dir = os.path.join(root, incident_dir)
            flight_recorder = FlightRecorder(
                incident_dir,
                tracer=tracer,
                registry=registry,
                cooldown_s=obs.incident.cooldown_s,
                max_bundles=obs.incident.max_bundles,
                keep_spans=obs.incident.keep_spans,
            )
    elif obs.incident != type(obs.incident)() or any(
        k.startswith("obs.incident.") for k in (passed or ())
    ):
        # inapplicable-flag convention: tuning a recorder that was never
        # enabled must not silently do nothing (`passed` catches a flag
        # explicitly set to its default, which the dataclass compare misses)
        raise SystemExit(
            "--obs.incident.* tunes the incident flight recorder, which is "
            "enabled by setting --obs.incident.dir (docs/observability.md)"
        )
    timeline = None
    timeline_export = None
    if obs.timeline.enabled:
        if obs.timeline.swap_gbps <= 0:
            raise SystemExit(
                f"--obs.timeline.swap_gbps must be > 0, got "
                f"{obs.timeline.swap_gbps}"
            )
        if is_main:
            from perceiver_io_tpu.observability import StepTimeline

            # the scheduler step timeline (docs/observability.md "Scheduler
            # timeline & post-mortems"): run_serve attaches this ring to
            # every engine it builds; the export lands at serve end
            timeline = StepTimeline(cap=obs.timeline.steps, registry=registry)
            if obs.timeline.export is not None:
                timeline_export = _resolve(obs.timeline.export)
    elif obs.timeline != type(obs.timeline)() or any(
        k.startswith("obs.timeline.") for k in (passed or ())
    ):
        # inapplicable-flag convention, same as --obs.incident.*
        raise SystemExit(
            "--obs.timeline.* tunes the scheduler step timeline, which is "
            "enabled by setting --obs.timeline.steps (docs/observability.md)"
        )
    trigger = None
    if obs.profile_on_regress_factor is not None and is_main:
        if jax.process_count() > 1:
            # an armed trigger flips process 0 to single-step scheduling
            # while other processes stay fused — desynchronized collectives
            # hang the SPMD run. Restricted until arming is rank-broadcast.
            print(
                "[obs] profile_on_regress_factor is single-process only; "
                "disabled for this multi-host run",
                file=sys.stderr, flush=True,
            )
        else:
            trigger = ProfilerTrigger(
                os.path.join(root, "profile_regress"),
                factor=obs.profile_on_regress_factor,
            )
    if slo_monitor is not None:
        slo_monitor.profiler_trigger = trigger
        # an SLO breach dumps an incident bundle, same stance as arming
        # the profiler trigger (docs/observability.md)
        slo_monitor.flight_recorder = flight_recorder
    return {
        "registry": registry,
        "tracer": tracer,
        "sink": sink,
        "snapshot_writer": snapshot_writer,
        "trigger": trigger,
        "slo_monitor": slo_monitor,
        "flight_recorder": flight_recorder,
        "timeline": timeline,
        "timeline_export": timeline_export,
    }


# -- the CLI ---------------------------------------------------------------
@dataclasses.dataclass
class ModelFamily:
    """Declarative description of one trainable model family.

    :param build_model: ``(model_cfg, data_module) -> flax module``
    :param make_loss: ``(model, model_cfg) -> loss_fn`` for the train step.
    :param init_args: ``(model_cfg, batch) -> (args, kwargs)`` used for
        ``model.init`` on the first host batch.
    :param link: ``(data_module, values dict) -> None`` — mutate dotted model
        values from data properties before the model config is built
        (``link_arguments`` parity).
    :param initial_params: optional ``(model, model_cfg, data_module) ->
        params`` warm-start hook (e.g. encoder from MLM checkpoint).
    """

    name: str
    config_class: type
    data_registry: Dict[str, Callable]
    build_model: Callable
    make_loss: Callable
    init_args: Callable
    nested: Optional[Dict[str, type]] = None
    link: Optional[Callable] = None
    defaults: Optional[Dict[str, Any]] = None
    initial_params: Optional[Callable] = None
    frozen_prefixes: Optional[Callable] = None  # (model_cfg) -> tuple of paths


def _announce_device(subcommand: str) -> None:
    """Name the backend on stderr before any model work, so that a run's log
    says what it ran on."""
    device = jax.devices()[0]
    print(
        f"[{subcommand}] device: platform={device.platform} "
        f"kind={device.device_kind} count={jax.device_count()}",
        file=sys.stderr, flush=True,
    )


def _wants_help(argv: Sequence[str]) -> bool:
    """True when a standalone ``-h``/``--help`` appears. Tokens consumed as
    the *value* of a space-separated flag don't count: ``--data.text --help``
    is a (strange) value, not a help request."""
    expecting_value = False
    for tok in argv:
        if expecting_value:
            expecting_value = False
            continue
        if tok in ("-h", "--help"):
            return True
        if tok.startswith("--") and "=" not in tok:
            expecting_value = True
    return False


def _parse_dotted(argv: Sequence[str], known: Dict[str, Any]) -> Dict[str, Any]:
    values: Dict[str, Any] = {}
    i = 0
    argv = list(argv)
    while i < len(argv):
        arg = argv[i]
        if not arg.startswith("--"):
            raise SystemExit(f"unexpected argument {arg!r}")
        if "=" in arg:
            key, text = arg[2:].split("=", 1)
        else:
            key = arg[2:]
            if i + 1 >= len(argv):
                raise SystemExit(f"missing value for --{key}")
            text = argv[i + 1]
            i += 1
        if key not in known:
            raise SystemExit(
                f"unknown flag --{key}; known flags include: "
                + ", ".join(sorted(known)[:12])
                + ", ..."
            )
        values[key] = _parse_value(text, known[key]) if isinstance(text, str) else text
        i += 1
    return values


class CLI:
    """fit/validate/preproc driver for one :class:`ModelFamily`."""

    def __init__(self, family: ModelFamily):
        self.family = family

    # -- flag space --------------------------------------------------------
    def _known_flags(self, data_cls) -> Dict[str, Any]:
        from perceiver_io_tpu.observability import ObservabilityArgs
        from perceiver_io_tpu.training.trainer import TrainerConfig

        known: Dict[str, Any] = {"config": str, "data": str, "params": str, "ckpt": str}
        known.update(flag_specs(self.family.config_class, "model", self.family.nested))
        known.update(_ctor_flag_specs(data_cls, "data"))
        known.update(flag_specs(TrainerConfig, "trainer"))
        known.update(flag_specs(OptimizerArgs, "optimizer"))
        known.update(flag_specs(LRSchedulerArgs, "lr_scheduler"))
        known.update(flag_specs(ObservabilityArgs, "obs"))
        from perceiver_io_tpu.parallel import MeshConfig

        known.update(flag_specs(MeshConfig, "mesh"))
        return known

    def main(self, argv: Optional[Sequence[str]] = None) -> Any:
        argv = list(sys.argv[1:] if argv is None else argv)
        if not argv or _wants_help(argv):
            # help anywhere in argv (e.g. `fit --help`), like jsonargparse
            self._print_help()
            return None
        subcommand = argv[0]
        if subcommand not in ("fit", "validate", "test", "preproc", "serve", "obs"):
            raise SystemExit(
                f"unknown subcommand {subcommand!r} "
                "(fit|validate|test|preproc|serve|obs)"
            )
        from perceiver_io_tpu.utils.compile_cache import configure_compile_cache

        configure_compile_cache()
        if subcommand == "obs":
            # offline analyzers — no checkpoint, no datamodule, no jax work:
            # `obs report` reads the artifacts a run left behind, `obs
            # incident` reads one flight-recorder bundle
            # (docs/observability.md)
            if len(argv) < 2 or argv[1] not in (
                "report", "incident", "timeline"
            ):
                raise SystemExit(
                    "usage: obs report --events <events.jsonl> "
                    "[--snapshot <snapshot.json>] [--top N] [--json true]\n"
                    "       obs incident --bundle <incident dir> "
                    "[--top N] [--json true]\n"
                    "       obs timeline --timeline <timeline.jsonl> "
                    "[--events <events.jsonl>] [--snapshot <snapshot.json>] "
                    "[--trace_out <trace.json>] [--top N] [--json true]"
                )
            import json as _json

            from perceiver_io_tpu.observability import report as report_mod

            if argv[1] == "incident":
                known = {"bundle": str, "top": int, "json": bool}
                vals = _parse_dotted(argv[2:], known)
                if "bundle" not in vals:
                    raise SystemExit(
                        "obs incident requires --bundle <incident dir>"
                    )
                try:
                    text = report_mod.run_incident(
                        vals["bundle"], top=int(vals.get("top", 8)),
                        as_json=bool(vals.get("json", False)),
                    )
                # JSONDecodeError IS a ValueError — catch it first, with
                # the bundle path the generic message would drop
                except _json.JSONDecodeError as e:
                    raise SystemExit(
                        f"obs incident: bundle manifest is not valid JSON "
                        f"({vals.get('bundle')}: {e})"
                    )
                except (OSError, ValueError) as e:
                    raise SystemExit(f"obs incident: {e}")
                print(text)
                return text
            if argv[1] == "timeline":
                known = {
                    "timeline": str, "events": str, "snapshot": str,
                    "trace_out": str, "top": int, "json": bool,
                }
                vals = _parse_dotted(argv[2:], known)
                if "timeline" not in vals:
                    raise SystemExit(
                        "obs timeline requires --timeline <timeline.jsonl> "
                        "(a --obs.timeline.export file)"
                    )
                try:
                    text = report_mod.run_timeline(
                        vals["timeline"], vals.get("events"),
                        vals.get("snapshot"),
                        trace_out=vals.get("trace_out"),
                        top=int(vals.get("top", 20)),
                        as_json=bool(vals.get("json", False)),
                    )
                # JSONDecodeError IS a ValueError — catch it first, with
                # the artifact path the generic message would drop
                except _json.JSONDecodeError as e:
                    raise SystemExit(
                        f"obs timeline: artifact is not valid JSON "
                        f"({vals.get('timeline')}: {e})"
                    )
                except (OSError, ValueError) as e:
                    raise SystemExit(f"obs timeline: {e}")
                print(text)
                return text
            known = {"events": str, "snapshot": str, "top": int, "json": bool}
            vals = _parse_dotted(argv[2:], known)
            if "events" not in vals:
                raise SystemExit("obs report requires --events <events.jsonl>")
            try:
                text = report_mod.run(
                    vals["events"], vals.get("snapshot"),
                    top=int(vals.get("top", 20)),
                    as_json=bool(vals.get("json", False)),
                )
            except OSError as e:
                # bad artifact paths get the same clean one-line errors as
                # every other flag mistake, not a traceback
                raise SystemExit(f"obs report: {e}")
            except _json.JSONDecodeError as e:
                raise SystemExit(
                    f"obs report: --snapshot is not valid JSON "
                    f"({vals.get('snapshot')}: {e})"
                )
            print(text)
            return text
        if subcommand == "serve":
            # serve needs no datamodule: the checkpoint's embedded config
            # picks the model, and prompts come from a file or stdin.
            from perceiver_io_tpu.observability import ObservabilityArgs

            known = {"ckpt": str, "params": str}
            known.update(flag_specs(ServeArgs, "serve"))
            known.update(flag_specs(ObservabilityArgs, "obs"))
            return self.run_serve(_parse_dotted(argv[1:], known))

        # data module choice first (its ctor defines the --data.* space)
        data_name = None
        for arg in argv[1:]:
            if arg.startswith("--data=") :
                data_name = arg.split("=", 1)[1]
            elif arg == "--data":
                idx = argv.index(arg)
                data_name = argv[idx + 1] if idx + 1 < len(argv) else None
        registry = self.family.data_registry
        if data_name is None:
            data_name = next(iter(registry))
        if data_name not in registry:
            raise SystemExit(
                f"unknown data module {data_name!r}; choose from {sorted(registry)}"
            )
        data_cls = registry[data_name]

        known = self._known_flags(data_cls)
        values = dict(self.family.defaults or {})
        cli_values = _parse_dotted(argv[1:], known)
        if "config" in cli_values:
            import yaml

            with open(cli_values.pop("config")) as fh:
                for key, val in (yaml.safe_load(fh) or {}).items():
                    values[key] = val
        values.update(cli_values)
        values.pop("data", None)
        return self.run(subcommand, data_cls, values)

    # -- execution ---------------------------------------------------------
    def run(self, subcommand: str, data_cls, values: Dict[str, Any]) -> Any:
        import optax

        from perceiver_io_tpu.parallel import MeshConfig, make_mesh
        from perceiver_io_tpu.training.lrs import constant_with_warmup, cosine_with_warmup
        from perceiver_io_tpu.training.optim import make_optimizer
        from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

        if any(k.startswith("obs.slo.") for k in values):
            # inapplicable-flag convention: SLO targets judge SERVING token
            # latency; a fit run has no TTFT to monitor. Checked before any
            # datamodule/model work so the error is instant.
            raise SystemExit(
                "--obs.slo.* applies to the serve subcommand (SLO targets "
                "monitor serving token latency; docs/observability.md)"
            )
        if any(k.startswith("obs.timeline.") for k in values):
            # same stance: the step timeline records SCHEDULER passes —
            # only the serve engines have one
            raise SystemExit(
                "--obs.timeline.* applies to the serve subcommand (the "
                "step timeline records scheduler passes; "
                "docs/observability.md)"
            )
        data_kwargs = {
            k.split(".", 1)[1]: v for k, v in values.items() if k.startswith("data.")
        }
        dm = data_cls(**data_kwargs)
        dm.prepare_data()
        if subcommand == "preproc":
            return None
        _announce_device(subcommand)
        dm.setup()

        if self.family.link is not None:
            self.family.link(dm, values)
        model_cfg = build_dataclass(
            self.family.config_class, values, "model", self.family.nested
        )
        model = self.family.build_model(model_cfg, dm)

        trainer_cfg = build_dataclass(TrainerConfig, values, "trainer")
        opt = build_dataclass(OptimizerArgs, values, "optimizer")
        lrs = build_dataclass(LRSchedulerArgs, values, "lr_scheduler")

        steps = lrs.training_steps or trainer_cfg.max_steps
        if lrs.name == "cosine":
            schedule = cosine_with_warmup(
                opt.lr, warmup_steps=lrs.warmup_steps,
                training_steps=steps, min_fraction=lrs.min_fraction,
            )
        elif lrs.name == "constant":
            schedule = constant_with_warmup(opt.lr, warmup_steps=lrs.warmup_steps)
        else:
            schedule = None
        tx = make_optimizer(
            schedule if schedule is not None else opt.lr,
            optimizer=opt.optimizer,
            weight_decay=opt.weight_decay,
            b1=opt.b1,
            b2=opt.b2,
            frozen_prefixes=(
                self.family.frozen_prefixes(model_cfg)
                if self.family.frozen_prefixes is not None
                else ()
            ),
        )

        mesh = make_mesh(build_dataclass(MeshConfig, values, "mesh"))
        from perceiver_io_tpu.observability import ObservabilityArgs

        obs = build_dataclass(ObservabilityArgs, values, "obs")
        kit = _obs_kit(
            obs, trainer_cfg.default_root_dir,
            is_main=jax.process_index() == 0, passed=set(values),
        )
        trainer = Trainer(
            trainer_cfg,
            mesh,
            self.family.make_loss(model, model_cfg),
            tx,
            model_config=model_cfg,
            lr_schedule=schedule,
            registry=kit["registry"],
            tracer=kit["tracer"],
            profiler_trigger=kit["trigger"],
            snapshot_writer=kit["snapshot_writer"],
        )

        first_batch = next(iter(dm.train_dataloader()))

        def init_params():
            args, kwargs = self.family.init_args(model_cfg, first_batch)
            return model.init(jax.random.PRNGKey(trainer_cfg.seed), *args, **kwargs)[
                "params"
            ]

        initial = None
        if values.get("ckpt") or values.get("params"):
            # Full-model warm start from a save_pretrained dir or trainer
            # checkpoint dir (reference ``--model.params`` reload,
            # ``clm/lightning.py:44-52``; ``--ckpt`` is the evaluation-time
            # spelling, matching the reference's ``test --ckpt_path``).
            from perceiver_io_tpu.training.checkpoint import load_pretrained

            initial, _ = load_pretrained(values.get("ckpt") or values["params"])
        elif self.family.initial_params is not None:
            initial = self.family.initial_params(model, model_cfg, dm)

        try:
            if subcommand in ("validate", "test"):
                trainer.setup_state(init_params, initial_params=initial)
                loader = dm.test_dataloader() if subcommand == "test" else dm.val_dataloader()
                metrics = trainer.test(loader) if subcommand == "test" else trainer.validate(loader)
                trainer.close()
                import json as _json

                print(_json.dumps({k: round(float(v), 6) for k, v in metrics.items()}))
                return metrics

            state = trainer.fit(
                init_params,
                dm.train_dataloader(),
                val_data=dm.val_dataloader,
                initial_params=initial,
            )
            trainer.close()
            return state
        finally:
            # validate/test never reach fit's own forced write — the flag
            # must not be silently ignored on those subcommands (fit already
            # wrote; a second identical write is harmless)
            if kit["snapshot_writer"] is not None:
                kit["snapshot_writer"].maybe_write(force=True)
            if kit["sink"] is not None:
                kit["sink"].close()

    # -- serving -----------------------------------------------------------
    def run_serve(self, values: Dict[str, Any]) -> list:
        """``serve --ckpt <dir>``: bucketed text generation over a saved
        model — prompts (file or stdin) → one JSON line per completion,
        plus a final engine-stats line (docs/serving.md).

        Error isolation (docs/reliability.md): an infeasible prompt (empty /
        longer than the largest bucket) becomes a per-line
        ``{"prompt": ..., "error": ...}`` record instead of aborting the
        run; a bounded queue (``--serve.max_queue``) backpressures by
        draining a micro-batch before resubmitting; timed-out or failed
        requests surface their status per line.

        ``--serve.replicas=N`` (N > 1) serves through a supervised
        :class:`~perceiver_io_tpu.serving.FleetRouter` — load-aware
        dispatch over N engine replicas with circuit breakers and
        (``--serve.failover``) exactly-once failover replay
        (docs/serving.md); the router mirrors the engine surface, so the
        prompt loop below is identical either way.
        """
        import json
        import os
        import time

        from perceiver_io_tpu.data.text.tokenizers import ByteTokenizer
        from perceiver_io_tpu.inference.generate import GenerationConfig
        from perceiver_io_tpu.inference.samplers import SamplingConfig
        from perceiver_io_tpu.models import model_for_config
        from perceiver_io_tpu.observability import ObservabilityArgs, Tracer
        from perceiver_io_tpu.serving import (
            BucketTable,
            QueueFull,
            ServingEngine,
            SlotServingEngine,
        )
        from perceiver_io_tpu.training.checkpoint import load_pretrained

        ckpt = values.get("ckpt") or values.get("params")
        if not ckpt:
            raise SystemExit("serve requires --ckpt <save_pretrained dir>")
        _announce_device("serve")
        args = build_dataclass(ServeArgs, values, "serve")
        obs = build_dataclass(ObservabilityArgs, values, "obs")
        kit = _obs_kit(obs, os.getcwd(), passed=set(values))
        # serve lines always carry a trace_id (the events.jsonl join key),
        # so the engine always gets a tracer — sink-less when --obs.events_path
        # is unset (spans stay in the bounded in-memory buffer).
        tracer = kit["tracer"] or Tracer()
        if kit["slo_monitor"] is not None:
            # slo.breach / slo.recover events land on the run's tracer
            # (into events.jsonl when configured — the obs-report timeline)
            kit["slo_monitor"].tracer = tracer
        if kit["flight_recorder"] is not None:
            # the recorder's span ring and its incident.dump events ride
            # the run's one tracer (sink-less runs still bundle from the
            # in-memory ring)
            kit["flight_recorder"].tracer = tracer
        # the device-cost ledger's builds stream into events.jsonl as
        # `ledger.compile` events, so an offline `obs report` over the
        # events alone still carries the compile/memory table
        from perceiver_io_tpu.observability import default_ledger

        ledger = default_ledger()
        detach_ledger = ledger.attach(
            lambda rec: tracer.event(
                "ledger.compile",
                site=rec["site"],
                compile_ms=rec["compile_ms"],
                flops=rec["flops"],
                bytes_accessed=rec["bytes_accessed"],
                argument_bytes=rec["argument_bytes"],
                output_bytes=rec["output_bytes"],
                temp_bytes=rec["temp_bytes"],
                retrace=rec["retrace"],
                reasons=",".join(rec["retrace_reasons"]),
                bucket_shape=rec["components"].get("bucket_shape"),
            )
        ) if kit["sink"] is not None else (lambda: None)
        # everything from here on runs under the teardown finally:
        # an error in checkpoint load / engine build / warmup must
        # still detach the ledger callback (it closes over THIS
        # run's tracer+sink — leaking it would stream later runs'
        # compiles into a dead events file) and close the artifacts
        try:
            params, model_cfg = load_pretrained(ckpt)
            if model_cfg is None:
                raise SystemExit(f"{ckpt} has no embedded model config")
            model = model_for_config(model_cfg)
            from perceiver_io_tpu.models.text.clm import CausalLanguageModel

            from perceiver_io_tpu.models.text.lm import DecoderLM

            if isinstance(model, DecoderLM):
                # the engines hold Perceiver AR's caches: no convolution
                # state, no grouped heads, no expert layer (docs/lm.md)
                raise SystemExit(
                    "serve does not take the lm family yet: the serving engines "
                    "cache Perceiver AR's latents and keys only (docs/lm.md); "
                    f"got a {type(model).__name__} checkpoint"
                )
            if not isinstance(model, CausalLanguageModel):
                # The decode side is the byte tokenizer; a non-text AR family
                # (e.g. symbolic audio) would sample ids the tokenizer cannot
                # decode — fail fast instead of mid-stream.
                raise SystemExit(
                    "serve currently supports text CLM checkpoints (byte "
                    f"tokenizer); got {type(model).__name__}"
                )

            table = BucketTable.for_model(model)
            if args.prompt_buckets or tuple(args.batch_buckets) != (1, 2, 4, 8):
                table = BucketTable(
                    prompt_lens=tuple(args.prompt_buckets or table.prompt_lens),
                    batch_sizes=tuple(args.batch_buckets),
                )
            tok = ByteTokenizer(padding_side="left")
            gen_cfg = GenerationConfig(
                max_new_tokens=args.max_new_tokens,
                num_latents=args.num_latents,
                pad_token_id=tok.pad_token_id or 0,
                eos_token_id=tok.eos_token_id,
                sampling=SamplingConfig(temperature=args.temperature),
            )
            if args.engine not in ("bucket", "slots"):
                raise SystemExit(
                    f"--serve.engine must be 'bucket' or 'slots', got {args.engine!r}"
                )
            from perceiver_io_tpu.inference import decode_strategy as strategy_mod

            decode_mode = _serve_decode_mode(args.decode_strategy)
            if args.decode_strategy_file:
                # persisted verdicts short-circuit the warmup autotune; fresh
                # verdicts measured this run are written back on warmup
                strategy_mod.load_registry(args.decode_strategy_file)
            if args.replicas < 1:
                raise SystemExit(
                    f"--serve.replicas must be >= 1, got {args.replicas}"
                )
            if args.preemption is not None:
                from perceiver_io_tpu.serving.slots import PREEMPTION_MODES

                if args.preemption not in PREEMPTION_MODES:
                    raise SystemExit(
                        "--serve.preemption must be one of "
                        f"{PREEMPTION_MODES}, got {args.preemption!r}"
                    )
            if args.admit_headroom_blocks < 0:
                raise SystemExit(
                    "--serve.admit_headroom_blocks must be >= 0, got "
                    f"{args.admit_headroom_blocks}"
                )
            if args.admit_headroom_blocks and args.preemption is None:
                # inapplicable-flag convention: headroom only shapes lazy
                # admission, which --serve.preemption enables
                raise SystemExit(
                    "--serve.admit_headroom_blocks applies with "
                    "--serve.preemption (strict reservations already "
                    "cover the worst case)"
                )
            if args.swap_gbps is not None:
                if args.preemption not in ("swap", "auto"):
                    # inapplicable-flag convention: the link prior only
                    # feeds the swap-vs-recompute cost model
                    raise SystemExit(
                        "--serve.swap_gbps applies with "
                        "--serve.preemption=swap|auto (no other mode "
                        "ships KV pages over the host link)"
                    )
                if args.swap_gbps <= 0:
                    raise SystemExit(
                        f"--serve.swap_gbps must be > 0, got "
                        f"{args.swap_gbps}"
                    )
            autoscale = args.autoscale
            if autoscale.max is None and any(
                k.startswith("serve.autoscale.") for k in values
            ):
                # inapplicable-flag convention: tuning an autoscaler that
                # was never enabled must not silently do nothing
                raise SystemExit(
                    "--serve.autoscale.* tunes the fleet autoscaler, which "
                    "is enabled by setting --serve.autoscale.max"
                )
            if autoscale.max is not None:
                if autoscale.max < max(autoscale.min, args.replicas):
                    raise SystemExit(
                        f"--serve.autoscale.max ({autoscale.max}) must be >= "
                        f"max(--serve.autoscale.min ({autoscale.min}), "
                        f"--serve.replicas ({args.replicas}))"
                    )
                if autoscale.scale_up_slots is not None and args.engine != "slots":
                    raise SystemExit(
                        "--serve.autoscale.scale_up_slots applies to "
                        "--serve.engine=slots (the bucket engine has no "
                        "persistent decode slots to resize)"
                    )
            # the autoscaler drives FleetRouter.add/remove_replica, so
            # enabling it builds the fleet layer even at one replica
            fleet_mode = args.replicas > 1 or autoscale.max is not None
            if not fleet_mode:
                # inapplicable-flag convention (same as --serve.prefill_chunk
                # with the bucket engine): asking for fleet supervision
                # without a fleet must not silently do nothing
                if args.step_timeout_s is not None:
                    raise SystemExit(
                        "--serve.step_timeout_s applies to --serve.replicas > 1 "
                        "(hang detection is fleet supervision; a single engine "
                        "is driven directly)"
                    )
                if not args.failover:
                    print(
                        "[serve] --serve.failover=false is a no-op with "
                        "--serve.replicas=1 (no fleet layer, so there is no "
                        "failover to disable)",
                        file=sys.stderr, flush=True,
                    )
            engine_kwargs = dict(
                rng=jax.random.PRNGKey(args.seed),
                # with a fleet, admission (bounded queue + deadlines) is
                # lifted to the router; the engines stay unbounded and
                # enforce only the remaining deadline handed over per
                # dispatch
                max_queue=None if fleet_mode else args.max_queue,
                default_deadline_s=None if fleet_mode else args.deadline_s,
                # fleet replicas keep PRIVATE registries so serve_stats'
                # per_replica engine stats attribute to one replica each
                # (a shared registry would show fleet-wide aggregates on
                # every row); the kit registry then carries the fleet_*
                # supervision families
                registry=None if fleet_mode else kit["registry"],
                tracer=tracer,
                # serve-side p95 regression trigger: the slot engine feeds
                # per-token decode-step times, the bucket engine per-batch
                # execute times; an armed trigger captures the next dispatch
                profiler_trigger=kit["trigger"],
                decode_strategy=decode_mode,
            )
            kv_mode = _serve_kv_layout(args.kv_layout)
            prefix_mode = _serve_prefix_cache(args.prefix_cache)
            spec_mode = _serve_speculation(args.speculation)
            if (
                args.engine == "slots"
                and args.warmup
                and spec_mode == "auto"
                and strategy_mod.lookup_speculation(model) is None
            ):
                # measure once, memoize (docs/serving.md "Speculative
                # decoding"): A/B each draft geometry against "off" on the
                # probe workload and record acceptance x per-step cost; the
                # verdict lands in the strategy registry so a persisted
                # --serve.decode_strategy_file skips this on the next boot
                t0 = time.monotonic()
                spec_mode = strategy_mod.autotune_speculation(model, params)
                print(
                    f"[serve] speculation autotune picked {spec_mode!r} in "
                    f"{time.monotonic() - t0:.1f}s", file=sys.stderr,
                    flush=True,
                )
            flight_recorder = kit["flight_recorder"]
            # sharded serving (docs/serving.md "Sharded serving"): any
            # --serve.mesh.* flag opts in — including an explicit 1x1
            # degenerate mesh (the byte-identical single-device form)
            mesh_requested = any(k.startswith("serve.mesh.") for k in values)
            mesh_alloc = None
            if mesh_requested:
                if args.engine != "slots":
                    raise SystemExit(
                        "--serve.mesh.* applies to --serve.engine=slots "
                        "(the sharded runtime compiles the slot engine's "
                        "executors over the mesh; the bucket engine is "
                        "single-device)"
                    )
                from perceiver_io_tpu.serving import (
                    MeshGroupAllocator,
                    ServingMeshSpec,
                    fleet_mesh_specs,
                )

                try:
                    base_spec = ServingMeshSpec(
                        data=args.mesh.data, model=args.mesh.model,
                        device_offset=args.mesh.device_offset,
                    )
                    # the INITIAL fleet must fit the device budget outright
                    # (autoscaler spawns past it wrap around the allocator,
                    # documented on sharding.MeshGroupAllocator)
                    fleet_mesh_specs(base_spec, max(1, args.replicas))
                except ValueError as e:
                    raise SystemExit(f"--serve.mesh.*: {e}")
                # one shared allocator hands each spawn the first FREE
                # disjoint device group — initial replicas, crash rebuilds
                # (the crashed group frees for its rebuild), scale-ups
                mesh_alloc = MeshGroupAllocator(base_spec)
            if args.engine == "slots":
                # swap modes let the engine resolve the link rate itself
                # (explicit --serve.swap_gbps > per-platform calibrated
                # registry entry > 16.0 prior); other modes keep the
                # post-mortem denominator pinned to the obs-side flag
                if args.preemption in ("swap", "auto"):
                    link_gbps = args.swap_gbps
                else:
                    link_gbps = obs.timeline.swap_gbps

                def make_engine():
                    eng = SlotServingEngine(
                        model, params, gen_cfg, table, slots=args.slots,
                        prefill_chunk=args.prefill_chunk,
                        kv_layout=kv_mode, kv_block_size=args.kv_block_size,
                        kv_blocks=args.kv_blocks, prefix_cache=prefix_mode,
                        preemption=args.preemption,
                        admit_headroom_blocks=args.admit_headroom_blocks,
                        speculation=spec_mode,
                        mesh=(
                            mesh_alloc.acquire() if mesh_alloc is not None
                            else None
                        ),
                        swap_link_gbps=link_gbps,
                        **engine_kwargs
                    )
                    # inside the factory, not after it: fleet replica
                    # restarts / autoscaler spawns rebuild engines through
                    # this factory and must keep the pool-exhaustion seam
                    eng.flight_recorder = flight_recorder
                    # shared ring: every replica's passes land in ONE
                    # step-ordered timeline (--obs.timeline.steps)
                    eng.timeline = kit["timeline"]
                    return eng
            else:
                if args.prefill_chunk is not None:
                    raise SystemExit(
                        "--serve.prefill_chunk applies to --serve.engine=slots "
                        "(the bucket engine has no resident decode to interleave)"
                    )
                # inapplicable-flag convention: an explicitly paged (or
                # sized) KV pool on the bucket engine must not silently do
                # nothing. Checked on the RAW flags, not the env-resolved
                # mode: a machine-wide PERCEIVER_KV_LAYOUT set for slot
                # deployments must not break unrelated bucket-engine jobs
                # on the same host.
                if args.kv_layout != "auto" or args.kv_block_size is not None \
                        or args.kv_blocks is not None:
                    raise SystemExit(
                        "--serve.kv_layout/--serve.kv_block_size/"
                        "--serve.kv_blocks apply to --serve.engine=slots "
                        "(the bucket engine has no persistent KV state to page)"
                    )
                if args.prefix_cache != "auto":
                    raise SystemExit(
                        "--serve.prefix_cache applies to --serve.engine=slots "
                        "with the paged KV layout (the bucket engine has no "
                        "block tables to share)"
                    )
                if args.preemption is not None \
                        or args.admit_headroom_blocks != 0 \
                        or args.swap_gbps is not None:
                    raise SystemExit(
                        "--serve.preemption/--serve.admit_headroom_blocks/"
                        "--serve.swap_gbps apply to --serve.engine=slots "
                        "with a paged KV layout (the bucket engine has no "
                        "page pool to preempt from)"
                    )
                if args.speculation != "auto":
                    raise SystemExit(
                        "--serve.speculation applies to --serve.engine=slots "
                        "(the bucket engine has no resident decode loop to "
                        "draft ahead of)"
                    )

                def make_engine():
                    eng = ServingEngine(
                        model, params, gen_cfg, table, **engine_kwargs
                    )
                    eng.flight_recorder = flight_recorder
                    eng.timeline = kit["timeline"]
                    return eng
            if fleet_mode:
                from perceiver_io_tpu.serving import FleetRouter

                # the fleet mirrors the engine request surface, so the
                # whole prompt loop below drives it unchanged; the warm
                # executor caches are process-global, so N replicas cost
                # one compile pass
                engine = FleetRouter(
                    [make_engine] * args.replicas,
                    max_pending=args.max_queue,
                    default_deadline_s=args.deadline_s,
                    failover=args.failover,
                    step_timeout_s=args.step_timeout_s,
                    registry=kit["registry"],
                    tracer=tracer,
                    # telemetry-driven admission (docs/observability.md): a
                    # sustained burn tightens max_pending/deadline shedding
                    slo_monitor=kit["slo_monitor"],
                    slo_shed_factor=obs.slo.shed_factor,
                    # replica failures / breaker opens dump incident
                    # bundles (docs/observability.md)
                    flight_recorder=flight_recorder,
                )
                if autoscale.max is not None:
                    from perceiver_io_tpu.serving import FleetAutoscaler

                    # ctor installs itself on the router; every fleet
                    # step() polls it (docs/serving.md "Elasticity")
                    FleetAutoscaler(
                        engine,
                        max_replicas=autoscale.max,
                        min_replicas=autoscale.min,
                        up_cooldown_s=autoscale.up_cooldown_s,
                        down_cooldown_s=autoscale.down_cooldown_s,
                        up_evidence=autoscale.up_evidence,
                        down_evidence=autoscale.down_evidence,
                        queue_high=autoscale.queue_high,
                        queue_low=autoscale.queue_low,
                        scale_up_slots=autoscale.scale_up_slots,
                        tracer=tracer,
                    )
            else:
                engine = make_engine()
                if kit["slo_monitor"] is not None:
                    # single-engine SLO feeds: the engine mirrors every
                    # TTFT/ITL sample into the monitor, and the error-rate
                    # dimension diffs the serving_* disposition counters
                    # (same registry) per poll
                    engine.latency_sink = kit["slo_monitor"].sink
                    kit["slo_monitor"].watch_counters(
                        kit["registry"].counters, prefix="serving"
                    )
            if flight_recorder is not None:
                # dump-time state sources (docs/observability.md): health
                # (the fleet's embeds replica_detail), SLO burn state,
                # autoscaler ladder state, and the paged pool(s)
                flight_recorder.add_source("health", engine.health)
                if kit["slo_monitor"] is not None:
                    flight_recorder.add_source("slo", kit["slo_monitor"].stats)
                autoscaler = getattr(engine, "autoscaler", None)
                if autoscaler is not None:
                    flight_recorder.add_source("autoscaler", autoscaler.stats)
                if fleet_mode:
                    def _fleet_pools():
                        return {
                            str(r.replica_id): r.engine._pool.stats()
                            for r in engine.replicas
                            if getattr(r.engine, "_pool", None) is not None
                        }

                    flight_recorder.add_source("kv_pool", _fleet_pools)
                elif getattr(engine, "_pool", None) is not None:
                    flight_recorder.add_source("kv_pool", engine._pool.stats)
                if kit["timeline"] is not None:
                    # ring summary lands in every bundle; the full records
                    # live in the --obs.timeline.export JSONL
                    flight_recorder.add_source(
                        "timeline", kit["timeline"].summary
                    )
                # per-victim recompute-vs-swap post-mortems (docs/
                # observability.md "Scheduler timeline & post-mortems")
                if fleet_mode:
                    def _fleet_postmortems():
                        return {
                            str(r.replica_id): r.engine.postmortems()
                            for r in engine.replicas
                            if hasattr(r.engine, "postmortems")
                        }

                    flight_recorder.add_source(
                        "preemption_postmortems", _fleet_postmortems
                    )
                elif hasattr(engine, "postmortems"):
                    flight_recorder.add_source(
                        "preemption_postmortems", engine.postmortems
                    )
            if args.warmup:
                t0 = time.monotonic()
                compiles = engine.warmup()
                print(
                    f"[serve] warmup compiled {compiles} executors in "
                    f"{time.monotonic() - t0:.1f}s", file=sys.stderr, flush=True,
                )
                if args.decode_strategy_file and (
                    decode_mode == "auto"
                    or (args.engine == "slots" and (
                        kv_mode == "auto" or args.speculation == "auto"
                        or args.preemption in ("swap", "auto")
                    ))
                ):
                    strategy_mod.save_registry(args.decode_strategy_file)

            if args.http.port is not None:
                # gateway mode (docs/serving.md "Streaming"): serve over
                # HTTP instead of the prompts loop — requests arrive on
                # sockets, tokens stream back as they decode
                if args.prompts:
                    raise SystemExit(
                        "--serve.prompts applies to the batch loop; with "
                        "--serve.http.port set, prompts arrive over "
                        "POST /v1/generate"
                    )
                return self._serve_http(engine, tok, args, kit)

            if args.prompts:
                with open(args.prompts) as fh:
                    prompts = [line.rstrip("\n") for line in fh if line.strip()]
            else:
                prompts = [line.rstrip("\n") for line in sys.stdin if line.strip()]
            if not prompts:
                raise SystemExit("serve: no prompts (empty file/stdin)")

            return self._serve_prompts(engine, tok, prompts, args, kit)
        finally:
            # swap transfers calibrate the per-platform link rate DURING
            # serving — persist the measured value beside spec_entries so
            # the next process prices swap-vs-recompute from evidence
            if args.decode_strategy_file \
                    and args.preemption in ("swap", "auto"):
                strategy_mod.save_registry(args.decode_strategy_file)
            # fit's teardown parity: even an exception mid-drain leaves a
            # final snapshot and a closed events file
            detach_ledger()
            ledger.update_device_gauges()
            if kit["snapshot_writer"] is not None:
                kit["snapshot_writer"].maybe_write(force=True)
            if kit["timeline_export"] is not None and kit["timeline"] is not None:
                # same stance as the snapshot: an exception mid-drain still
                # leaves the ring on disk for `obs timeline`
                n = kit["timeline"].write_jsonl(kit["timeline_export"])
                print(
                    f"[serve] timeline: wrote {n} step records to "
                    f"{kit['timeline_export']}",
                    file=sys.stderr, flush=True,
                )
            if kit["sink"] is not None:
                kit["sink"].close()

    def _serve_http(self, engine, tok, args, kit) -> list:
        """``serve --serve.http.port=N``: run the async HTTP/SSE streaming
        gateway over the built engine/fleet until ``--serve.http.max_streams``
        terminal streams (or Ctrl-C), then drain and print the final
        ``serve_stats`` line — gateway wire counters included."""
        import json
        import time

        from perceiver_io_tpu.serving.gateway import STREAM_MODES, StreamingGateway

        if args.http.stream not in STREAM_MODES:
            raise SystemExit(
                "--serve.http.stream must be one of "
                f"{'|'.join(STREAM_MODES)}, got {args.http.stream!r}"
            )
        t0 = time.monotonic()
        gateway = StreamingGateway(
            engine,
            host=args.http.host,
            port=args.http.port,
            stream=args.http.stream,
            encode=lambda text: tok.encode(text),
            decode=lambda ids: tok.decode(ids),
            registry=kit["registry"],
            tracer=engine.tracer if hasattr(engine, "tracer") else None,
            slo_monitor=kit["slo_monitor"],
            snapshot_writer=kit["snapshot_writer"],
            flight_recorder=kit["flight_recorder"],
            max_streams=args.http.max_streams,
        )
        gateway.run_in_thread()
        print(
            f"[serve] http gateway listening on {gateway.host}:{gateway.port} "
            f"(stream={args.http.stream}"
            + (f", max_streams={args.http.max_streams}"
               if args.http.max_streams is not None else "")
            + ")",
            file=sys.stderr, flush=True,
        )
        try:
            gateway.wait()
        except KeyboardInterrupt:
            print("[serve] interrupt: shutting the gateway down",
                  file=sys.stderr, flush=True)
        finally:
            gateway.close()
        engine.drain()
        if kit["slo_monitor"] is not None:
            # unconditional final poll (the _serve_prompts convention): the
            # fleet router polls at the START of each step, so the last
            # drain step's dispositions would otherwise never be diffed
            # into the monitor's error window
            kit["slo_monitor"].poll()
        wall_s = time.monotonic() - t0
        if args.stats:
            from perceiver_io_tpu.observability import default_ledger, default_registry

            stats = engine.stats()
            stats["health"] = engine.health()
            stats["wall_s"] = round(wall_s, 3)
            stats["gateway"] = gateway.stats()
            stats["metrics"] = engine.registry.snapshot()
            stats["compile_ledger"] = default_ledger().snapshot()
            stats["process_metrics"] = default_registry().snapshot()
            if kit["slo_monitor"] is not None and "slo" not in stats:
                stats["slo"] = kit["slo_monitor"].stats()
            if kit["flight_recorder"] is not None:
                stats["incident"] = kit["flight_recorder"].stats()
            if kit["timeline"] is not None and "timeline" not in stats:
                # fleet stats() has no ring of its own; the shared ring's
                # summary rides the run record (single-engine stats()
                # already embeds it)
                stats["timeline"] = kit["timeline"].summary()
            print(json.dumps({"serve_stats": stats}), flush=True)
        return []

    def _serve_prompts(self, engine, tok, prompts, args, kit) -> list:
        import json
        import time

        from perceiver_io_tpu.serving import QueueFull

        t0 = time.monotonic()
        pad_id = tok.pad_token_id or 0
        # (prompt, ServeRequest | None, error | None, trace_id | None, status)
        handles: list = []
        for p in prompts:
            ids = np.asarray(tok.encode(p), np.int32)
            try:
                # backpressure: make room BEFORE submitting so a full queue
                # drains work instead of tripping the shed counter (shed
                # should count true rejections, not this retry loop). A
                # fleet with every breaker open makes no progress until a
                # cooldown elapses — yield instead of hot-spinning (plain
                # engines never report no-progress; their step always
                # works when pending)
                while not engine.health()["ready"] and engine.pending():
                    if (
                        engine.step() == 0
                        and not getattr(engine, "last_step_made_progress", True)
                    ):
                        time.sleep(0.005)
                req = engine.submit(ids)
                handles.append((p, req, None, req.trace_id, None))
            except (ValueError, QueueFull) as e:
                # reject/shed this line, keep serving the rest; the engine
                # already emitted this submission's terminal span — carry its
                # trace ID (and the SAME terminal status the span/counters
                # use) so the error record joins against events.jsonl
                handles.append(
                    (p, None, f"{type(e).__name__}: {e}",
                     getattr(e, "trace_id", None),
                     "shed" if isinstance(e, QueueFull) else "rejected")
                )
            if kit["snapshot_writer"] is not None:
                kit["snapshot_writer"].maybe_write()
            if kit["flight_recorder"] is not None:
                kit["flight_recorder"].maybe_record()
        # CLI-driven drain (not the blocking engine.drain()): the snapshot
        # cadence must keep firing while the queue — the bulk of the run's
        # wall time — generates, or a mid-run poller sees stale telemetry.
        # pending(), not step()'s return value: a slot-engine step advances
        # one token and legitimately disposes of nothing mid-generation.
        # The SLO monitor is polled per pass for the single-engine path
        # (the fleet router polls it inside its own step()).
        slo_monitor = kit["slo_monitor"]
        fleet_polls = hasattr(engine, "slo_monitor")
        while engine.pending():
            if (
                engine.step() == 0
                and not getattr(engine, "last_step_made_progress", True)
            ):
                time.sleep(0.005)  # fleet waiting out a breaker cooldown
            if slo_monitor is not None and not fleet_polls:
                slo_monitor.poll()
            if kit["snapshot_writer"] is not None:
                kit["snapshot_writer"].maybe_write()
            if kit["flight_recorder"] is not None:
                # the incident ring's periodic "before" evidence rides the
                # same opportunistic cadence as the snapshot writer
                kit["flight_recorder"].maybe_record()
        if slo_monitor is not None:
            # unconditional final poll: the fleet router polls at the START
            # of each step, so the last step's dispositions would otherwise
            # never be diffed into the error window (a duplicate poll is an
            # idempotent counter diff — harmless for the single-engine path)
            slo_monitor.poll()
        engine.drain()  # queue already empty: just stop accepting
        wall_s = time.monotonic() - t0

        results = []
        for p, req, error, trace_id, status in handles:
            if req is not None and req.status == "ok":
                completion = tok.decode([t for t in req.result.tolist() if t != pad_id])
                results.append({
                    "prompt": p, "completion": completion,
                    "status": "ok", "trace_id": trace_id,
                })
            else:
                results.append({
                    "prompt": p,
                    "error": error if req is None else (req.error or req.status),
                    "status": status if req is None else req.status,
                    "trace_id": trace_id,
                })
        for row in results:
            print(json.dumps(row), flush=True)
        if args.stats:
            from perceiver_io_tpu.observability import default_ledger, default_registry

            stats = engine.stats()
            stats["health"] = engine.health()
            stats["wall_s"] = round(wall_s, 3)
            stats["metrics"] = engine.registry.snapshot()
            # the engine's stats() carries the ledger rollup; serve_stats is
            # the run's one durable record, so it ships the full per-executor
            # compile/memory table AND the process-wide registry (compile_*/
            # retrace_*/executor_cache_* counters, hbm/resident gauges —
            # families that live beside, not on, the engine's registry)
            stats["compile_ledger"] = default_ledger().snapshot()
            stats["process_metrics"] = default_registry().snapshot()
            if kit["slo_monitor"] is not None and "slo" not in stats:
                # fleet stats() already embeds the monitor; single-engine
                # runs attach it here so serve_stats always carries the
                # burn/breach summary when SLO targets were set
                stats["slo"] = kit["slo_monitor"].stats()
            if kit["flight_recorder"] is not None:
                # the run's one durable record names every bundle written
                stats["incident"] = kit["flight_recorder"].stats()
            if kit["timeline"] is not None and "timeline" not in stats:
                stats["timeline"] = kit["timeline"].summary()
            print(json.dumps({"serve_stats": stats}), flush=True)
        return results

    def _print_help(self) -> None:
        print(f"usage: {self.family.name} {{fit|validate|test|preproc|serve|obs}} [--flag=value ...]")
        print("flag groups: --model.* --data.* --trainer.* --optimizer.* "
              "--lr_scheduler.* --obs.* --config=<yaml> --data=<name> --ckpt=<dir>")
        print("serve: --ckpt=<dir> --serve.prompts=<file|stdin> --serve.max_new_tokens "
              "--serve.engine={bucket|slots} --serve.slots --serve.prefill_chunk "
              "--serve.decode_strategy={auto|cached|recompute} "
              "--serve.decode_strategy_file "
              "--serve.speculation={auto|off|k<K>d<D>} "
              "--serve.prompt_buckets --serve.batch_buckets --serve.warmup "
              "--serve.max_queue --serve.deadline_s "
              "--serve.replicas=<n> --serve.failover={true|false} "
              "--serve.step_timeout_s=<s>")
        print("serve autoscale: --serve.autoscale.max=<n> --serve.autoscale.min "
              "--serve.autoscale.up_cooldown_s --serve.autoscale.down_cooldown_s "
              "--serve.autoscale.up_evidence --serve.autoscale.down_evidence "
              "--serve.autoscale.queue_high --serve.autoscale.queue_low "
              "--serve.autoscale.scale_up_slots — SLO-driven fleet elasticity: "
              "burn/queue pressure scales replicas up to max, cooldown-gated "
              "zero-downtime scale-down (docs/serving.md)")
        print("serve mesh: --serve.mesh.data=<n> --serve.mesh.model=<n> "
              "--serve.mesh.device_offset=<i> — sharded serving over the "
              "parallelism mesh (slots engine): slots shard along data, "
              "attention heads + KV caches along model; with replicas each "
              "replica owns the next disjoint data x model device group "
              "(docs/serving.md \"Sharded serving\")")
        print("serve http gateway: --serve.http.port=<n|0> --serve.http.host "
              "--serve.http.stream={sse|jsonl} --serve.http.max_streams — "
              "POST /v1/generate streams tokens as they decode; GET /healthz, "
              "GET /metrics; client disconnects cancel mid-generation "
              "(docs/serving.md)")
        print("observability: --obs.events_path=<events.jsonl> --obs.snapshot_every_s "
              "--obs.snapshot_path --obs.profile_on_regress_factor "
              "--obs.trace_sample=<0..1> --obs.trace_keep_slow_ms "
              "--obs.events_max_bytes (fit and serve; docs/observability.md)")
        print("incident flight recorder: --obs.incident.dir=<dir> "
              "--obs.incident.cooldown_s --obs.incident.max_bundles "
              "--obs.incident.keep_spans — triggered bounded bundles at the "
              "serving seams (SLO breach, replica failure, pool exhaustion, "
              "autoscaler escalation, gateway mass-disconnect); analyze with "
              "obs incident --bundle=<dir>")
        print("slo (serve): --obs.slo.ttft_p95_ms --obs.slo.inter_token_p95_ms "
              "--obs.slo.error_rate --obs.slo.fast_window_s --obs.slo.slow_window_s "
              "--obs.slo.burn_rate --obs.slo.shed_factor — burn-rate monitor, "
              "breach events, fleet admission tightening")
        print("timeline (serve): --obs.timeline.steps=<n> --obs.timeline.export"
              "=<timeline.jsonl> --obs.timeline.swap_gbps — per-pass scheduler "
              "ring (admissions, slot occupancy, preemption post-mortems); "
              "analyze with obs timeline")
        print("obs report: --events=<events.jsonl> [--snapshot=<snapshot.json>] "
              "[--top N] [--json true] — offline latency/compile/padding report")
        print("obs timeline: --timeline=<timeline.jsonl> [--events=<events.jsonl>] "
              "[--snapshot=<snapshot.json>] [--trace_out=<trace.json>] — "
              "per-slot gantt + per-request decomposition + Chrome-trace export")
        print(f"data modules: {sorted(self.family.data_registry)}")


def _ctor_flag_specs(cls, prefix: str) -> Dict[str, Any]:
    """Flag specs from ``__init__`` signatures (datamodules are plain
    classes, not dataclasses). Walks the MRO while ``**kwargs`` forwards to
    the base class, so subclass flags include inherited knobs."""
    import inspect

    specs: Dict[str, Any] = {}
    for klass in cls.__mro__:
        if klass is object or "__init__" not in vars(klass):
            continue
        sig = inspect.signature(klass.__init__)
        hints = typing.get_type_hints(klass.__init__)
        has_var_kw = False
        for name, param in sig.parameters.items():
            if param.kind is inspect.Parameter.VAR_KEYWORD:
                has_var_kw = True
                continue
            if name == "self" or param.kind is inspect.Parameter.VAR_POSITIONAL:
                continue
            specs.setdefault(f"{prefix}.{name}", hints.get(name, str))
        if not has_var_kw:
            break
    return specs

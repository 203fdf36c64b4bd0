"""Shape-bucketed serving engine: continuous micro-batching over the
compiled generation executors.

``generate()`` compiles one executor per exact ``(batch, prompt_len,
num_latents, s1, s2)`` plan, and ``TextGenerationPipeline`` pads each
caller's batch to its own max width — so ragged real traffic causes
unbounded retracing and tiny fixed batches. This engine is the first
load-path layer between "a jitted ``generate()``" and "a service":

- **Bucketing** — every prompt is padded up to a static
  ``(batch_size, prompt_len)`` grid (:class:`~.buckets.BucketTable`), so
  all traffic lands on at most ``len(table)`` pre-compilable executors
  (plus the phase-plan split, see :meth:`ServingEngine.warmup`).
- **Continuous micro-batching** — queued requests are packed FIFO into the
  next bucket slot via the existing left-pad path (``prompt_pad_count``);
  unfilled rows are dummy pad rows whose outputs are discarded; results are
  split back per request.
- **Warmup** — :meth:`ServingEngine.warmup` compiles every bucket before
  traffic is accepted.
- **Observability** (docs/observability.md) — every counter lives on a
  :class:`~perceiver_io_tpu.observability.MetricsRegistry` under canonical
  Prometheus-style names (``serving_requests_completed_total``, ...), with
  queue-wait / batch-assembly / device-execute histograms; an optional
  :class:`~perceiver_io_tpu.observability.Tracer` threads one trace per
  request through ``submit → queued → batched → executed → split/complete``
  so every submitted request ends in exactly one terminal
  ``serving.request`` span (status ``ok``/``shed``/``timed_out``/
  ``failed``/``rejected``). The executor cache's hit/miss/evict counters
  (``generate.executor_cache_stats``) surface in
  :meth:`ServingEngine.stats` too, so residual retracing is measured,
  never silent.

Exactness: generation is left-pad invariant (padded keys are masked out of
every softmax; ``tests/test_generate.py`` pins padded == unpadded against
the torch reference), so for greedy decoding the bucketed output is
token-identical to the unbucketed path. The effective latent count is
clamped by the bucket width (``min(bucket_len, config.num_latents)``)
exactly as the unbucketed pipeline clamps it by the batch's max width —
keep ``config.num_latents`` at or below the shortest served prompt if
per-request calls must match bit-for-bit.

The engine is deliberately synchronous and single-owner: ``submit()``
enqueues, ``step()`` drains one micro-batch, ``serve()`` is submit-all +
drain. An async front end (HTTP/RPC) drives the same queue from its own
loop; device work already serializes inside each compiled executor.

Fault tolerance (docs/reliability.md): the queue is bounded (``max_queue``
→ :class:`~perceiver_io_tpu.reliability.QueueFull` backpressure + a shed
counter), requests carry deadlines (expired ones complete ``timed_out``
instead of occupying a bucket slot), a failing request or executor marks
only its own request(s) ``failed`` while the rest of the queue drains,
``drain()`` is the graceful-shutdown path, and ``health()`` is the
readiness snapshot a front end polls. All failure paths are drilled by the
deterministic chaos harness (``reliability.chaos``) via the optional
``chaos`` / ``clock`` hooks.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    executor_cache_stats,
    generate,
)
from perceiver_io_tpu.observability import MetricsRegistry, Tracer
from perceiver_io_tpu.reliability import QueueFull
from perceiver_io_tpu.serving.buckets import BucketTable

#: shared no-op capture context for unarmed dispatches (nullcontext is
#: stateless and re-enterable, so one instance serves every step)
_NULL_CAPTURE = contextlib.nullcontext()


class _SafeCapture:
    """A profiler capture that cannot fail the dispatch it observes: enter
    and exit errors (an already-active profiler session, an unwritable
    capture dir) degrade to no capture instead of surfacing inside the
    engine's executor-failure handler — which would terminally fail every
    resident request over telemetry."""

    __slots__ = ("_ctx",)

    def __init__(self, ctx):
        self._ctx = ctx

    def __enter__(self):
        try:
            return self._ctx.__enter__()
        except Exception:
            self._ctx = None
            return None

    def __exit__(self, *exc):
        if self._ctx is None:
            return False
        try:
            return self._ctx.__exit__(*exc)
        except Exception:
            return False  # never replace the dispatch's own exception

#: The shared health-snapshot schema contract (docs/serving.md): every
#: ``health()`` in the serving layer — both engines, the fleet's per-replica
#: snapshot, and the FleetRouter itself — exposes AT LEAST these keys, so a
#: supervisor (the fleet router, a load balancer probe) reads any of them
#: uniformly. Implementations may add keys (the slot engine adds ``slots``/
#: ``slots_active``; a Replica adds breaker state) but never drop these.
#: Pinned by the contract test in ``tests/test_fleet.py``.
HEALTH_KEYS = frozenset({
    "ready", "accepting", "queue_depth", "max_queue", "oldest_wait_ms",
    "completed", "shed", "timed_out", "failed", "cancelled",
})

#: canonical registry counter names -> the legacy ``stats()`` keys they
#: replace (kept as deprecation aliases; docs/observability.md)
STAT_ALIASES = {
    "serving_requests_submitted_total": "requests",
    "serving_requests_completed_total": "completed",
    "serving_requests_shed_total": "shed",
    "serving_requests_timed_out_total": "timed_out",
    "serving_requests_failed_total": "failed",
    "serving_requests_rejected_total": "rejected",
    "serving_requests_cancelled_total": "cancelled",
    "serving_batches_total": "batches",
    "serving_tokens_generated_total": "tokens_generated",
}


def _round_ms(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 3)


@dataclass
class ServeRequest:
    """One queued prompt and, after its micro-batch ran, its outcome.

    ``status`` is ``"queued"`` until the scheduler disposes of the request:
    ``"ok"`` (``result`` holds the generated row), ``"timed_out"`` (deadline
    expired before a bucket slot ran it), ``"cancelled"`` (the caller
    withdrew it via :meth:`ServingEngine.cancel` — the streaming gateway's
    client-disconnect path), or ``"failed"`` (``error`` holds the reason;
    its micro-batch peers are unaffected).
    """

    request_id: int
    prompt: np.ndarray  # (len,) int32, unpadded
    config: GenerationConfig
    submitted_at: float
    deadline_at: Optional[float] = None  # absolute, in engine-clock seconds
    started_at: Optional[float] = None
    result: Optional[np.ndarray] = None  # (max_new_tokens,) ids, pad after EOS
    status: str = "queued"  # queued | ok | timed_out | cancelled | failed
    error: Optional[str] = None
    #: per-request trace ID (None when the engine has no tracer) — the join
    #: key between the serve CLI's JSON lines and events.jsonl
    trace_id: Optional[str] = None
    #: TTFT measurement anchor on the engine clock — defaults to
    #: ``submitted_at``. The fleet router backdates it to the FLEET submit
    #: time at dispatch (and the HTTP gateway to the SOCKET accept
    #: instant), so time-to-first-token stays the user-facing number
    #: (front door → first token) instead of resetting at each replica
    #: handoff. Queue-wait / request-latency accounting keeps using
    #: ``submitted_at`` — those attribute THIS engine's share.
    ttft_anchor_s: Optional[float] = None
    #: optional per-request incremental token sink (docs/serving.md
    #: "Streaming"): called ``on_token(index, token_id)`` the moment a REAL
    #: token for this request materializes — per token step on the slot
    #: engine, once per token at batch completion on the bucket engine
    #: (batch granularity). Indices restart at 0 when a fleet failover
    #: replays the request; greedy determinism makes the replayed prefix
    #: identical, so stream consumers dedupe by index. A raising sink is
    #: isolated (``serving_token_sink_errors_total``), never failing the
    #: request it observes.
    on_token: Optional[Callable[[int, int], None]] = None
    #: scheduling tier (docs/serving.md "Preemption & priorities"): HIGHER
    #: int = more important. The slot engine admits higher tiers first and
    #: — under optimistic KV admission — preempts strictly-lower tiers
    #: when the pool runs dry ("interactive preempts batch, never vice
    #: versa"). 0 (default) keeps pure FIFO; the bucket engine stores but
    #: ignores it.
    priority: int = 0
    #: tenant label for per-tenant resident-page fairness under preemption
    #: (victim selection prefers the tenant holding the most pool pages at
    #: equal priority). None = untagged.
    tenant: Optional[str] = None
    #: times this request was preempted (pages returned, requeued for a
    #: token-identical greedy replay) — ``serving.readmitted`` span events
    #: and the replay dedupe contract key off it
    preemptions: int = 0

    @property
    def ttft_from_s(self) -> float:
        return self.submitted_at if self.ttft_anchor_s is None else self.ttft_anchor_s

    @property
    def done(self) -> bool:
        return self.status != "queued"


class ServingEngine:
    """Request queue + scheduler over the bucketed generation executors.

    :param model: an ``AutoregressiveSequenceModel`` (CLM / symbolic audio).
    :param params: its parameter tree.
    :param config: default :class:`GenerationConfig` (per-request override
        via ``submit(..., config=...)``; only identical-config requests are
        packed into one micro-batch).
    :param table: the bucket grid; defaults to a powers-of-two grid up to
        the model's context length (:meth:`BucketTable.for_model`).
    :param rng: base PRNG key; each micro-batch uses a fresh split.
    :param max_queue: bounded-queue depth; ``submit`` past it raises
        :class:`QueueFull` and counts a shed. None = unbounded (offline use).
    :param default_deadline_s: deadline applied to requests submitted without
        an explicit ``deadline_s``; expired requests complete ``timed_out``.
    :param clock: monotonic time source. Tests and the chaos harness pass a
        :class:`~perceiver_io_tpu.reliability.FakeClock` so deadline expiry
        is deterministic; production uses the default ``time.monotonic``.
    :param chaos: optional fault-injection registry
        (:class:`~perceiver_io_tpu.reliability.ChaosRegistry`); None skips
        every hook.
    :param registry: metrics registry the engine's counters/histograms live
        on. Defaults to a private one (two engines must not double-count);
        pass a shared registry for unified export (the serve CLI does).
    :param tracer: optional span tracer — one trace per request, one
        terminal ``serving.request`` span per submission, one
        ``serving.batch`` span per micro-batch. None skips every span site.
    :param profiler_trigger: optional
        :class:`~perceiver_io_tpu.observability.ProfilerTrigger` watching
        the serving device path (this engine feeds it per-batch
        ``serving_device_execute_ms``; the slot engine feeds per-token
        ``serving_decode_step_ms``). When a p95 regression arms it, the
        NEXT device dispatch runs under a ``jax.profiler`` capture —
        the serve-side twin of the trainer wiring (docs/observability.md).
    :param decode_strategy: per-phase decode strategy forwarded to every
        ``generate()`` dispatch — ``"auto" | "cached" | "recompute"``
        (``inference/decode_strategy.py``). ``None`` defers to
        ``PERCEIVER_DECODE_STRATEGY`` then the measured registry. With an
        explicit ``"auto"``, :meth:`warmup` runs the boundary autotuner
        first so the deployment measures once and compiles against the
        winner.
    """

    def __init__(self, model, params, config: Optional[GenerationConfig] = None,
                 table: Optional[BucketTable] = None, *, rng: Optional[jax.Array] = None,
                 max_queue: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 chaos=None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 profiler_trigger=None,
                 decode_strategy: Optional[str] = None):
        from perceiver_io_tpu.inference import decode_strategy as _strategy

        if decode_strategy is not None and decode_strategy not in _strategy.MODES:
            raise ValueError(
                f"decode_strategy must be one of {_strategy.MODES}, "
                f"got {decode_strategy!r}"
            )
        self.decode_strategy = decode_strategy
        self.model = model
        self.params = params
        self.config = config or GenerationConfig()
        self.table = table or BucketTable.for_model(model)
        too_long = [L for L in self.table.prompt_lens if L > model.max_seq_len]
        if too_long:
            raise ValueError(
                f"prompt buckets {too_long} exceed the model context "
                f"length {model.max_seq_len}"
            )
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_queue = max_queue
        self.default_deadline_s = default_deadline_s
        self._clock = clock
        self._chaos = chaos
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._queue: List[ServeRequest] = []
        self._next_id = 0
        self._accepting = True
        self._cache0 = executor_cache_stats()
        # One source of truth for every counter/histogram (the old private
        # _completed/_shed/... ints). stats() reads these back and also
        # exposes the legacy key names as aliases.
        self.registry = registry if registry is not None else MetricsRegistry(clock=clock)
        self.registry.declare_counters(
            *STAT_ALIASES,
            "serving_prompt_tokens_real_total",
            "serving_prompt_tokens_padded_total",
            "serving_decode_rows_total",
            "serving_decode_rows_padded_total",
        )
        self.tracer = tracer
        self.profiler_trigger = profiler_trigger
        #: optional mirror for the per-token latency histograms
        #: (``serving_ttft_ms`` / ``serving_inter_token_ms``,
        #: docs/observability.md): called with ``(name, value_ms)`` after
        #: the engine's own registry observes. The fleet router installs
        #: one per replica so fleet-scope percentiles exist beside the
        #: per-replica ones, and an
        #: :class:`~perceiver_io_tpu.observability.slo.SLOMonitor`'s
        #: ``sink`` plugs in the same way.
        self.latency_sink: Optional[Callable[[str, float], None]] = None
        #: optional incident
        #: :class:`~perceiver_io_tpu.observability.FlightRecorder` — the
        #: slot engine fires its ``pool_exhausted`` seam when an admission
        #: stalls on KV pool blocks (docs/observability.md "Flight
        #: recorder & incident bundles"); None skips the seam, the same
        #: contract as ``tracer``/``chaos``
        self.flight_recorder = None
        #: optional scheduler step timeline
        #: (:class:`~perceiver_io_tpu.observability.StepTimeline`,
        #: docs/observability.md "Scheduler timeline & post-mortems"):
        #: when attached, every ``step()`` pass appends one structured
        #: record — admissions / token emissions / terminal dispositions
        #: this pass plus per-phase wall ms on the engine clock. None
        #: skips the seam entirely, the same contract as ``tracer``.
        self.timeline = None
        self._tl_draft: Optional[dict] = None  # per-pass event accumulator
        self._tl_marks: Optional[dict] = None  # per-pass phase marks

    # -- scheduler timeline seams -------------------------------------------
    def _tl_event(self, kind: str, **fields) -> None:
        """Accumulate one timeline event under ``kind`` for the pass in
        flight (or the NEXT pass for out-of-band calls like ``cancel()``
        between steps — deterministic either way)."""
        if self.timeline is None:
            return
        if self._tl_draft is None:
            self._tl_draft = {}
        self._tl_draft.setdefault(kind, []).append(fields)

    def _tl_mark(self, key: str, value) -> None:
        if self._tl_marks is not None:
            self._tl_marks[key] = value

    def _tl_mark_clock(self, key: str) -> None:
        """Phase-boundary clock mark — reads the clock ONLY when a pass is
        being recorded, so a timeline-less engine's step stays byte-
        identical (FakeClock drills included)."""
        if self._tl_marks is not None:
            self._tl_marks[key] = self._clock()

    def _run_pass(self, pass_fn):
        """Run one scheduler pass, appending its timeline record on every
        exit path (early returns and raises included)."""
        if self.timeline is None:
            return pass_fn()
        t0 = self._clock()
        self._tl_marks = {}
        try:
            return pass_fn()
        finally:
            self._tl_record(t0, self._clock())

    def _tl_record(self, t0: float, t1: float) -> None:
        """Build and append the bucket engine's per-pass record; the slot
        engine overrides this with its occupancy/pool shape."""
        draft, self._tl_draft = self._tl_draft, None
        marks, self._tl_marks = self._tl_marks or {}, None
        phases = {"total": round((t1 - t0) * 1e3, 3)}
        for key in ("assemble_ms", "execute_ms"):
            if key in marks:
                phases[key[: -len("_ms")]] = round(marks[key], 3)
        rec = {
            "engine": "bucket",
            "t_start_s": round(t0, 6),
            "t_end_s": round(t1, 6),
            "queue_depth": len(self._queue),
            "phases_ms": phases,
        }
        rec.update(draft or {})
        self.timeline.append(rec)

    def _observe_token_latency(self, name: str, value_ms: float) -> None:
        """One TTFT / inter-token observation: engine registry first (the
        scope ``stats()`` reads), then the optional mirror (replica → fleet
        scope, SLO monitor)."""
        self.registry.observe(name, value_ms)
        if self.latency_sink is not None:
            self.latency_sink(name, value_ms)

    def _device_capture(self, *, step=None):
        """Context for one device dispatch: a profiler capture when the
        trigger armed on the previous observation, else a shared no-op — so
        the capture shows a representative regressed dispatch, not the blip
        that armed it (the trainer-loop convention). ``step`` may be a
        zero-arg callable, evaluated only when a capture actually runs —
        keeps step-number bookkeeping off the unarmed per-token path."""
        trigger = self.profiler_trigger
        if trigger is None or not trigger.armed:
            return _NULL_CAPTURE
        try:
            return _SafeCapture(
                trigger.capture(step=step() if callable(step) else step)
            )
        except Exception:
            return _NULL_CAPTURE

    # -- queue front --------------------------------------------------------
    def submit(self, prompt, config: Optional[GenerationConfig] = None,
               *, deadline_s: Optional[float] = None,
               ttft_anchor_s: Optional[float] = None,
               on_token: Optional[Callable[[int, int], None]] = None,
               priority: int = 0, tenant: Optional[str] = None
               ) -> ServeRequest:
        """Enqueue one prompt (1-D token ids); returns its request handle.

        Raises ``ValueError`` for infeasible prompts (empty, or longer than
        the largest bucket / prefix capacity) at submit time — never inside
        bucket packing — and :class:`QueueFull` when the bounded queue is at
        ``max_queue`` (the request is shed and counted, not enqueued).
        ``ttft_anchor_s`` backdates the TTFT measurement to an earlier
        instant on the same clock (the fleet router passes its front-door
        submit time; the HTTP gateway its socket-accept time — see
        :class:`ServeRequest`). ``on_token`` installs the request's
        incremental token sink (:attr:`ServeRequest.on_token`).
        ``priority`` (higher = more important) and ``tenant`` tag the
        request for the slot engine's priority-ordered admission and
        preemption victim policy (docs/serving.md "Preemption &
        priorities"); this bucket engine stores them untouched.
        """
        if not self._accepting:
            raise RuntimeError("engine is draining; new submissions rejected")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cfg = config or self.config
        try:
            self.check_feasible(prompt, cfg)
        except ValueError as e:
            # infeasible submissions still get a terminal span + counter so
            # the CLI's per-line error records join against events.jsonl
            self.registry.inc("serving_requests_rejected_total")
            e.trace_id = self._terminal_event("rejected", error=str(e))
            raise
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self.registry.inc("serving_requests_shed_total")
            exc = QueueFull(
                f"queue depth {len(self._queue)} is at max_queue="
                f"{self.max_queue}; request shed — drain with step() or "
                "retry after backoff"
            )
            exc.trace_id = self._terminal_event(
                "shed", queue_depth=len(self._queue)
            )
            raise exc
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        now = self._clock()
        req = ServeRequest(
            self._next_id, prompt, cfg, now,
            deadline_at=None if deadline_s is None else now + deadline_s,
            trace_id=self.tracer.new_trace_id() if self.tracer else None,
            ttft_anchor_s=ttft_anchor_s,
            on_token=on_token,
            priority=int(priority), tenant=tenant,
        )
        self._next_id += 1
        self._queue.append(req)
        self.registry.inc("serving_requests_submitted_total")
        return req

    def check_feasible(self, prompt, config: Optional[GenerationConfig] = None
                       ) -> GenerationConfig:
        """Raise the precise ``ValueError`` this engine's ``submit`` would
        raise for an infeasible prompt (empty, longer than the largest
        bucket, or — via the subclass's ``_pick_prompt_bucket`` — out of the
        slot engine's scope), WITHOUT touching the queue or emitting spans;
        returns the resolved config. The fleet router shares it for
        fleet-level admission, so a request that no replica could ever serve
        rejects at the front door instead of bouncing between replicas.
        The slot engine's override additionally gates on KV-pool capacity
        (a single request's pages must all fit the pool, a physical bound
        prefix sharing cannot relax); the scheduler's admission gate is
        where shareable blocks enter the accounting — referenced prefix
        blocks are excluded from each admission's reservation, so
        hot-prefix residents pack concurrently (docs/serving.md "Prefix
        sharing"). Fleet replicas keep independent caches; replay after a
        failover re-prefills through the survivor's own index."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cfg = config or self.config
        if prompt.size == 0:
            raise ValueError("cannot serve an empty prompt")
        if prompt.size > self.table.prompt_lens[-1]:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the largest bucket "
                f"{self.table.prompt_lens[-1]}; extend the bucket table or "
                "truncate the prompt"
            )
        self._pick_prompt_bucket(int(prompt.size), cfg)  # fail fast, not mid-batch
        return cfg

    def _terminal_event(self, status: str, **attrs) -> Optional[str]:
        """Emit a terminal ``serving.request`` span for a submission that
        never became a queue entry (shed / rejected); returns its trace ID
        so the raising path can attach it to the exception."""
        if self.tracer is None:
            return None
        trace_id = self.tracer.new_trace_id()
        self.tracer.event("serving.request", trace_id=trace_id, status=status, **attrs)
        return trace_id

    def serve(self, prompts: Sequence, config: Optional[GenerationConfig] = None,
              *, rng: Optional[jax.Array] = None) -> List[Optional[np.ndarray]]:
        """Submit every prompt, drain the queue, return results in order.

        This batch convenience API is STRICT about failures: a ``failed``
        request (a real executor error, which ``step()`` isolates instead of
        propagating) re-raises here so callers like the bucketed pipeline
        surface the root cause instead of crashing on a None row. A
        ``timed_out`` request's slot holds None (only reachable when the
        engine has deadlines configured). Use ``submit``/``step``/``drain``
        directly for per-request fault handling."""
        if rng is not None:
            self._rng = rng
        reqs = [self.submit(p, config) for p in prompts]
        self.run_until_idle()
        failed = [r for r in reqs if r.status == "failed"]
        if failed:
            raise RuntimeError(
                f"{len(failed)} of {len(reqs)} served requests failed; "
                f"first error: {failed[0].error}"
            )
        return [r.result for r in reqs]

    def pending(self) -> bool:
        """True while a call to :meth:`step` has work to do. The bucket
        engine's unit of work is a whole micro-batch, so this is just queue
        depth; the slot engine overrides it to include resident slots (its
        ``step`` legitimately disposes of nothing mid-generation). Drive
        drain loops off this, not off ``step()``'s return value."""
        return bool(self._queue)

    def run_until_idle(self) -> int:
        """Drain the whole queue; returns the number of requests disposed of
        (completed + timed out + failed)."""
        served = 0
        while True:
            n = self.step()
            if n == 0:
                return served
            served += n

    def drain(self) -> int:
        """Graceful shutdown: stop accepting submissions, run every queued
        request to completion, return the number disposed of. Idempotent —
        a second call is a no-op returning 0."""
        self._accepting = False
        return self.run_until_idle()

    # -- streaming -----------------------------------------------------------
    def _emit_token(self, req: ServeRequest, index: int, token: int) -> None:
        """Deliver one token to the request's incremental sink. A raising
        sink (a torn-down stream consumer) is isolated and counted — the
        request it observes must finish normally."""
        try:
            req.on_token(index, token)
        except Exception:
            self.registry.inc("serving_token_sink_errors_total")

    def cancel(self, request_id: int) -> bool:
        """Withdraw one request — the streaming gateway's client-disconnect
        retirement route (docs/serving.md). A queued request leaves the
        queue and finishes ``cancelled`` (one terminal span, a
        ``serving.cancelled`` event, ``serving_requests_cancelled_total``).
        The bucket engine schedules whole micro-batches, so a request
        already packed into a running batch cannot be interrupted — it
        completes and the caller discards the result; the slot engine
        overrides this with token-granular mid-generation cancellation.
        Returns True when the request was found live and cancelled."""
        for i, req in enumerate(self._queue):
            if req.request_id == request_id:
                del self._queue[i]
                if self.tracer is not None:
                    self.tracer.event(
                        "serving.cancelled", trace_id=req.trace_id,
                        stage="queued", tokens_emitted=0,
                    )
                self._finish(req, "cancelled")
                return True
        return False

    def evacuate(self, cause: str = "scale_down") -> int:
        """Withdraw EVERY live request at once — the fleet scale-down path
        (docs/serving.md "Elasticity"): the router has already failed this
        engine's work over to survivors, so the local copies are stale and
        must be retired immediately rather than decoded to completion.
        Each finishes ``cancelled`` with one terminal span (the ``cause``
        attribute separates a scale-down evacuation from a client
        disconnect in the events stream). The bucket engine only holds
        queued work between steps; the slot engine overrides this to also
        retire residents and return their KV pool pages tagged ``cause``.
        Returns the number of requests evacuated."""
        evacuated = 0
        queued, self._queue = list(self._queue), []
        for req in queued:
            if self.tracer is not None:
                self.tracer.event(
                    "serving.cancelled", trace_id=req.trace_id,
                    stage="queued", tokens_emitted=0, cause=cause,
                )
            self._finish(req, "cancelled", error=f"evacuated ({cause})")
            evacuated += 1
        return evacuated

    # -- fault disposition ---------------------------------------------------
    def _finish(self, req: ServeRequest, status: str, *, error: Optional[str] = None) -> None:
        req.status = status
        req.error = error
        self._tl_event(
            "finished", request_id=req.request_id, status=status,
            tenant=req.tenant, priority=req.priority,
        )
        if status == "ok":
            self.registry.inc("serving_requests_completed_total")
        elif status == "timed_out":
            self.registry.inc("serving_requests_timed_out_total")
        elif status == "cancelled":
            self.registry.inc("serving_requests_cancelled_total")
        elif status == "failed":
            self.registry.inc("serving_requests_failed_total")
        now = self._clock()
        latency_s = now - req.submitted_at
        self.registry.observe("serving_request_latency_ms", latency_s * 1e3)
        if self.tracer is not None:
            # the request's ONE terminal span: submit time -> disposition.
            # The latency was measured on the ENGINE clock; backdate in the
            # tracer's own clock domain so the span duration stays correct
            # even when the two clocks differ (FakeClock engine + wall-clock
            # tracer, or vice versa).
            span = self.tracer.start_span(
                "serving.request", trace_id=req.trace_id,
                start_s=self.tracer.now() - latency_s,
                request_id=req.request_id,
                prompt_len=int(req.prompt.size),
            )
            self.tracer.end_span(
                span, status=status, **({"error": error} if error else {})
            )

    def _apply_request_chaos(self, req: ServeRequest) -> bool:
        """Run the per-request chaos hook (``serving.request``); returns True
        when the fault disposed of the request — an injected error fails it,
        a hang advances the injectable clock and times it out if that burned
        through its deadline. Shared by both engines' schedulers so fault
        semantics cannot drift between them."""
        if self._chaos is None:
            return False
        fault = self._chaos.hit("serving.request", req.request_id)
        if fault is None:
            return False
        if fault.kind == "error":
            self._finish(req, "failed", error=str(fault.make_error()))
            return True
        if fault.kind == "hang":
            # A hung request stalls its slot: advance the injectable clock
            # (FakeClock; a real monotonic clock can't be moved) and re-check
            # the deadline it just burned through.
            advance = getattr(self._clock, "advance", None)
            if advance is not None:
                advance(fault.delay_s)
            if req.deadline_at is not None and self._clock() >= req.deadline_at:
                self._finish(
                    req, "timed_out",
                    error=f"hung for {fault.delay_s}s past its deadline",
                )
                return True
        return False

    def _expire_overdue(self) -> int:
        """Complete every queue entry past its deadline as ``timed_out`` so
        expired requests never occupy a bucket slot."""
        now = self._clock()
        live: List[ServeRequest] = []
        expired = 0
        for req in self._queue:
            if req.deadline_at is not None and now >= req.deadline_at:
                self._finish(
                    req, "timed_out",
                    error=f"deadline exceeded after {now - req.submitted_at:.3f}s in queue",
                )
                expired += 1
            else:
                live.append(req)
        self._queue = live
        return expired

    # -- scheduler ----------------------------------------------------------
    def _pick_prompt_bucket(self, length: int, cfg: GenerationConfig) -> int:
        """Smallest prompt bucket that fits ``length`` AND the model's
        prefix capacity under ``cfg`` (``generate`` rejects plans whose
        nominal prefix ``L - min(L, num_latents)`` exceeds
        ``max_prefix_len``)."""
        max_prefix = self.model.max_prefix_len
        for cap in self.table.prompt_lens:
            if cap < length:
                continue
            if cap - min(cap, cfg.num_latents) > max_prefix:
                continue
            return cap
        raise ValueError(
            f"no feasible prompt bucket for length {length} with "
            f"num_latents={cfg.num_latents}: buckets {self.table.prompt_lens} "
            f"must satisfy len <= {self.model.max_seq_len} and "
            f"len - num_latents <= max_prefix_len={max_prefix}"
        )

    def step(self) -> int:
        """Run ONE micro-batch: the queue head plus following requests with
        the same config, packed FIFO into the next bucket slot. Returns the
        number of requests disposed of — completed, timed out, or failed
        (0 = queue empty).

        Fault isolation: requests past their deadline finish ``timed_out``
        before packing; a chaos-injected per-request fault finishes only
        that request ``failed``; an exception out of the executor (real or
        injected) fails every request in this micro-batch but leaves the
        rest of the queue intact.
        """
        return self._run_pass(self._step_pass)

    def _step_pass(self) -> int:
        disposed = self._expire_overdue()
        if not self._queue:
            return disposed
        cfg = self._queue[0].config
        picked: List[ServeRequest] = []
        rest: List[ServeRequest] = []
        for req in self._queue:
            if len(picked) >= self.table.batch_sizes[-1] or req.config != cfg:
                rest.append(req)
                continue
            if self._apply_request_chaos(req):
                disposed += 1
                continue
            picked.append(req)
        self._queue = rest
        if not picked:
            return disposed

        b = self.table.batch_bucket(len(picked))
        length = self._pick_prompt_bucket(max(r.prompt.size for r in picked), cfg)
        assemble_t0 = self._clock()
        ids = np.full((b, length), cfg.pad_token_id, np.int32)
        # Dummy filler rows claim zero pads — a full-width "prompt" of pad-id
        # tokens whose output is computed and dropped. Zero, not length-1:
        # ``generate`` enables the cached prefix-growth phase only when EVERY
        # row's pad count fits the nominal prefix (``phase2_ok``), so a
        # max-padded filler would silently demote an underfilled micro-batch
        # to the slow windowed-recompute plan. Attention is per-row; filler
        # content never touches real rows.
        pad_count = np.zeros((b,), np.int32)
        now = self._clock()
        for i, req in enumerate(picked):
            ids[i, length - req.prompt.size:] = req.prompt
            pad_count[i] = length - req.prompt.size
            req.started_at = now
            self.registry.observe(
                "serving_queue_wait_ms", (now - req.submitted_at) * 1e3
            )

        self._rng, key = jax.random.split(self._rng)
        batch_index = int(self.registry.inc("serving_batches_total"))
        assemble_ms = (self._clock() - assemble_t0) * 1e3
        self.registry.observe("serving_batch_assembly_ms", assemble_ms)
        self._tl_mark("assemble_ms", assemble_ms)
        if self.timeline is not None:
            for req in picked:
                self._tl_event(
                    "admitted", request_id=req.request_id,
                    tenant=req.tenant, priority=req.priority,
                    bucket=[b, length],
                )
        batch_span = None
        if self.tracer is not None:
            batch_span = self.tracer.start_span(
                "serving.batch", batch_index=batch_index, size=len(picked),
                bucket=[b, length], assemble_ms=round(assemble_ms, 3),
                trace_ids=[r.trace_id for r in picked],
            )
        execute_t0 = self._clock()
        try:
            batch_fault = self._chaos.hit("serving.batch") if self._chaos else None
            if batch_fault is not None and batch_fault.kind == "error":
                raise batch_fault.make_error()
            with self._device_capture(step=batch_index):
                out = np.asarray(
                    generate(
                        self.model, self.params, jnp.asarray(ids), cfg,
                        rng=key, prompt_pad_count=jnp.asarray(pad_count),
                        decode_strategy=self.decode_strategy,
                    )
                )
        except Exception as e:
            # Executor failure: this micro-batch fails, the queue survives.
            self.registry.observe(
                "serving_device_execute_ms", (self._clock() - execute_t0) * 1e3
            )
            if batch_span is not None:
                self.tracer.end_span(
                    batch_span, status="failed", error=f"{type(e).__name__}: {e}"
                )
            for req in picked:
                self._finish(req, "failed", error=f"{type(e).__name__}: {e}")
            return disposed + len(picked)
        # np.asarray above materialized the result, so this is device time
        # plus dispatch — the per-batch execute phase of the trace.
        execute_ms = (self._clock() - execute_t0) * 1e3
        self.registry.observe("serving_device_execute_ms", execute_ms)
        self._tl_mark("execute_ms", execute_ms)
        if self.profiler_trigger is not None:
            self.profiler_trigger.observe(execute_ms)
        if batch_span is not None:
            self.tracer.end_span(batch_span, execute_ms=round(execute_ms, 3))
        # Per-request token-latency accounting (docs/observability.md): the
        # bucket engine is batch-granular — every token of the micro-batch
        # materializes at the np.asarray fence above — so TTFT is submit →
        # batch completion and inter-token latency is the amortized device
        # time per generated token, ONE sample per request (a per-token
        # observation would just repeat the same amortized value). The slot
        # engine records both per real token step.
        done_at = self._clock()
        itl_ms = execute_ms / max(1, cfg.max_new_tokens)
        for i, req in enumerate(picked):
            req.result = out[i]
            if req.on_token is not None:
                # batch-granular streaming: the whole row materialized at
                # the fence above, so the sink gets every real token now —
                # the row up to and including the first EOS (pad after EOS
                # is filler, never a generated token)
                toks = out[i].tolist()
                eos = cfg.eos_token_id
                if eos is not None and eos in toks:
                    toks = toks[: toks.index(eos) + 1]
                for idx, t in enumerate(toks):
                    self._emit_token(req, idx, int(t))
            ttft_ms = (done_at - req.ttft_from_s) * 1e3
            self._observe_token_latency("serving_ttft_ms", ttft_ms)
            self._observe_token_latency("serving_inter_token_ms", itl_ms)
            if self.timeline is not None:
                self._tl_event(
                    "tokens", request_id=req.request_id, first=True,
                    ttft_ms=round(ttft_ms, 3), itl_ms=round(itl_ms, 3),
                    batch_granular=True,
                )
            if self.tracer is not None:
                self.tracer.event(
                    "serving.first_token", trace_id=req.trace_id,
                    ttft_ms=round(ttft_ms, 3),
                    inter_token_ms=round(itl_ms, 3), batch_granular=True,
                )
            self._finish(req, "ok")
        self.registry.inc(
            "serving_tokens_generated_total", len(picked) * cfg.max_new_tokens
        )
        self.registry.inc(
            "serving_prompt_tokens_real_total",
            sum(int(r.prompt.size) for r in picked),
        )
        self.registry.inc("serving_prompt_tokens_padded_total", b * length)
        # decode-row accounting, comparable with the slot engine's: every
        # row of every decode step, split real vs batch-padding filler —
        # the padding-waste ratio `stats()` reports
        self.registry.inc("serving_decode_rows_total", b * cfg.max_new_tokens)
        self.registry.inc(
            "serving_decode_rows_padded_total",
            (b - len(picked)) * cfg.max_new_tokens,
        )
        return disposed + len(picked)

    # -- ahead-of-time warmup ----------------------------------------------
    def warmup(self, config: Optional[GenerationConfig] = None) -> int:
        """Compile every feasible bucket before accepting traffic; returns
        the number of fresh executor compiles.

        Each ``(batch, prompt_len)`` cell is driven through ``generate``
        with BOTH phase plans it can map to at serve time: zero left pads
        (prefix-growth cache eligible) and maximal left pads (pad overflow
        beyond the nominal prefix disables phase 2, a different static
        plan). Cells infeasible under ``config`` (prefix capacity) are
        skipped — serve-time scheduling skips them identically."""
        cfg = config or self.config
        before = executor_cache_stats()["misses"]
        if self.decode_strategy == "auto":
            # measure the boundary winner ONCE before compiling the grid, so
            # every warmed executor is the plan steady-state traffic uses
            # (the probe's two small generation compiles count in the return)
            from perceiver_io_tpu.inference import decode_strategy as _strategy

            _strategy.autotune_boundary(self.model, self.params)
        max_prefix = self.model.max_prefix_len
        for b, length in self.table.grid():
            nominal_prefix = length - min(length, cfg.num_latents)
            if nominal_prefix > max_prefix:
                continue
            pad_variants = {0}
            if length - 1 > nominal_prefix:
                pad_variants.add(length - 1)
            for pad in pad_variants:
                ids = jnp.full((b, length), cfg.pad_token_id, jnp.int32)
                pad_count = jnp.full((b,), pad, jnp.int32)
                generate(self.model, self.params, ids, cfg,
                         rng=jax.random.PRNGKey(0), prompt_pad_count=pad_count,
                         decode_strategy=self.decode_strategy)
        return executor_cache_stats()["misses"] - before

    # -- observability ------------------------------------------------------
    def stats(self) -> dict:
        """Serving counters since engine construction, read back from the
        metrics registry (the one source of truth). Every counter appears
        under its canonical registry name (``serving_*_total``) AND its
        legacy short key (``completed``, ``shed``, ... — deprecation
        aliases; see ``STAT_ALIASES`` / docs/observability.md).

        ``compiles`` is the executor-cache miss delta — the engine assumes
        it owns the process's generation traffic over its lifetime (true for
        the CLI and tests)."""
        cache_now = executor_cache_stats()
        # clamp at 0: reset_executor_caches() mid-lifetime rewinds the global
        # counters below this engine's construction-time snapshot
        cache = {k: max(0, cache_now[k] - self._cache0[k]) for k in cache_now}
        reg = self.registry
        # one consistent read, not 16 separate ones: a scrape thread polling
        # stats() mid-step must still see alias == canonical for every pair
        # (counters(), not snapshot() — no histogram sorting under the lock)
        counts = reg.counters()
        counters = {
            alias: int(counts.get(name, 0)) for name, alias in STAT_ALIASES.items()
        }
        counters.update(
            {name: int(counts.get(name, 0)) for name in STAT_ALIASES}
        )
        real = counts.get("serving_prompt_tokens_real_total", 0)
        padded = counts.get("serving_prompt_tokens_padded_total", 0)
        # compile-ledger rollup (docs/observability.md): the full per-key
        # compile/memory table stays on default_ledger().snapshot() — the
        # serve CLI embeds it in serve_stats; stats() carries the summary
        # so a poller sees compile cost and retrace reasons without the
        # per-record bulk
        from perceiver_io_tpu.observability import default_ledger

        ledger = default_ledger().rollup()
        out = {
            **counters,
            "queued": len(self._queue),
            "compiles": cache["misses"],
            "executor_cache": cache,
            "compile_ledger": ledger,
            # registry.percentile is the LOCKED accessor — stats() may be
            # polled from a scrape thread while the owner thread observes
            "queue_wait_ms": {
                "p50": _round_ms(reg.percentile("serving_queue_wait_ms", 50.0)),
                "p95": _round_ms(reg.percentile("serving_queue_wait_ms", 95.0)),
            },
            # the SLO-facing token latencies (docs/observability.md): TTFT
            # and inter-token latency, per-token on the slot engine,
            # batch-amortized on this one
            "ttft_ms": {
                "p50": _round_ms(reg.percentile("serving_ttft_ms", 50.0)),
                "p95": _round_ms(reg.percentile("serving_ttft_ms", 95.0)),
            },
            "inter_token_ms": {
                "p50": _round_ms(reg.percentile("serving_inter_token_ms", 50.0)),
                "p95": _round_ms(reg.percentile("serving_inter_token_ms", 95.0)),
            },
            "prompt_padding_efficiency": round(real / max(1, padded), 4),
            "bucket_grid": {
                "prompt_lens": list(self.table.prompt_lens),
                "batch_sizes": list(self.table.batch_sizes),
            },
        }
        if self.timeline is not None:
            # scheduler-timeline rollup (docs/observability.md "Scheduler
            # timeline & post-mortems"): pass/event totals over the ring
            out["timeline"] = self.timeline.summary()
        return out

    def health(self) -> dict:
        """Readiness snapshot for a serving front end: ``ready`` means the
        engine accepts a submission right now (not draining, queue below
        ``max_queue``). Cheap — no device work, no cache reads."""
        now = self._clock()
        depth = len(self._queue)
        reg = self.registry
        return {
            "ready": self._accepting
            and (self.max_queue is None or depth < self.max_queue),
            "accepting": self._accepting,
            "queue_depth": depth,
            "max_queue": self.max_queue,
            "oldest_wait_ms": round(
                max((now - r.submitted_at) for r in self._queue) * 1e3, 3
            ) if self._queue else 0.0,
            "completed": int(reg.counter("serving_requests_completed_total")),
            "shed": int(reg.counter("serving_requests_shed_total")),
            "timed_out": int(reg.counter("serving_requests_timed_out_total")),
            "failed": int(reg.counter("serving_requests_failed_total")),
            "cancelled": int(reg.counter("serving_requests_cancelled_total")),
        }

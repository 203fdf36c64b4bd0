"""The trainer loop — Lightning-free equivalent of the reference's
``Trainer.fit(model, datamodule)`` flow (reference
``perceiver/scripts/cli.py``, ``perceiver/model/core/lightning.py``):

step-based training with periodic validation, best-``val_loss`` orbax
checkpointing, learning-rate + loss logging (TensorBoard when torch is
importable, JSONL always), and rank-0 end-of-validation callbacks (the
qualitative text-sampling hooks, reference ``clm/lightning.py:113-151``).

The loop body is host-side Python; every numeric step is one jitted SPMD
call. Metrics are device scalars fetched once per log interval so logging
never stalls the device queue (Lightning's ``sync_dist=True`` reduction is
implicit: metric arrays are replicated outputs of the sharded step).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback
from collections import deque
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from perceiver_io_tpu.observability import MetricsRegistry, default_ledger, default_registry

from perceiver_io_tpu.parallel import (
    TrainState,
    create_train_state,
    make_eval_step,
    make_train_step,
    shard_or_assemble,
)
from perceiver_io_tpu.training.checkpoint import (
    BestCheckpointManager,
    ResumeCheckpointManager,
)


@dataclasses.dataclass
class TrainerConfig:
    """Trainer hyperparameters (the ``--trainer.*`` surface of the reference
    CLI, reference ``perceiver/scripts/trainer.yaml``)."""

    max_steps: int
    val_check_interval: int = 1000
    log_every_n_steps: int = 50
    limit_val_batches: Optional[int] = None
    limit_test_batches: Optional[int] = None
    default_root_dir: str = "logs"
    max_checkpoints: int = 1
    grad_clip_norm: Optional[float] = None
    #: split each batch into N microbatches and average their gradients
    #: inside the jitted step. NOTE: unlike Lightning's
    #: ``accumulate_grad_batches`` (which multiplies the loader batch), this
    #: DIVIDES the given batch — pass the full effective batch size and use
    #: this knob to bound activation memory per microbatch
    grad_accum_steps: int = 1
    #: run N optimizer steps per device program (``lax.scan`` over stacked
    #: batches) — amortizes host dispatch latency; steps that need host-side
    #: work (validation, snapshots, profiling) automatically run singly.
    #: Trades preemption-response latency (≤ N steps) for throughput.
    steps_per_execution: int = 1
    seed: int = 0
    enable_checkpointing: bool = True
    enable_tensorboard: bool = True
    #: shard the sequence dim of batches over the ``seq`` mesh axis
    #: (context parallelism; XLA partitions attention over kv accordingly)
    shard_seq: bool = False
    #: capture a jax.profiler trace of _PROFILE_WINDOW steps starting here
    #: into <default_root_dir>/profile (None disables)
    profile_start: Optional[int] = None
    #: snapshot the full TrainState (step, params, optimizer state) every N
    #: steps into <default_root_dir>/resume for mid-training resume
    save_state_every_n_steps: Optional[int] = None
    #: resume from the latest TrainState snapshot in this directory (a
    #: <root>/resume dir, or a root containing one) — Lightning
    #: ``fit(ckpt_path=...)`` parity; the loss trajectory of a resumed run
    #: matches the uninterrupted run exactly (per-step rng is fold_in-derived
    #: and the data stream is fast-forwarded)
    resume: Optional[str] = None
    #: halt when the train loss goes non-finite — checked at each log flush
    #: and before every TrainState snapshot (a diverged state is never
    #: snapshotted, so existing snapshots stay a finite resume point); the
    #: device queue is never stalled per-step (Lightning ``detect_anomaly``
    #: role). ``False`` disables all non-finite handling (policy ``off``)
    #: unless ``non_finite_policy`` is explicitly skip/rollback.
    terminate_on_non_finite: bool = True
    #: what a non-finite train loss does (docs/reliability.md):
    #: ``halt`` (raise at the log flush — the historical behavior),
    #: ``skip`` (discard that step's update, keep the last-good state, count
    #: it in ``Trainer.fault_stats``), or ``rollback`` (skip, and after
    #: ``non_finite_rollback_after`` consecutive bad steps restore the latest
    #: finite TrainState snapshot and fast-forward the data stream — requires
    #: ``save_state_every_n_steps``). skip/rollback check the loss every step
    #: (one device fetch per step) and force ``steps_per_execution=1``
    #: scheduling, trading dispatch throughput for recoverability. NOTE:
    #: rollback pins roughly ``save_state_every_n_steps +
    #: non_finite_rollback_after`` recent batches in host memory (the
    #: exact-replay buffer) — budget the snapshot cadence accordingly
    #: (e.g. a 5000-step cadence with 2 MB batches pins ~10 GB host RAM).
    non_finite_policy: str = "halt"
    #: K consecutive non-finite steps trigger the policy's escalation:
    #: ``rollback`` restores the latest snapshot, ``skip`` halts (a streak
    #: that long is persistent divergence, not a transient fault)
    non_finite_rollback_after: int = 3
    #: give up (raise) after this many rollbacks in one fit — a persistent
    #: divergence is a hyperparameter problem, not a transient fault
    non_finite_max_rollbacks: int = 3


#: steps traced per jax.profiler capture: [profile_start, profile_start + _PROFILE_WINDOW)
_PROFILE_WINDOW = 3

#: the phases whose seconds ``_span("trainer.<phase>")`` adds to
#: ``trainer_<phase>_seconds_total``: those in which the loop waits for
#: something else (the stream; the device, at a log flush) and the making of
#: the state when a ``fit`` starts
_WAITS = ("data_wait", "log_flush")
_TIMED = _WAITS + ("setup_state",)
#: counters kept on the process-wide registry as well as on the trainer's
#: own, so that they can be read after the trainer is gone
_PROCESS_WIDE = ("trainer_steps_total",) + tuple(f"trainer_{p}_seconds_total" for p in _WAITS)
#: the same, declared when a ``fit`` begins: the start of a fit by phase
#: (``_dispatching`` splits a step function's first dispatch) and the steps
#: that compiled again
_FIT_START = (
    "trainer_setup_state_seconds_total",
    "trainer_first_step_seconds_total",
    "trainer_first_step_lower_seconds_total",
    "trainer_first_step_backend_seconds_total",
    "trainer_step_recompiles_total",
)


def _check_uniform_block(block, k_exec: int) -> None:
    """Fused multi-step blocks np.stack ``k_exec`` batches — a user-supplied
    iterable yielding ragged batches would otherwise die in an opaque
    broadcast error deep inside tree_map. Built-in loaders use
    ``drop_last=True``; arbitrary ``fit()`` iterables must match it."""
    ref = block[0]
    ref_structure = jax.tree_util.tree_structure(ref)
    ref_shapes = [np.shape(leaf) for leaf in jax.tree_util.tree_leaves(ref)]
    for i, b in enumerate(block[1:], 1):
        structure = jax.tree_util.tree_structure(b)
        shapes = [np.shape(leaf) for leaf in jax.tree_util.tree_leaves(b)]
        if structure != ref_structure or shapes != ref_shapes:
            raise ValueError(
                f"steps_per_execution={k_exec} requires fixed-shape batches, "
                f"but batch {i} of the block has leaves {shapes} vs the "
                f"block's first batch {ref_shapes} — use a loader that drops "
                "or pads the last partial batch (built-in loaders use "
                "drop_last=True)"
            )


@jax.jit
def _params_finite(params) -> jnp.ndarray:
    """Device-side all-finite reduction over a param tree (one fused pass;
    used to guard TrainState snapshots against persisting diverged state)."""
    leaves = [
        jnp.isfinite(x).all()
        for x in jax.tree_util.tree_leaves(params)
        if jnp.issubdtype(x.dtype, jnp.floating)
    ]
    return jnp.all(jnp.stack(leaves)) if leaves else jnp.asarray(True)


def _effective_non_finite_policy(cfg: TrainerConfig) -> str:
    """halt | skip | rollback | off. ``terminate_on_non_finite=False`` keeps
    its historical meaning (no checks at all) unless the new policy field is
    explicitly set to a recovering mode."""
    if cfg.non_finite_policy not in ("halt", "skip", "rollback"):
        raise ValueError(
            f"non_finite_policy must be halt|skip|rollback, got "
            f"{cfg.non_finite_policy!r}"
        )
    if cfg.non_finite_policy != "halt":
        return cfg.non_finite_policy
    return "halt" if cfg.terminate_on_non_finite else "off"


class _BatchStream:
    """The trainer's seekable view of ``train_data``: cycles on exhaustion
    (rejecting one-shot generators), counts batches handed out
    (``position``, 0-based), fast-forwards to a resume point, and — when a
    replay buffer is enabled — rewinds to a recent position so the rollback
    policy replays the exact batches the rolled-back steps consumed.

    The rewind never touches the underlying iterable: handed-out batches are
    retained in a bounded deque and replayed from memory, after which the
    live iterator resumes exactly where it left off. That keeps rollback
    correct for *any* iterable (lists, loaders, streaming pipelines) at the
    cost of ``replay_buffer`` batches of host memory.
    """

    def __init__(self, data: Iterable, *, replay_buffer: int = 0):
        self._data = data
        self._iter = iter(data)
        self.position = 0  # index of the next batch next() hands out
        self._pulled = 0  # batches pulled off the underlying iterator
        self._buffer: Optional[deque] = (
            deque(maxlen=replay_buffer) if replay_buffer > 0 else None
        )
        self._replay: deque = deque()

    def _pull(self):
        try:
            return next(self._iter)
        except StopIteration:
            self._iter = iter(self._data)
            try:
                return next(self._iter)
            except StopIteration:
                raise ValueError(
                    "train_data is exhausted and not re-iterable "
                    "(one-shot generator?); pass a list or a loader"
                ) from None

    def next(self):
        if self._replay:
            pos, batch = self._replay.popleft()
            self.position = pos + 1
            return batch
        batch = self._pull()
        if self._buffer is not None:
            self._buffer.append((self.position, batch))
        self.position += 1
        self._pulled = self.position
        return batch

    def fast_forward(self, n: int) -> None:
        """Position a FRESH stream so the next batch is batch ``n`` — the
        resume replay. Loaders with a ``skip_batches`` hook jump in O(1);
        anything else is consumed batch by batch."""
        if n <= 0:
            return
        if hasattr(self._data, "skip_batches") and hasattr(self._data, "__len__"):
            self._data.skip_batches(n)
            self._iter = iter(self._data)
            self.position = self._pulled = n
        else:
            for _ in range(n):
                self.next()

    def rewind_to(self, n: int) -> None:
        """Re-position so the next batch handed out is batch ``n`` again,
        replaying retained batches (rollback fast-forward). Everything
        already pulled off the underlying iterator — including batches ahead
        of ``position`` left over from an earlier rewind — must replay from
        the buffer, because the live iterator cannot be stepped back."""
        if n > self.position:
            raise ValueError(f"rewind_to({n}) is ahead of position {self.position}")
        entries = dict(self._buffer or ())
        entries.update(self._replay)
        wanted = sorted((p, b) for p, b in entries.items() if p >= n)
        if [p for p, _ in wanted] != list(range(n, self._pulled)):
            raise RuntimeError(
                f"rollback to batch {n} exceeds the replay buffer (retained "
                f"{[p for p, _ in wanted]}, pulled {self._pulled}); raise the "
                "snapshot cadence coverage or lower non_finite_rollback_after"
            )
        self._replay = deque(wanted)
        self.position = n


class Trainer:
    """Step-based fit/validate driver.

    :param loss_fn: ``(params, batch, rng) -> (loss, metrics)`` (one of
        :mod:`perceiver_io_tpu.training.tasks`).
    :param callbacks: callables ``(trainer, state, step, val_metrics)`` run on
        process 0 after each validation pass. A raising callback is logged
        and counted (``fault_stats["callback_errors"]``), never fatal.
    :param chaos: optional fault-injection registry
        (:class:`~perceiver_io_tpu.reliability.ChaosRegistry`); consulted
        once per optimizer step at the ``trainer.step`` site. None (the
        default) skips the hook entirely.
    :param registry: metrics registry the trainer's counters/histograms live
        on (``trainer_steps_total``, ``trainer_step_ms``, fault counters...);
        defaults to a private one (docs/observability.md).
        ``trainer_steps_total``, ``trainer_data_wait_seconds_total``,
        ``trainer_log_flush_seconds_total`` and, from the first ``fit`` on,
        the start-up counters and ``trainer_step_recompiles_total`` are
        counted on ``default_registry()`` as well.
    :param tracer: optional :class:`~perceiver_io_tpu.observability.Tracer`
        — one trace per ``fit``: ``trainer.setup_state``, a
        ``trainer.first_step`` around each step function's first dispatch
        (attributes ``trace_s``, ``lower_s``, ``backend_s``, ``cache``) and
        per-step ``trainer.data_wait`` / ``trainer.step`` /
        ``trainer.log_flush`` / ``trainer.checkpoint`` spans. With or
        without one, each is a ``jax.profiler`` annotation of the span's
        name, and the start of a fit is counted by phase
        (docs/observability.md "The start of a fit").
    :param profiler_trigger: optional
        :class:`~perceiver_io_tpu.observability.ProfilerTrigger` — fed each
        single step's host time; when the p95 regresses, the next step runs
        under a ``jax.profiler`` capture.
    :param snapshot_writer: optional
        :class:`~perceiver_io_tpu.observability.SnapshotWriter` — cadence
        checked at every log flush, forced once at ``fit`` exit.
    """

    def __init__(
        self,
        config: TrainerConfig,
        mesh,
        loss_fn: Callable,
        tx: optax.GradientTransformation,
        *,
        model_config: Any = None,
        lr_schedule: Optional[optax.Schedule] = None,
        callbacks: Sequence[Callable] = (),
        chaos=None,
        registry: Optional[MetricsRegistry] = None,
        tracer=None,
        profiler_trigger=None,
        snapshot_writer=None,
    ):
        self.config = config
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.tx = tx
        self.model_config = model_config
        self.lr_schedule = lr_schedule
        self.callbacks = list(callbacks)
        self.state: Optional[TrainState] = None
        self._shardings = None
        self._ckpt: Optional[BestCheckpointManager] = None
        self._eval_step = None
        self._tb = None
        self._metrics_file = None
        self._chaos = chaos
        self._policy = _effective_non_finite_policy(config)
        self.registry = registry if registry is not None else MetricsRegistry()
        self.registry.declare_counters(
            *_PROCESS_WIDE,
            "trainer_skipped_steps_total",
            "trainer_rollbacks_total",
            "trainer_callback_errors_total",
        )
        default_registry().declare_counters(*_PROCESS_WIDE)
        self._tracer = tracer
        self._fit_trace: Optional[str] = None
        self._profiler_trigger = profiler_trigger
        self._snapshot_writer = snapshot_writer
        #: fault-recovery counters for this trainer's lifetime (kept as a
        #: plain dict for compatibility; each increment is mirrored onto the
        #: registry under ``trainer_*_total``)
        self.fault_stats = {"skipped_steps": 0, "rollbacks": 0, "callback_errors": 0}

        if config.enable_checkpointing:
            # Created on EVERY process: orbax save of multi-host sharded
            # arrays is a collective (each host writes its own shards).
            self._ckpt = BestCheckpointManager(
                os.path.join(config.default_root_dir, "checkpoints"),
                max_to_keep=config.max_checkpoints,
            )
        self._open_writers()

    @property
    def is_main_process(self) -> bool:
        """``rank_zero_only`` parity (reference ``clm/lightning.py:113``)."""
        return jax.process_index() == 0

    @contextlib.contextmanager
    def _span(self, name: str, **attrs):
        """One phase of a fit, ``trainer.<phase>``: an annotation of that
        name on the profiler's clock and, with a tracer, a span under this
        fit's trace (``Tracer.span`` enters the annotation itself), which is
        what the body is given (None without a tracer). The seconds of the
        ``_TIMED`` go to ``trainer_<phase>_seconds_total``."""
        if self._tracer is None:
            cm = jax.profiler.TraceAnnotation(name)
        else:
            cm = self._tracer.span(name, trace_id=self._fit_trace, **attrs)
        phase = name.partition(".")[2]
        t0 = time.perf_counter()
        try:
            with cm as span:
                yield span if self._tracer is not None else None
        finally:
            if phase in _TIMED:
                self._count(f"trainer_{phase}_seconds_total", time.perf_counter() - t0)

    @contextlib.contextmanager
    def _dispatching(self, step_fn, step_idx: int, **attrs):
        """``trainer.step`` around one dispatch of ``step_fn``, and what the
        dispatch built besides. The first dispatch of a step function in a
        fit is the call in which ``jax.jit`` traces, lowers and compiles or
        loads before it returns: it runs under ``trainer.first_step`` too,
        whose seconds are split by the ledger's readings of JAX's own
        events before and after into lowering, the backend and the rest
        (tracing, the cache's key, the hand-off), counted, written on the
        span and logged as one ``startup/`` row. No fence: what the device
        then runs is ``trainer.step``'s business or nobody's. A backend
        compile heard during any later dispatch is a step that compiled
        again (a batch of another shape): counted and logged."""
        ledger = default_ledger()
        if step_fn not in self._dispatched:
            self._dispatched.add(step_fn)
            before = ledger.jax_totals()
            with self._span("trainer.first_step", step=step_idx) as first:
                t0 = time.perf_counter()
                with self._span("trainer.step", step=step_idx, parent=first, **attrs):
                    yield
                seconds = time.perf_counter() - t0
                built = {k: v - before[k] for k, v in ledger.jax_totals().items()}
                lower_s, backend_s = built["lower_s"], built["backend_s"]
                # JAX times its events on another clock: never below nought
                trace_s = max(0.0, seconds - lower_s - backend_s)
                seconds = trace_s + lower_s + backend_s
                hit = 0 < built["backend_compiles"] <= built["cache_hits"]
                if first is not None:
                    first.attrs.update(trace_s=trace_s, lower_s=lower_s, backend_s=backend_s,
                                       cache="hit" if hit else "miss")
            self._count("trainer_first_step_seconds_total", seconds)
            self._count("trainer_first_step_lower_seconds_total", lower_s)
            self._count("trainer_first_step_backend_seconds_total", backend_s)
            self.log_metrics(step_idx, {
                "setup_state_s": self._setup_state_s,
                "first_step_s": seconds, "trace_s": trace_s, "lower_s": lower_s,
                "backend_s": backend_s, "cache_hit": float(hit),
            }, prefix="startup/")
            return
        compiles = ledger.backend_compiles()
        with self._span("trainer.step", step=step_idx, **attrs):
            yield
        if ledger.backend_compiles() != compiles:
            self._count("trainer_step_recompiles_total")
            self.log_metrics(step_idx, {"step_recompiled_at": step_idx})

    def _count(self, name: str, value: float = 1.0) -> None:
        """Add to one of the ``_PROCESS_WIDE`` or ``_FIT_START`` counters,
        here and on the process-wide registry."""
        self.registry.inc(name, value)
        if self.registry is not default_registry():
            default_registry().inc(name, value)

    def _gauge(self, name: str, value: float) -> None:
        """Set a gauge here and on the process-wide registry."""
        self.registry.set_gauge(name, value)
        if self.registry is not default_registry():
            default_registry().set_gauge(name, value)

    def _write_op_scopes(self, trace_dirs) -> None:
        """Beside each profiler capture of this ``fit``, ``op_scopes.json``
        (the model scope, ``op_name``, of each device operation the trace
        names by its instruction) and ``fused_scopes.json`` (what each
        fusion holds besides). Lowers and compiles the step once more, which
        takes about what a warm start spends on it: done once, when the loop
        has ended, and only where something was captured."""
        if not trace_dirs or not self.is_main_process:
            return
        ledger = default_ledger()
        tables = {"op_scopes.json": ledger.op_scopes("trainer.step"),
                  "fused_scopes.json": ledger.fused_scopes("trainer.step")}
        if not tables["op_scopes.json"]:
            return
        for trace_dir in trace_dirs:
            os.makedirs(trace_dir, exist_ok=True)
            for name, table in tables.items():
                with open(os.path.join(trace_dir, name), "w") as f:
                    json.dump(table, f)

    def _count_fault(self, key: str) -> None:
        """Increment one ``fault_stats`` counter and its registry mirror."""
        self.fault_stats[key] += 1
        self.registry.inc(f"trainer_{key}_total")

    def _record_step_time(self, step_ms: float, trigger) -> None:
        """One home for the fenced/dispatch metric-name split and the
        trigger feed — the fused and single-step paths must never diverge
        on it. Without a trigger nothing syncs per step, so the honest
        export name is dispatch time; the fenced name only exists when the
        trigger forced the per-step sync."""
        self.registry.observe(
            "trainer_step_ms" if trigger is not None
            else "trainer_step_dispatch_ms",
            step_ms,
        )
        if trigger is not None:
            trigger.observe(step_ms)

    def _open_writers(self) -> None:
        """(Re)open the rank-0 metrics JSONL + TensorBoard writers — called
        at construction and again by ``fit`` after a previous fit closed
        them (metrics.jsonl is append-mode, so re-fitting appends)."""
        if not self.is_main_process:
            return
        cfg = self.config
        os.makedirs(cfg.default_root_dir, exist_ok=True)
        if self._metrics_file is None:
            self._metrics_file = open(
                os.path.join(cfg.default_root_dir, "metrics.jsonl"), "a"
            )
        if cfg.enable_tensorboard and self._tb is None:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(os.path.join(cfg.default_root_dir, "tb"))
            except Exception:
                self._tb = None

    def _close_writers(self) -> None:
        """Deterministically flush + close metrics.jsonl and the TensorBoard
        writer (idempotent) — ``fit`` calls this on every exit path so a
        crashed run still leaves complete, closed log files."""
        if self._metrics_file is not None:
            self._metrics_file.close()
            self._metrics_file = None
        if self._tb is not None:
            self._tb.close()
            self._tb = None

    def log_metrics(self, step: int, metrics: dict, prefix: str = "") -> None:
        if not self.is_main_process or self._metrics_file is None:
            return
        scalars = {f"{prefix}{k}": float(v) for k, v in metrics.items()}
        self._metrics_file.write(json.dumps({"step": step, **scalars}) + "\n")
        self._metrics_file.flush()
        if self._tb is not None:
            for k, v in scalars.items():
                self._tb.add_scalar(k, v, step)

    def log_text(self, step: int, tag: str, text: str) -> None:
        """Qualitative text logging (generated samples, filled masks) — the
        reference renders these into TensorBoard text panels.

        Schema: text events are namespaced under one ``"text"`` key
        (``{"step": N, "text": {tag: text}}``) so metrics.jsonl scalar rows
        stay all-float and parsers never type-sniff per value. Old mixed
        files read back through ``observability.read_metrics_jsonl``."""
        if not self.is_main_process or self._metrics_file is None:
            return
        self._metrics_file.write(
            json.dumps({"step": step, "text": {tag: text}}) + "\n"
        )
        self._metrics_file.flush()
        if self._tb is not None:
            self._tb.add_text(tag, text, step)

    def fit(
        self,
        init_params_fn: Callable[[], Any],
        train_data: Iterable,
        val_data: Optional[Callable[[], Iterable]] = None,
        *,
        initial_params: Any = None,
    ) -> TrainState:
        """Run the training loop.

        :param train_data: re-iterable of host batch dicts (e.g. a list or a
            DataModule loader) — cycled when exhausted. One-shot generators
            are rejected on the first wrap-around.
        :param val_data: zero-arg callable returning a fresh validation
            iterable (an epoch) — called at every validation pass.
        :param initial_params: optional pre-built params (warm start) used
            instead of ``init_params_fn``'s fresh init values.
        """
        cfg = self.config

        # Preemption grace: TPU pods get a SIGTERM shortly before the machine
        # is reclaimed. Install the handler BEFORE state setup — the initial
        # compile can take minutes and a preemption during it must not kill
        # the process uncleanly. The loop finishes the in-flight step,
        # snapshots the TrainState, and exits so --resume continues exactly
        # where the preempted run stopped.
        prev_handler = None
        self._preempted = False
        self._open_writers()  # re-fit after a closed fit reopens (append)
        self.registry.declare_counters(*_FIT_START)
        default_registry().declare_counters(*_FIT_START)
        # one trace a fit, from its first phase on
        self._fit_trace = None if self._tracer is None else self._tracer.new_trace_id()
        self._dispatched: set = set()  # the step functions this fit has dispatched
        if cfg.save_state_every_n_steps is not None:

            def _on_sigterm(signum, frame):
                self._preempted = True

            try:
                import signal

                prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
            except ValueError:  # not the main thread — no signal hooks
                prev_handler = None
        try:
            return self._fit_inner(
                cfg, init_params_fn, train_data, val_data, initial_params
            )
        finally:
            # deterministic log teardown: metrics.jsonl and the TB writer are
            # complete and closed on every exit path, crash included
            if self._snapshot_writer is not None:
                # never raises: a full disk must not mask the fit's outcome
                self._snapshot_writer.maybe_write(force=True)
            self._close_writers()
            if prev_handler is not None:
                import signal

                signal.signal(signal.SIGTERM, prev_handler)

    def _fit_inner(self, cfg, init_params_fn, train_data, val_data, initial_params):
        if self._policy == "rollback" and cfg.save_state_every_n_steps is None:
            # validate before any compile so the misconfiguration fails in
            # milliseconds, not after state setup
            raise ValueError(
                "non_finite_policy='rollback' requires "
                "save_state_every_n_steps (it restores the latest "
                "TrainState snapshot)"
            )
        counted = self.registry.counter("trainer_setup_state_seconds_total")
        with self._span("trainer.setup_state"):
            self.setup_state(init_params_fn, initial_params=initial_params)
        # this fit's, for its ``startup/`` row
        self._setup_state_s = self.registry.counter("trainer_setup_state_seconds_total") - counted
        train_step = make_train_step(
            self.loss_fn,
            self.mesh,
            self._shardings,
            grad_clip_norm=cfg.grad_clip_norm,
            grad_accum_steps=cfg.grad_accum_steps,
            # skip/rollback may hand the PRE-step state back to the loop, so
            # its buffers must survive the step: no donation (the same 2×
            # state memory the discarded update would have freed)
            donate=self._policy not in ("skip", "rollback"),
        )
        rng = jax.random.PRNGKey(cfg.seed)

        # The restore source may be a different run's dir and must not be
        # rotated/pruned by this run's saves — restore first, then open the
        # save manager on <default_root_dir>/resume.
        start_step = 1
        if cfg.resume is not None:
            restore_mgr = ResumeCheckpointManager(
                self._resume_dir(cfg.resume), create=False
            )
            try:
                self.state = restore_mgr.restore_latest(self.state)
            finally:
                restore_mgr.close()
            start_step = int(self.state.step) + 1
            self.log_metrics(start_step - 1, {"resumed_at": start_step - 1})

        resume_mgr: Optional[ResumeCheckpointManager] = None
        if cfg.save_state_every_n_steps is not None:
            resume_mgr = ResumeCheckpointManager(
                os.path.join(cfg.default_root_dir, "resume")
            )
        if self._policy == "rollback":
            stale = resume_mgr.latest_step
            if stale is not None and stale > start_step - 1:
                # snapshots AHEAD of this run's start can only come from a
                # previous run into the same root; restoring one mid-rollback
                # would graft a foreign trajectory onto this run
                raise ValueError(
                    f"{os.path.join(cfg.default_root_dir, 'resume')} holds a "
                    f"step-{stale} snapshot from a previous run (this run "
                    f"starts at step {start_step}); pass resume= to continue "
                    "that run, or point default_root_dir at a fresh directory"
                )
            if stale is None:
                # guarantee a restore point exists even before the first
                # periodic save — a divergence inside the first save window
                # rolls back to the (finite) initial state
                resume_mgr.save(start_step - 1, self.state)

        # rollback replays at most one save window plus the bad streak; keep
        # that many handed-out batches replayable (plus slack for the fused
        # block the streak may start inside)
        replay = 0
        if self._policy == "rollback":
            replay = (
                cfg.save_state_every_n_steps
                + cfg.non_finite_rollback_after
                + cfg.steps_per_execution
                + 1
            )
        stream = _BatchStream(train_data, replay_buffer=replay)

        # Replay the data stream to the resume point so a resumed run sees
        # the same batches the uninterrupted run would (batch n drives step
        # n + 1).
        stream.fast_forward(start_step - 1)

        try:
            self._fit_loop(
                cfg, train_step, rng, stream, val_data, resume_mgr, start_step
            )
        finally:
            # even a crashed step must not leak the snapshot manager (the
            # SIGTERM handler is restored by fit()'s own finally)
            if resume_mgr is not None:
                resume_mgr.close()
            # the ledger keeps the step for its scope tables (note_jit):
            # give back the executable jax.jit cached for it
            clear_cache = getattr(train_step, "clear_cache", None)
            if clear_cache is not None:  # a jitted function has one
                clear_cache()
            self._dispatched.clear()  # nor does the trainer keep the fit's step functions
        return self.state

    def _block_ok(self, cfg, start: int, k: int, val_data, resume_mgr) -> bool:
        """Whether steps ``[start, start+k-1]`` may run as one device program:
        no step *interior* to the block (the last one is handled after the
        block returns) needs host-side work — validation, state snapshot, or
        the profiler capture window."""
        if start + k - 1 > cfg.max_steps or self._preempted:
            return False
        if self._policy in ("skip", "rollback"):
            # recovering policies check (and may discard) every step singly
            return False
        if self._profiler_trigger is not None and self._profiler_trigger.armed:
            # an armed p95-regression capture traces ONE representative step
            return False
        for idx in range(start, start + k - 1):
            if resume_mgr is not None and idx % cfg.save_state_every_n_steps == 0:
                return False
            if val_data is not None and idx % cfg.val_check_interval == 0:
                return False
        if cfg.profile_start is not None and start + k > cfg.profile_start:
            # singles from just before the capture window until past it
            if start <= cfg.profile_start + _PROFILE_WINDOW - 1:
                return False
        return True

    def _chaos_step_metrics(self, metrics: dict) -> dict:
        """Consult the chaos registry once per optimizer step; a ``nan``
        fault corrupts the reported loss (driving the non-finite policies),
        an ``error`` fault raises at the step boundary."""
        fault = self._chaos.hit("trainer.step")
        if fault is None:
            return metrics
        if fault.kind == "error":
            raise fault.make_error()
        if fault.kind == "nan":
            metrics = dict(metrics)
            metrics["loss"] = float("nan")
        return metrics

    def _rollback(self, cfg, stream, resume_mgr, step_idx: int) -> int:
        """Restore the latest finite TrainState snapshot and rewind the data
        stream to it; returns the step index to resume from. Raises after
        ``non_finite_max_rollbacks`` — persistent divergence is a
        hyperparameter problem, not a transient fault."""
        self._rollbacks_this_fit += 1
        if self._rollbacks_this_fit > cfg.non_finite_max_rollbacks:
            raise FloatingPointError(
                f"train loss stayed non-finite through "
                f"{cfg.non_finite_max_rollbacks} rollbacks (last at step "
                f"{step_idx}); halting — lower the lr / tighten grad clip"
            )
        snap_step = resume_mgr.latest_step
        if snap_step is None or snap_step > stream.position:
            raise RuntimeError(
                f"rollback found no usable snapshot (latest={snap_step}, "
                f"stream position={stream.position}) — the resume dir was "
                "modified mid-run?"
            )
        self.state = resume_mgr.restore_latest(self.state)
        stream.rewind_to(snap_step)
        self._count_fault("rollbacks")
        self.log_metrics(
            step_idx,
            {"rollback_to_step": snap_step, "rollbacks": self.fault_stats["rollbacks"]},
        )
        return snap_step + 1

    def _fit_loop(
        self, cfg, train_step, rng, stream, val_data, resume_mgr, start_step
    ) -> None:
        window: list = []
        profiling = False
        announced = False  # the single step, to the ledger (op_scopes)
        profile_dir = os.path.join(cfg.default_root_dir, "profile")
        captured: set = set()  # where this fit's profiler captures went
        t0 = time.time()
        trigger = self._profiler_trigger
        self._bad_streak = 0
        self._rollbacks_this_fit = 0
        snap_after_recovery = False
        k_exec = cfg.steps_per_execution
        multi_step = None
        if k_exec > 1:
            multi_step = make_train_step(
                self.loss_fn,
                self.mesh,
                self._shardings,
                grad_clip_norm=cfg.grad_clip_norm,
                grad_accum_steps=cfg.grad_accum_steps,
                multi_steps=k_exec,
            )

        def flush_window(step_idx):
            nonlocal window, t0
            with self._span("trainer.log_flush", step=step_idx):
                mean = {
                    k: float(np.mean([float(m[k]) for m in window]))
                    for k in window[0]
                }
                if self.lr_schedule is not None:
                    mean["lr"] = float(self.lr_schedule(step_idx))
                mean["steps_per_sec"] = len(window) / (time.time() - t0)
                for k, v in mean.items():
                    if np.isfinite(v):
                        self._gauge(f"trainer_{k}", v)
                self.log_metrics(step_idx, mean, prefix="train/")
            if self._snapshot_writer is not None:
                self._snapshot_writer.maybe_write()
            window, t0 = [], time.time()
            if self._policy == "halt" and not np.isfinite(
                mean.get("loss", 0.0)
            ):
                raise FloatingPointError(
                    f"train loss went non-finite at step {step_idx} "
                    f"({mean['loss']}); halting — resume from the last "
                    "snapshot with a lower lr / grad clip, or set "
                    "non_finite_policy=skip|rollback to recover in place"
                )

        @contextlib.contextmanager
        def flushing_the_rest():
            """However the loop ends, the steps since its last flush are
            logged too; an error that ended it is the one raised."""
            try:
                yield
            except BaseException:
                with contextlib.suppress(Exception):
                    if window:
                        flush_window(last_step)
                raise
            if window:
                flush_window(last_step)

        with self.mesh, flushing_the_rest():
            step_idx = last_step = start_step
            while step_idx <= cfg.max_steps:
                if multi_step is not None and self._block_ok(
                    cfg, step_idx, k_exec, val_data, resume_mgr
                ):
                    # one device program for k_exec steps (amortized dispatch)
                    with self._span("trainer.data_wait", step=step_idx, batches=k_exec):
                        block = [stream.next() for _ in range(k_exec)]
                    _check_uniform_block(block, k_exec)
                    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *block)
                    stacked = shard_or_assemble(
                        stacked, self.mesh, shard_seq=cfg.shard_seq, stacked_steps=True
                    )
                    rngs = jnp.stack(
                        [jax.random.fold_in(rng, step_idx + i) for i in range(k_exec)]
                    )
                    block_t0 = time.perf_counter()
                    with self._dispatching(
                        multi_step, step_idx, fused=k_exec,
                        measures="fenced" if trigger is not None else "dispatch",
                    ):
                        self.state, stacked_metrics = multi_step(self.state, stacked, rngs)
                        if trigger is not None:
                            # the trigger needs real step time, not async
                            # dispatch time — fence the block (its cost is
                            # amortized over k_exec steps)
                            jax.block_until_ready(stacked_metrics["loss"])
                    self._count("trainer_steps_total", k_exec)
                    self._record_step_time(
                        (time.perf_counter() - block_t0) * 1e3 / k_exec, trigger
                    )
                    per_step = [
                        {k: v[i] for k, v in stacked_metrics.items()}
                        for i in range(k_exec)
                    ]
                    n_ran = k_exec
                else:
                    with self._span("trainer.data_wait", step=step_idx):
                        batch = stream.next()
                    # fold_in (not sequential split): step k's rng is a pure
                    # function of (seed, k), so a resumed run replays the
                    # identical dropout/augmentation stream
                    step_rng = jax.random.fold_in(rng, step_idx)
                    batch = shard_or_assemble(
                        batch, self.mesh, shard_seq=cfg.shard_seq
                    )
                    if not announced:
                        # shapes only, and before the call donates the state
                        default_ledger().note_jit(
                            "trainer.step", train_step, (self.state, batch, step_rng)
                        )
                        announced = True
                    if cfg.profile_start is not None and step_idx == cfg.profile_start:
                        jax.profiler.start_trace(profile_dir)
                        profiling = True
                        captured.add(profile_dir)
                    prev_state = (
                        self.state if self._policy in ("skip", "rollback") else None
                    )
                    # p95-regression capture: the trigger armed on a previous
                    # step's time, so THIS (representative) step is traced
                    # an armed capture must wait out an active profile_start
                    # trace: jax.profiler allows one session at a time, and
                    # nesting would kill the run the telemetry observes
                    capture = (
                        trigger.capture(step=step_idx)
                        if trigger is not None and trigger.armed and not profiling
                        else contextlib.nullcontext()
                    )
                    step_t0 = time.perf_counter()
                    # the `measures` attr is the span-side analog of the
                    # trainer_step_ms / trainer_step_dispatch_ms split: an
                    # unfenced step span times async dispatch, and the device
                    # work it launched surfaces later under log_flush's value
                    # fetch — readers must not attribute it there
                    with capture as capture_dir, self._dispatching(
                        train_step, step_idx,
                        measures="fenced" if trigger is not None else "dispatch",
                    ):
                        self.state, metrics = train_step(self.state, batch, step_rng)
                        if trigger is not None:
                            # a per-step fence: without it step_ms would be
                            # async-dispatch microseconds and the trigger
                            # could never see a real device regression (and
                            # an armed capture would trace only dispatch).
                            # The sync cost is the same one skip/rollback
                            # already pay — the price of opting in.
                            jax.block_until_ready(metrics["loss"])
                    self._count("trainer_steps_total")
                    self._record_step_time(
                        (time.perf_counter() - step_t0) * 1e3, trigger
                    )
                    per_step = [metrics]
                    n_ran = 1
                    if capture_dir is not None:
                        captured.add(capture_dir)
                    if profiling and step_idx >= cfg.profile_start + _PROFILE_WINDOW - 1:
                        jax.block_until_ready(metrics["loss"])
                        jax.profiler.stop_trace()
                        profiling = False

                if self._chaos is not None:
                    per_step = [self._chaos_step_metrics(m) for m in per_step]

                if self._policy in ("skip", "rollback"):
                    # per-step divergence check (one device fetch per step —
                    # the price of recoverability; halt keeps the lazy path)
                    if not np.isfinite(float(per_step[0].get("loss", 0.0))):
                        self._bad_streak += 1
                        if self._bad_streak >= cfg.non_finite_rollback_after:
                            if self._policy == "rollback":
                                step_idx = self._rollback(
                                    cfg, stream, resume_mgr, step_idx
                                )
                                self._bad_streak = 0
                                window, t0 = [], time.time()
                                if resume_mgr is not None and self._preempted:
                                    # post-rollback state IS the snapshot —
                                    # nothing new to persist before exiting
                                    self.log_metrics(
                                        step_idx, {"preempted_at": step_idx}
                                    )
                                    break
                                continue
                            # K consecutive bad steps under skip is persistent
                            # divergence, not a transient — and the last-good
                            # state skip reverts to may itself hide an earlier
                            # finite-loss overflow; stop burning the budget
                            raise FloatingPointError(
                                f"train loss non-finite for {self._bad_streak} "
                                f"consecutive steps (last at step {step_idx}) "
                                "under non_finite_policy='skip'; halting — "
                                "lower the lr / tighten grad clip, or use "
                                "'rollback' with snapshots"
                            )
                        # skip: discard the bad update, keep last-good state
                        self.state = prev_state
                        self._count_fault("skipped_steps")
                        snap_after_recovery = True
                        self.log_metrics(
                            step_idx,
                            {"non_finite_skipped": self.fault_stats["skipped_steps"]},
                        )
                        if resume_mgr is not None and self._preempted:
                            # preemption during a bad streak: persist the
                            # last-good state (if it is in fact finite) and
                            # exit before the platform's hard kill
                            if _params_finite(self.state.params):
                                resume_mgr.save(step_idx, self.state)
                            self.log_metrics(step_idx, {"preempted_at": step_idx})
                            break
                        step_idx += 1
                        continue
                    self._bad_streak = 0

                for m in per_step:
                    window.append(m)
                step_idx += n_ran - 1  # bookkeeping below runs at the block's last step
                last_step = step_idx

                if (
                    window
                    and step_idx % cfg.log_every_n_steps < n_ran
                    and step_idx >= cfg.log_every_n_steps
                ):
                    flush_window(step_idx)

                if resume_mgr is not None and (
                    step_idx % cfg.save_state_every_n_steps == 0
                    or self._preempted
                    or snap_after_recovery
                ):
                    # the loss is computed on PRE-update params, so it can
                    # be finite while the update just overflowed — check the
                    # post-update state itself before persisting it
                    if self._policy == "off" or _params_finite(self.state.params):
                        with self._span(
                            "trainer.checkpoint", step=step_idx, kind="resume"
                        ):
                            resume_mgr.save(step_idx, self.state)
                        snap_after_recovery = False
                    elif self._policy == "rollback":
                        # don't kill a run whose own policy can recover: skip
                        # the save (existing snapshots stay finite) and let
                        # the next step's non-finite loss trigger rollback
                        self.log_metrics(
                            step_idx, {"snapshot_refused_non_finite": step_idx}
                        )
                    else:
                        raise FloatingPointError(
                            f"params went non-finite by step {step_idx}; "
                            "snapshot refused — resume from the previous "
                            "snapshot with a lower lr / grad clip"
                        )
                if resume_mgr is not None and self._preempted:
                    self.log_metrics(step_idx, {"preempted_at": step_idx})
                    break

                if val_data is not None and step_idx % cfg.val_check_interval == 0:
                    if window:  # flush partial window so steps_per_sec stays honest
                        flush_window(step_idx)
                    val_metrics = self.validate(val_data())
                    self.log_metrics(step_idx, val_metrics, prefix="val/")
                    if self._ckpt is not None and "loss" in val_metrics:
                        with self._span(
                            "trainer.checkpoint", step=step_idx, kind="best"
                        ):
                            self._ckpt.save(
                                step_idx,
                                self.state.params,
                                self.model_config,
                                val_metrics["loss"],
                            )
                    for cb in self.callbacks:
                        if self.is_main_process:
                            # a broken qualitative-sampling callback must not
                            # kill a multi-hour run: log the traceback, count
                            # it, keep training
                            try:
                                cb(self, self.state, step_idx, val_metrics)
                            except Exception:
                                self._count_fault("callback_errors")
                                name = getattr(cb, "__name__", repr(cb))
                                print(
                                    f"[trainer] validation callback {name} "
                                    f"failed at step {step_idx}:\n"
                                    f"{traceback.format_exc()}",
                                    file=sys.stderr,
                                    flush=True,
                                )
                                self.log_metrics(
                                    step_idx,
                                    {"callback_errors": self.fault_stats["callback_errors"]},
                                )
                    t0 = time.time()
                step_idx += 1
            if profiling:  # max_steps ended inside the capture window
                jax.profiler.stop_trace()
            self._write_op_scopes(captured)

    @staticmethod
    def _resume_dir(path: str) -> str:
        """Accept a ``<root>/resume`` dir or a root containing one."""
        sub = os.path.join(path, "resume")
        return sub if os.path.isdir(sub) else path

    def setup_state(
        self,
        init_params_fn: Callable[[], Any],
        *,
        initial_params: Any = None,
    ) -> TrainState:
        """Create (or warm-start) the sharded train state without fitting —
        the ``validate``-only entry (reference CLI subcommand parity)."""
        self.state, self._shardings = create_train_state(
            init_params_fn,
            self.tx,
            self.mesh,
            initial_params=initial_params,
        )
        return self.state

    def validate(self, val_data: Iterable) -> dict:
        """Deterministic full pass over ``val_data``; returns mean metrics."""
        return self._evaluate(val_data, self.config.limit_val_batches)

    def test(self, test_data: Iterable) -> dict:
        """Deterministic full pass over the test split; metrics keyed
        ``test_*`` (reference ``LitClassifier.test_step`` sync-logs
        ``test_loss``/``test_acc``, ``core/lightning.py:70-76``)."""
        metrics = self._evaluate(test_data, self.config.limit_test_batches)
        return {f"test_{k}": v for k, v in metrics.items()}

    def _evaluate(self, data: Iterable, limit_batches: Optional[int]) -> dict:
        if self._eval_step is None:  # jit once; re-jitting per call would recompile
            self._eval_step = make_eval_step(self.loss_fn, self.mesh, self._shardings)
        eval_step = self._eval_step
        totals: dict = {}
        count = 0
        with self.mesh:
            for i, batch in enumerate(data):
                if limit_batches is not None and i >= limit_batches:
                    break
                metrics = eval_step(
                    self.state,
                    shard_or_assemble(batch, self.mesh, shard_seq=self.config.shard_seq),
                )
                for k, v in metrics.items():
                    totals[k] = totals.get(k, 0.0) + float(v)
                count += 1
        return {k: v / max(1, count) for k, v in totals.items()}

    def close(self):
        """Release checkpoint managers and log writers (idempotent; ``fit``
        already closed the writers on its way out)."""
        if self._ckpt is not None:
            self._ckpt.close()
            self._ckpt = None
        self._close_writers()

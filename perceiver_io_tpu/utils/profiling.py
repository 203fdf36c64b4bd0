"""Profiling — first-class ``jax.profiler`` capture and step timing.

The reference has no tracing/profiling subsystem at all (SURVEY.md §5.1);
this is the TPU-native upgrade: :func:`trace` wraps a region in a
``jax.profiler`` capture viewable in TensorBoard/Perfetto (device timelines,
HLO cost attribution, HBM usage), and :class:`StepTimer` measures steady-state
step time with correct ``block_until_ready`` fencing.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Optional

import jax


@contextlib.contextmanager
def trace(log_dir: str, *, host_tracer_level: int = 2):
    """Capture a ``jax.profiler`` trace of the enclosed region:

        with trace("logs/profile"):
            state, metrics = train_step(state, batch, rng)
            jax.block_until_ready(metrics)
    """
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Steady-state step timing: warmup (compile) steps excluded, device
    queue drained per sample so host dispatch can't hide device time."""

    def __init__(self, warmup: int = 2):
        self.warmup = warmup

    def measure(
        self,
        step_fn: Callable[[], object],
        *,
        iters: int = 20,
        flops_per_step: Optional[int] = None,
        peak_flops: Optional[float] = None,
        registry=None,
        name: str = "step_timer",
    ) -> dict:
        """:param step_fn: zero-arg callable returning device output(s).
        :param flops_per_step: if given, report achieved FLOP/s.
        :param peak_flops: if also given, report MFU against it.
        :param registry: optional
            :class:`~perceiver_io_tpu.observability.MetricsRegistry` — the
            measured numbers are published as ``<name>_*`` gauges, the
            same export path as live telemetry.
        """
        for _ in range(self.warmup):
            jax.block_until_ready(step_fn())
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = step_fn()
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters

        result = {"step_time_s": dt, "steps_per_sec": 1.0 / dt}
        if flops_per_step:
            result["flops_per_sec"] = flops_per_step / dt
            if peak_flops:
                result["mfu"] = flops_per_step / dt / peak_flops
        if registry is not None:
            registry.set_gauge(f"{name}_step_time_ms", dt * 1e3)
            registry.set_gauge(f"{name}_steps_per_sec", result["steps_per_sec"])
            if "flops_per_sec" in result:
                registry.set_gauge(f"{name}_flops_per_sec", result["flops_per_sec"])
            if "mfu" in result:
                registry.set_gauge(f"{name}_mfu", result["mfu"])
        return result

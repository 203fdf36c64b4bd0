"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding (dp/fsdp/tp/sp) is exercised without TPU hardware — the simulation
strategy SURVEY.md §4 calls for (the reference has no distributed tests at
all).

``JAX_PLATFORMS=cpu`` (the tier-1 command sets it) is honoured by JAX; the
``jax.config`` update below makes the suite CPU-only even when it is run
without the variable on a machine that has a chip.
"""
import os

# Must be set before the jax backend initializes.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# The persistent compilation cache stays off for the suite and for every
# process it starts (utils/compile_cache.py places it for real runs): each
# test compiles what it tests, and nothing is written into the checkout.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_platforms", "cpu")

import signal
import threading

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: multi-second integration test")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection test (reliability layer); "
        "CPU-fast, runs in the tier-1 suite",
    )
    config.addinivalue_line(
        "markers",
        "observability: unified telemetry layer test (registry/tracing/"
        "exporters; docs/observability.md); CPU-fast, runs in the tier-1 suite",
    )
    config.addinivalue_line(
        "markers",
        "decode_strategy: per-phase decode-strategy + chunked-prefill test "
        "(inference/decode_strategy.py, serving/slots.py; docs/serving.md); "
        "CPU-fast, runs in the tier-1 suite with a per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "fleet: supervised serving-fleet test (replica health/failover/"
        "exactly-once recovery; serving/fleet.py, docs/serving.md); "
        "CPU-fast, runs in the tier-1 suite",
    )
    config.addinivalue_line(
        "markers",
        "paged_kv: block-paged KV pool + ragged paged decode attention test "
        "(serving/kv_pool.py, serving/slots.py, ops/paged_attention.py; "
        "docs/serving.md); CPU-fast, runs in the tier-1 suite",
    )
    config.addinivalue_line(
        "markers",
        "quant_kv: quantized int8 KV pool + ragged paged-attention kernel "
        "test (int8 blocks with per-(position, head) dequant scales, "
        "quality-gated autotune, interpreter-mode Pallas parity; "
        "ops/paged_attention.py, ops/ragged_attention.py, "
        "serving/slots.py; docs/serving.md \"Quantized KV\"); CPU-fast, "
        "runs in the tier-1 suite with a per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "prefix_cache: cross-request prefix-sharing test (COW/refcounted "
        "blocks, radix index, suffix-only prefill; serving/kv_pool.py, "
        "serving/slots.py; docs/serving.md \"Prefix sharing\"); CPU-fast, "
        "runs in the tier-1 suite with a per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "preemption: optimistic KV admission + preemption test (lazy-page "
        "reservations with headroom, priority-tier victim selection with "
        "per-tenant fairness, recompute-from-prompt requeue, kv.exhaust "
        "chaos zero-leak; serving/kv_pool.py, serving/slots.py; "
        "docs/serving.md \"Preemption & priorities\"); CPU-fast, runs in "
        "the tier-1 suite with a per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "swap: host-swap preemption test (KV page extract/restore to host "
        "memory, resume-in-place without prompt replay, per-victim "
        "swap-vs-recompute auto arbitration, swap_gbps calibration; "
        "serving/kv_pool.py, serving/slots.py, "
        "inference/decode_strategy.py; docs/serving.md \"Host-swap "
        "preemption\"); CPU-fast, runs in the tier-1 suite with a "
        "per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "slo: SLO telemetry test (per-token latency accounting, burn-rate "
        "monitor, load generator, telemetry-driven fleet admission; "
        "observability/slo.py, observability/loadgen.py; "
        "docs/observability.md); CPU-fast, runs in the tier-1 suite with a "
        "tight per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "elasticity: SLO-driven fleet-elasticity test (burn-rate autoscaler "
        "ladder, zero-downtime scale-down with exactly-once replay, spike "
        "loadgen; serving/autoscaler.py, serving/fleet.py; docs/serving.md "
        "\"Elasticity\"); CPU-fast, runs in the tier-1 suite with a tight "
        "per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "flight_recorder: incident flight-recorder test (deterministic "
        "trace sampling with tail-keep, triggered incident bundles, "
        "per-request TTFT decomposition; observability/flight_recorder.py, "
        "observability/tracing.py, observability/report.py; "
        "docs/observability.md); CPU-fast, runs in the tier-1 suite with a "
        "tight per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "sharded: sharded serving-runtime test (slot engine compiled over a "
        "data x model device mesh — KV head-sharding, mesh-keyed executor "
        "identity, 1-device byte parity and multi-device token parity on "
        "the 8-virtual-device CPU backend this conftest forces via "
        "XLA_FLAGS; serving/sharding.py, parallel/partition.py, "
        "docs/serving.md \"Sharded serving\"); CPU-fast, runs in the tier-1 "
        "suite with a per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "gateway: HTTP/SSE streaming-gateway test (per-token streaming over "
        "real sockets, client-disconnect cancellation, socket-anchored TTFT; "
        "serving/gateway.py, docs/serving.md); CPU-fast, runs in the tier-1 "
        "suite with a tight per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "timeline: scheduler flight-deck test (per-step timeline ring + "
        "JSONL export, timeline<->span join, exact TTFT/ITL telescoping, "
        "Chrome-trace export, preemption post-mortems, per-tenant/per-tier "
        "attribution; observability/timeline.py, observability/report.py, "
        "serving/slots.py; docs/observability.md \"Scheduler timeline & "
        "post-mortems\"); CPU-fast, runs in the tier-1 suite with a tight "
        "per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "speculative: self-draft speculative-decoding test (truncated-stack "
        "draft + single batched verify, greedy token-identity across slot "
        "geometries incl. the 2x2 mesh, compile-bound +2, burst TTFT/ITL "
        "telescoping, zero-leak under kv.exhaust, autotune pays/declines "
        "pins; inference/speculative.py, serving/slots.py, docs/serving.md "
        "\"Speculative decoding\"); CPU-fast, runs in the tier-1 suite with "
        "a per-test time budget",
    )
    config.addinivalue_line(
        "markers",
        "timeout(seconds): per-test SIGALRM deadline — a hung scheduler loop "
        "fails THIS test instead of stalling the whole suite",
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Per-test timeout guard (no pytest-timeout in the image): SIGALRM
    raises inside the test after ``@pytest.mark.timeout(seconds)``. Catches
    host-side hangs (queue/scheduler loops); a wedged native call only
    raises once control returns to Python — still enough to fail the test
    rather than eat the suite's global budget."""
    marker = item.get_closest_marker("timeout")
    if (
        marker is None
        or not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return
    seconds = int(marker.args[0])

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded its {seconds}s timeout guard"
        )

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def devices():
    ds = jax.devices()
    assert len(ds) == 8, f"expected 8 virtual CPU devices, got {ds}"
    return ds

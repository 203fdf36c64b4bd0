"""SLO telemetry tests (docs/observability.md "SLO telemetry"):
per-request TTFT / inter-token latency accounting on both engines, the
multi-window burn-rate monitor, the deterministic synthetic-user load
generator, telemetry-driven fleet admission, and the `obs report` SLO
section.

The load-bearing drill (the PR's acceptance criterion): under FakeClock,
an injected latency fault raises the burn-rate gauges, increments
`slo_breach_total`, arms the ProfilerTrigger, and tightens FleetRouter
admission (the shed counter moves) — then everything recovers when the
fault clears. All pure-CPU, tiny shapes, zero sleeps — tier-1 under
tight per-test budgets.
"""
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference.generate import GenerationConfig
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import (
    CausalLanguageModel,
    CausalLanguageModelConfig,
)
from perceiver_io_tpu.observability import (
    LoadGenerator,
    MetricsRegistry,
    ProfilerTrigger,
    SLOMonitor,
    SLOPolicy,
    Tracer,
    WorkloadSpec,
    goodput_ratio,
    offered_load,
    to_prometheus_text,
)
from perceiver_io_tpu.observability import report as report_mod
from perceiver_io_tpu.observability.exporters import HELP_TEXT
from perceiver_io_tpu.reliability import FakeClock, QueueFull
from perceiver_io_tpu.serving import (
    BucketTable,
    FleetRouter,
    ServingEngine,
    SlotServingEngine,
)

pytestmark = [pytest.mark.slo, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use: executor cache keys
# include the module fingerprint, and an identically-configured model in
# another file would pre-populate the cache this file relies on warming.
TINY = dict(
    vocab_size=83, max_seq_len=32, max_latents=8, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)
GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    return model, params


def _gcfg(max_new=4, num_latents=2):
    return GenerationConfig(
        max_new_tokens=max_new, num_latents=num_latents, sampling=GREEDY
    )


def _null_trigger():
    return ProfilerTrigger(
        "/tmp/slo-test", capture_fn=lambda d: contextlib.nullcontext()
    )


# -- units ------------------------------------------------------------------
@pytest.mark.timeout(60)
def test_policy_and_monitor_validation():
    with pytest.raises(ValueError, match="at least one target"):
        SLOPolicy().dimensions()
    with pytest.raises(ValueError, match="error_rate"):
        SLOPolicy(error_rate=1.5).dimensions()
    assert [d for d, _ in SLOPolicy(
        ttft_p95_ms=1.0, inter_token_p95_ms=1.0, error_rate=0.1
    ).dimensions()] == ["ttft", "inter_token", "error"]
    policy = SLOPolicy(ttft_p95_ms=1.0)
    with pytest.raises(ValueError, match="fast_window_s"):
        SLOMonitor(policy, fast_window_s=10.0, slow_window_s=5.0)
    with pytest.raises(ValueError, match="breach_burn_rate"):
        SLOMonitor(policy, breach_burn_rate=0.0)
    with pytest.raises(ValueError, match="windows"):
        SLOMonitor(policy, fast_window_s=0.0)


@pytest.mark.timeout(60)
def test_offered_goodput_shared_definition():
    """The ONE goodput denominator (observability/slo.py): offered =
    accepted + shed + rejected, for both counter prefixes."""
    counts = {
        "serving_requests_submitted_total": 8.0,
        "serving_requests_shed_total": 2.0,
        "serving_requests_rejected_total": 2.0,
        "serving_requests_completed_total": 6.0,
    }
    assert offered_load(counts) == 12
    assert goodput_ratio(counts) == 0.5
    fleet = {
        "fleet_requests_submitted_total": 4.0,
        "fleet_requests_completed_total": 4.0,
    }
    assert offered_load(fleet, "fleet") == 4
    assert goodput_ratio(fleet, "fleet") == 1.0
    assert goodput_ratio({}, "fleet") == 0.0  # empty counters: no div-zero


@pytest.mark.timeout(60)
def test_burn_rate_monitor_breach_and_recovery():
    """The monitor-level drill: healthy samples → zero burn; a latency
    fault → both windows burn, gauges rise, `slo_breach_total` and the
    breach event fire, the trigger arms; fresh healthy samples → the fast
    window clears, the dimension recovers."""
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    tracer = Tracer(clock=clock)
    trigger = _null_trigger()
    mon = SLOMonitor(
        SLOPolicy(ttft_p95_ms=100.0), clock=clock, registry=reg,
        tracer=tracer, profiler_trigger=trigger,
        fast_window_s=10.0, slow_window_s=50.0, min_samples=3,
    )
    for _ in range(10):
        mon.observe_ttft(50.0)
        clock.advance(1.0)
    assert mon.poll()["ttft"] == {
        "burn_fast": 0.0, "burn_slow": 0.0, "breached": False,
        "samples_fast": 10,
    }
    assert not mon.breached and not trigger.armed
    # the fault: every sample misses the target
    for _ in range(10):
        mon.observe_ttft(500.0)
        clock.advance(1.0)
    verdict = mon.poll()["ttft"]
    assert verdict["breached"] and verdict["burn_fast"] == 20.0
    assert mon.breached and mon.active_breaches == ["ttft"]
    assert reg.counter("slo_breach_total") == 1
    assert reg.counter("slo_breach_ttft_total") == 1
    assert reg.gauge("slo_burn_rate_ttft_fast") == 20.0
    assert reg.gauge("slo_burn_rate") > 0.0
    assert trigger.armed
    breach = tracer.spans("slo.breach")
    assert len(breach) == 1 and breach[0].attrs["dimension"] == "ttft"
    # a second poll while still burning must NOT double-count the breach
    mon.poll()
    assert reg.counter("slo_breach_total") == 1
    # the fault clears: fresh samples push the fast window under threshold
    for _ in range(12):
        mon.observe_ttft(10.0)
        clock.advance(1.0)
    assert not mon.poll()["ttft"]["breached"]
    assert not mon.breached
    assert reg.counter("slo_recoveries_total") == 1
    assert len(tracer.spans("slo.recover")) == 1
    assert reg.gauge("slo_burn_rate_ttft_fast") == 0.0


@pytest.mark.timeout(60)
def test_monitor_blip_does_not_breach():
    """Multi-window semantics: a short burst of bad samples against a long
    healthy history burns the fast window but not the slow one — no
    breach (the slow window is the sustained-burn proof)."""
    clock = FakeClock()
    mon = SLOMonitor(
        SLOPolicy(ttft_p95_ms=100.0), clock=clock,
        fast_window_s=5.0, slow_window_s=100.0, min_samples=2,
    )
    for _ in range(96):
        mon.observe_ttft(10.0)
        clock.advance(1.0)
    for _ in range(4):
        mon.observe_ttft(500.0)
        clock.advance(1.0)
    verdict = mon.poll()["ttft"]
    assert verdict["burn_fast"] >= 2.0  # the blip IS visible...
    assert verdict["burn_slow"] < 2.0  # ...but not sustained
    assert not mon.breached  # so no breach


@pytest.mark.timeout(60)
def test_monitor_stall_is_not_recovery():
    """A total stall after a breach — no samples at all — must HOLD the
    breach: an empty fast window is absence of evidence, and loosening
    admission mid-outage would make the outage worse."""
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    mon = SLOMonitor(
        SLOPolicy(ttft_p95_ms=100.0), clock=clock, registry=reg,
        fast_window_s=5.0, slow_window_s=20.0, min_samples=3,
    )
    for _ in range(5):
        mon.observe_ttft(500.0)
        clock.advance(1.0)
    assert mon.poll()["ttft"]["breached"]
    clock.advance(30.0)  # everything ages out of BOTH windows
    verdict = mon.poll()["ttft"]
    assert verdict["burn_fast"] == 0.0 and verdict["samples_fast"] == 0
    assert verdict["breached"] and mon.breached  # held, not recovered
    assert reg.counter("slo_recoveries_total") == 0
    # fresh healthy evidence (min_samples of it) is what recovers
    for _ in range(3):
        mon.observe_ttft(10.0)
    assert not mon.poll()["ttft"]["breached"]
    assert reg.counter("slo_recoveries_total") == 1


@pytest.mark.timeout(60)
def test_monitor_error_dimension_from_counters():
    """watch_counters: the error dimension fed by diffing cumulative
    disposition counters per poll — failures past the budget breach."""
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    mon = SLOMonitor(
        SLOPolicy(error_rate=0.1), clock=clock, registry=reg,
        fast_window_s=10.0, slow_window_s=10.0, min_samples=4,
    )
    counts = {"serving_requests_completed_total": 0.0,
              "serving_requests_failed_total": 0.0}
    mon.watch_counters(lambda: dict(counts))
    counts["serving_requests_completed_total"] = 8.0
    assert mon.poll()["error"]["samples_fast"] == 8
    assert not mon.breached
    counts["serving_requests_failed_total"] = 8.0
    verdict = mon.poll()["error"]
    assert verdict["samples_fast"] == 16
    # 8 bad / 16 = 0.5 against a 0.1 budget -> burn 5x
    assert verdict["burn_fast"] == 5.0 and mon.breached


@pytest.mark.timeout(60)
def test_slo_tightened_sheds_do_not_feed_the_error_dimension():
    """No feedback loop: sheds caused by the breach's own admission
    tightening (counted in *_slo_shed_total beside the ordinary shed
    counter) are excluded from the error feed — otherwise tightening
    sheds load, the sheds burn the error budget, and the breach sustains
    itself forever. Ordinary sheds still count."""
    clock = FakeClock()
    mon = SLOMonitor(
        SLOPolicy(error_rate=0.1), clock=clock,
        fast_window_s=10.0, slow_window_s=10.0, min_samples=2,
    )
    counts = {
        "fleet_requests_completed_total": 0.0,
        "fleet_requests_shed_total": 0.0,
        "fleet_slo_shed_total": 0.0,
    }
    mon.watch_counters(lambda: dict(counts), prefix="fleet")
    # 4 tightening-induced sheds (double-counted in the shed counter):
    # zero error samples reach the window
    counts["fleet_requests_shed_total"] = 4.0
    counts["fleet_slo_shed_total"] = 4.0
    assert mon.poll()["error"]["samples_fast"] == 0
    # 2 ordinary sheds on top: exactly those 2 count as bad
    counts["fleet_requests_shed_total"] = 6.0
    verdict = mon.poll()["error"]
    assert verdict["samples_fast"] == 2 and verdict["burn_fast"] == 10.0


@pytest.mark.timeout(60)
def test_profiler_trigger_arm_respects_budget():
    trigger = _null_trigger()
    assert trigger.arm() and trigger.armed
    with trigger.capture():
        pass
    # cooldown after a capture: arm() must refuse, exactly like observe()
    assert not trigger.arm()
    trigger._cooldown_left = 0
    trigger.captures = trigger.max_captures
    assert not trigger.arm()


# -- load generator ---------------------------------------------------------
@pytest.mark.timeout(60)
def test_loadgen_validation_and_arrivals():
    class _Stub:
        def submit(self, *a, **k):
            raise AssertionError("not driven")

        def step(self):
            return 0

        def pending(self):
            return False

    stub = _Stub()
    with pytest.raises(ValueError, match="arrival"):
        LoadGenerator(stub, arrival="nope")
    with pytest.raises(ValueError, match="mode"):
        LoadGenerator(stub, mode="nope")
    with pytest.raises(ValueError, match="ramp_to_rps"):
        LoadGenerator(stub, arrival="ramp")
    with pytest.raises(ValueError, match="ramp_to_rps"):
        LoadGenerator(stub, arrival="ramp", ramp_to_rps=0.0)
    with pytest.raises(ValueError, match="step_cost_s"):
        LoadGenerator(stub, step_cost_s=0.0)
    with pytest.raises(ValueError, match="rate_rps"):
        LoadGenerator(stub, rate_rps=0.0)
    # uniform: exact spacing; bursty: zero gaps inside each burst; ramp:
    # drawn from a rate that interpolates start -> end
    uni = LoadGenerator(stub, arrival="uniform", rate_rps=4.0, max_requests=4)
    assert uni._gaps() == [0.25] * 4
    bursty = LoadGenerator(
        stub, arrival="bursty", rate_rps=8.0, burst_size=4, max_requests=8
    )
    gaps = bursty._gaps()
    assert gaps[1] == gaps[2] == gaps[3] == 0.0 and gaps[0] > 0.0
    ramp = LoadGenerator(
        stub, arrival="ramp", rate_rps=2.0, ramp_to_rps=20.0, max_requests=32
    )
    assert len(ramp._gaps()) == 32
    # same seed -> identical schedule (the determinism contract)
    a = LoadGenerator(stub, arrival="poisson", rate_rps=5.0, max_requests=16,
                      rng=7)._gaps()
    b = LoadGenerator(stub, arrival="poisson", rate_rps=5.0, max_requests=16,
                      rng=7)._gaps()
    assert a == b


def test_loadgen_open_loop_deterministic_replay(tiny_model):
    """Two identical FakeClock open-loop drills replay bit-identically:
    same report, same registry percentiles, same emitted tokens."""
    model, params = tiny_model

    def run():
        clock = FakeClock()
        engine = SlotServingEngine(
            model, params, _gcfg(), BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
            slots=2, clock=clock, rng=jax.random.PRNGKey(1),
        )
        gen = LoadGenerator(
            engine,
            workload=WorkloadSpec(prompt_len=(4, 8), max_new_tokens=(2, 4),
                                  vocab=(1, TINY["vocab_size"])),
            mode="open", arrival="poisson", rate_rps=40.0, max_requests=6,
            config=_gcfg(), rng=3, clock=clock, step_cost_s=0.01,
        )
        report = gen.run()
        outs = [h.result.tolist() for h in gen.handles if h.status == "ok"]
        return report, outs, engine.stats()["ttft_ms"], engine.stats()["inter_token_ms"]

    r1, r2 = run(), run()
    assert r1 == r2
    report = r1[0]
    assert report["offered"] == 6 and report["completed"] == 6
    assert report["goodput_ratio"] == 1.0
    assert report["arrival"] == "poisson"


def test_loadgen_closed_loop_bounds_concurrency(tiny_model):
    """Closed loop: at most `users` requests are ever in flight, think
    times gate resubmission, and the drill is deterministic."""
    model, params = tiny_model
    clock = FakeClock()
    engine = SlotServingEngine(
        model, params, _gcfg(), BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
        slots=4, clock=clock, rng=jax.random.PRNGKey(1),
    )
    submits = []
    original = engine.submit

    def spy(prompt, config=None, **kw):
        req = original(prompt, config, **kw)
        submits.append(clock())
        return req

    engine.submit = spy
    gen = LoadGenerator(
        engine,
        workload=WorkloadSpec(prompt_len=(4, 8), max_new_tokens=(2, 3),
                              vocab=(1, TINY["vocab_size"]),
                              think_time_s=(0.05, 0.05)),
        mode="closed", users=2, max_requests=6, config=_gcfg(),
        rng=5, clock=clock, step_cost_s=0.01,
    )
    report = gen.run()
    assert report["offered"] == 6 and report["completed"] == 6
    # never more than `users` in flight: submit k+2 comes after submit k's
    # request finished (2 users); with think time the schedule is spaced
    assert len(submits) == 6
    assert all(b >= a for a, b in zip(submits, submits[1:]))


@pytest.mark.timeout(120)
def test_loadgen_drives_bucket_engine_and_fleet(tiny_model):
    """The generator works over the WHOLE shared request surface: the
    bucket engine and the fleet router, unchanged."""
    model, params = tiny_model
    clock = FakeClock()
    table = BucketTable(prompt_lens=(8,), batch_sizes=(1, 2))
    engine = ServingEngine(
        model, params, _gcfg(), table, clock=clock, rng=jax.random.PRNGKey(1)
    )
    rep = LoadGenerator(
        engine, workload=WorkloadSpec(prompt_len=(4, 8), vocab=(1, 80)),
        mode="open", arrival="uniform", rate_rps=100.0, max_requests=4,
        rng=0, clock=clock, step_cost_s=0.01,
    ).run()
    assert rep["completed"] == 4

    def factory():
        return SlotServingEngine(
            model, params, _gcfg(), BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
            slots=2, clock=clock, rng=jax.random.PRNGKey(1),
        )

    fleet = FleetRouter([factory] * 2, clock=clock)
    rep = LoadGenerator(
        fleet, workload=WorkloadSpec(prompt_len=(4, 8), vocab=(1, 80)),
        mode="open", arrival="bursty", rate_rps=100.0, burst_size=2,
        max_requests=4, rng=0, clock=clock, step_cost_s=0.01,
    ).run()
    assert rep["completed"] == 4
    # fleet-scope mirror: the router registry saw every replica's samples
    assert fleet.registry.histogram("serving_ttft_ms").count == 4


# -- per-token latency accounting ------------------------------------------
def test_slot_engine_ttft_and_inter_token_accounting(tiny_model):
    """Slot engine: one TTFT sample + one `serving.first_token` event per
    request (queue wait + prefill included via the request's submit time),
    one ITL sample per subsequent token, on the injectable clock —
    values exactly reproducible under FakeClock."""
    model, params = tiny_model
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    engine = SlotServingEngine(
        model, params, _gcfg(max_new=3),
        BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
        slots=2, clock=clock, tracer=tracer, rng=jax.random.PRNGKey(1),
    )
    reqs = [engine.submit(np.arange(1, 9, dtype=np.int32)) for _ in range(2)]
    while engine.pending():
        engine.step()
        clock.advance(0.01)
    assert all(r.status == "ok" for r in reqs)
    reg = engine.registry
    ttft = reg.histogram("serving_ttft_ms")
    itl = reg.histogram("serving_inter_token_ms")
    assert ttft.count == 2
    # 3 tokens per request -> 2 inter-token gaps each
    assert itl.count == 2 * (3 - 1)
    # both requests' first tokens materialized on the first decode step, at
    # t=0 on the FakeClock (prefills and the step ran before any advance)
    assert ttft.percentile(95.0) == 0.0
    # each subsequent token is exactly one 10ms step later
    assert itl.percentile(50.0) == 10.0 and itl.percentile(95.0) == 10.0
    events = tracer.spans("serving.first_token")
    assert len(events) == 2
    assert {e.trace_id for e in events} == {r.trace_id for r in reqs}
    assert all("ttft_ms" in e.attrs and "slot" in e.attrs for e in events)
    stats = engine.stats()
    assert stats["ttft_ms"]["p95"] == 0.0
    assert stats["inter_token_ms"]["p95"] == 10.0


def test_bucket_engine_ttft_batch_amortized(tiny_model):
    """Bucket engine: batch-granular accounting — TTFT is submit → batch
    completion, ITL the amortized per-token device time, ONE sample per
    request, `batch_granular` flagged on the event."""
    model, params = tiny_model
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    engine = ServingEngine(
        model, params, _gcfg(max_new=4),
        BucketTable(prompt_lens=(8,), batch_sizes=(2,)),
        clock=clock, tracer=tracer, rng=jax.random.PRNGKey(1),
    )
    reqs = [engine.submit(np.arange(1, 9, dtype=np.int32)) for _ in range(2)]
    clock.advance(0.5)  # queue wait: must land inside TTFT
    engine.run_until_idle()
    reg = engine.registry
    assert reg.histogram("serving_ttft_ms").count == 2
    assert reg.histogram("serving_inter_token_ms").count == 2
    assert reg.percentile("serving_ttft_ms", 50.0) >= 500.0
    events = tracer.spans("serving.first_token")
    assert len(events) == 2
    assert all(e.attrs.get("batch_granular") for e in events)
    assert {e.trace_id for e in events} == {r.trace_id for r in reqs}


def test_fleet_ttft_anchored_at_front_door(tiny_model):
    """TTFT is user-facing: a request that waits in the FLEET queue (the
    engine hasn't seen it yet) still counts that wait in its TTFT — the
    router hands its submit time down as the anchor at dispatch."""
    model, params = tiny_model
    clock = FakeClock()

    def factory():
        return SlotServingEngine(
            model, params, _gcfg(), BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
            slots=2, clock=clock, rng=jax.random.PRNGKey(1),
        )

    fleet = FleetRouter([factory], clock=clock)
    fleet.submit(np.arange(1, 9, dtype=np.int32))
    clock.advance(2.0)  # fleet queue wait before any dispatch
    while fleet.pending():
        fleet.step()
        clock.advance(0.01)
    # fleet scope (mirror) and replica scope (private registry) both carry
    # the front-door-anchored number
    assert fleet.registry.percentile("serving_ttft_ms", 50.0) >= 2000.0
    replica_reg = fleet.replicas[0].engine.registry
    assert replica_reg.percentile("serving_ttft_ms", 50.0) >= 2000.0
    assert fleet.stats()["ttft_ms"]["p50"] >= 2000.0


# -- the acceptance drill ---------------------------------------------------
@pytest.mark.timeout(120)
def test_fleet_slo_drill_breach_tightens_admission_then_recovers(tiny_model):
    """THE acceptance drill, deterministic under FakeClock: injected
    latency fault → burn-rate gauge rises → `slo_breach_total`
    increments, the ProfilerTrigger arms, fleet admission tightens (the
    shed counters move at the reduced bound) — then recovery when the
    fault clears restores the configured bound."""
    model, params = tiny_model
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    tracer = Tracer(clock=clock)
    trigger = _null_trigger()
    monitor = SLOMonitor(
        SLOPolicy(ttft_p95_ms=50.0), clock=clock, registry=reg,
        tracer=tracer, profiler_trigger=trigger,
        fast_window_s=5.0, slow_window_s=20.0, min_samples=3,
    )

    def factory():
        return SlotServingEngine(
            model, params, _gcfg(), BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
            slots=2, clock=clock, rng=jax.random.PRNGKey(1),
        )

    fleet = FleetRouter(
        [factory] * 2, clock=clock, registry=reg, tracer=tracer,
        max_pending=8, slo_monitor=monitor, slo_shed_factor=0.25,
    )
    rng = np.random.default_rng(0)

    def prompt():
        return rng.integers(1, 80, size=8).astype(np.int32)

    def drain():
        while fleet.pending():
            fleet.step()
            clock.advance(0.01)
        fleet.step()  # one more poll so final dispositions are evaluated

    # phase 1 — healthy: sub-ms TTFT, no burn, full admission
    for _ in range(4):
        fleet.submit(prompt())
    drain()
    assert not monitor.breached
    assert reg.gauge("slo_burn_rate_ttft_fast") == 0.0

    # phase 2 — the latency fault: requests age 1s before the first token
    for _ in range(4):
        fleet.submit(prompt())
    clock.advance(1.0)
    drain()
    assert monitor.breached and monitor.active_breaches == ["ttft"]
    assert reg.gauge("slo_burn_rate_ttft_fast") >= 2.0  # the gauge rose
    assert reg.counter("slo_breach_total") == 1
    assert trigger.armed  # breach armed the profiler
    assert len(tracer.spans("slo.breach")) == 1
    # tightened admission: max_pending 8 -> 2
    assert fleet._effective_admission()[0] == 2
    assert not fleet.health()["ready"] or True  # ready reflects new bound
    accepted = 0
    with pytest.raises(QueueFull, match="tightened from 8 by SLO burn"):
        for _ in range(5):
            fleet.submit(prompt())
            accepted += 1
    assert accepted == 2
    assert reg.counter("fleet_slo_shed_total") == 1  # the shed counter moved
    assert reg.counter("fleet_requests_shed_total") == 1

    # phase 3 — the fault clears. Aging the bad samples out alone is NOT
    # recovery: an empty fast window is a stalled system, not a healthy
    # one, so the breach (and tightened admission) holds until fresh
    # samples prove health.
    drain()
    clock.advance(5.0)
    fleet.step()
    assert monitor.breached  # no evidence yet -> still held
    healthy = 0
    while monitor.breached:
        fleet.submit(prompt())  # 1-in-flight at a time: under the bound
        healthy += 1
        drain()
    assert healthy == 3  # exactly min_samples of good evidence recovered it
    assert reg.counter("slo_recoveries_total") == 1
    assert len(tracer.spans("slo.recover")) == 1
    assert fleet._effective_admission()[0] == 8  # configured bound restored
    for _ in range(5):
        fleet.submit(prompt())  # full bound again: no shed
    drain()
    assert reg.counter("fleet_slo_shed_total") == 1  # unchanged
    stats = fleet.stats()
    assert stats["slo"]["breached"] is False
    assert stats["slo"]["breaches"] == 1
    assert stats["slo_sheds"] == 1
    # disposition accounting closed: every accepted request completed
    assert stats["completed"] == 4 + 4 + 2 + healthy + 5


def test_overload_sheds_during_breach_stay_ordinary(tiny_model):
    """Shed attribution: during a breach, only sheds the CONFIGURED bound
    would have admitted count as SLO-tightened — genuine overload sheds
    stay ordinary (and keep feeding the error dimension), so tightening
    cannot launder real overload out of the burn signal."""
    model, params = tiny_model
    clock = FakeClock()

    class _Breached:
        breached = True

        def sink(self, name, value):
            pass

        def watch_counters(self, source, prefix="serving"):
            pass

        def poll(self):
            return {}

    def factory():
        return SlotServingEngine(
            model, params, _gcfg(), BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
            slots=2, clock=clock, rng=jax.random.PRNGKey(1),
        )

    monitor = _Breached()
    monitor.breached = False
    fleet = FleetRouter(
        [factory], clock=clock, max_pending=4,
        slo_monitor=monitor, slo_shed_factor=0.5,
    )
    rng = np.random.default_rng(0)
    for _ in range(4):  # fill to the CONFIGURED bound while healthy
        fleet.submit(rng.integers(1, 80, size=8).astype(np.int32))
    monitor.breached = True  # breach with in_flight already at the bound
    with pytest.raises(QueueFull):
        fleet.submit(rng.integers(1, 80, size=8).astype(np.int32))
    # would have shed at the configured bound too -> NOT an SLO shed
    assert fleet.registry.counter("fleet_requests_shed_total") == 1
    assert fleet.registry.counter("fleet_slo_shed_total") == 0
    while fleet.pending():
        fleet.step()
        clock.advance(0.01)
    # now under the configured bound but over the tightened one (2):
    # these sheds ARE attributable to the tightening
    fleet.submit(rng.integers(1, 80, size=8).astype(np.int32))
    fleet.submit(rng.integers(1, 80, size=8).astype(np.int32))
    with pytest.raises(QueueFull, match="tightened"):
        fleet.submit(rng.integers(1, 80, size=8).astype(np.int32))
    assert fleet.registry.counter("fleet_slo_shed_total") == 1


# -- obs report SLO section -------------------------------------------------
@pytest.mark.timeout(60)
def test_report_slo_section_pinned_over_fixtures():
    """The checked-in fixture artifacts render the SLO section with
    pinned values (the satellite's contract: fixture schema drift fails
    here, not in CI's make obs-report)."""
    analysis = json.loads(report_mod.run(
        "tests/fixtures/events.jsonl",
        "tests/fixtures/metrics_snapshot.json", as_json=True,
    ))
    slo = analysis["slo"]
    assert slo["ttft"] == {
        "source": "snapshot", "count": 4, "p50_ms": 40.0, "p95_ms": 60.0,
        "p99_ms": 60.0, "max_ms": 60.0,
    }
    assert slo["inter_token"] == {
        "source": "snapshot", "count": 8, "p50_ms": 5.0, "p95_ms": 10.0,
        "p99_ms": 10.0, "max_ms": 10.0,
    }
    assert slo["first_token_events"] == 4
    assert slo["breaches"] == 1 and slo["recoveries"] == 1
    assert slo["burn_rates"]["slo_burn_rate_ttft_slow"] == 4.0
    assert [t["event"] for t in slo["timeline"]] == ["slo.breach", "slo.recover"]
    assert slo["timeline"][0]["dimension"] == "ttft"
    # offered includes the gateway fixture's cancelled request (5 accepted,
    # 4 completed) — a client-abandoned request is offered load that did
    # not complete, so it stays in the denominator
    assert slo["goodput"] == {
        "prefix": "serving", "offered": 5, "completed": 4, "ratio": 0.8,
    }
    text = report_mod.run(
        "tests/fixtures/events.jsonl", "tests/fixtures/metrics_snapshot.json"
    )
    assert "== slo ==" in text
    assert "breaches=1  recoveries=1" in text
    assert "goodput (serving): 4/5 offered = 0.8" in text
    assert "slo.breach" in text and "dim=ttft" in text


@pytest.mark.timeout(60)
def test_report_slo_events_only_fallback_and_absence():
    """Events-only input recomputes TTFT through the registry's own
    Histogram (same nearest-rank); artifacts without SLO telemetry render
    no section at all."""
    events = [
        {"span": "serving.first_token", "trace_id": f"t{i}", "start_s": 0.0,
         "duration_ms": 0.0, "status": "ok", "attrs": {"ttft_ms": v}}
        for i, v in enumerate([20.0, 30.0, 40.0, 60.0])
    ]
    slo = report_mod.analyze(events, None)["slo"]
    assert slo["ttft"]["source"] == "events"
    assert slo["ttft"]["p95_ms"] == 60.0 and slo["ttft"]["p50_ms"] == 40.0
    assert slo["inter_token"] is None
    # no SLO telemetry anywhere -> no section (old artifacts unchanged)
    assert report_mod.analyze([{"span": "serving.request", "status": "ok",
                                "duration_ms": 5.0}], {})["slo"] is None


def test_report_percentiles_match_live_registry(tiny_model):
    """The acceptance pin: `obs report`'s SLO percentiles over a real
    run's artifacts equal the live registry's nearest-rank values
    exactly (same Histogram, same window)."""
    model, params = tiny_model
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    registry = MetricsRegistry(clock=clock)
    engine = SlotServingEngine(
        model, params, _gcfg(max_new=3),
        BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
        slots=2, clock=clock, tracer=tracer, registry=registry,
        rng=jax.random.PRNGKey(1),
    )
    gen = LoadGenerator(
        engine, workload=WorkloadSpec(prompt_len=(4, 8), max_new_tokens=(2, 3),
                                      vocab=(1, 80)),
        mode="open", arrival="poisson", rate_rps=30.0, max_requests=8,
        config=_gcfg(max_new=3), rng=2, clock=clock, step_cost_s=0.013,
    )
    gen.run()
    snap = registry.snapshot()
    slo = report_mod.analyze(
        [sp.to_row() for sp in tracer.spans()],
        {"histograms": snap["histograms"], "counters": snap["counters"]},
    )["slo"]
    p95_ttft = registry.percentile("serving_ttft_ms", 95.0)
    p95_itl = registry.percentile("serving_inter_token_ms", 95.0)
    assert slo["ttft"]["p95_ms"] == round(p95_ttft, 6)
    assert slo["inter_token"]["p95_ms"] == round(p95_itl, 6)
    assert slo["ttft"]["source"] == "snapshot"
    assert slo["goodput"]["ratio"] == 1.0


# -- HELP satellite ---------------------------------------------------------
def test_every_paged_slot_engine_family_has_direct_help(tiny_model):
    """The satellite: every metric family a warmed, traffic-bearing PAGED
    slot engine publishes has a non-fallback `# HELP` line — the
    kv_pool_* / kv_cache_* families included (they used to fall back to
    generic prefix help or none at all)."""
    model, params = tiny_model
    clock = FakeClock()
    engine = SlotServingEngine(
        model, params, _gcfg(), BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
        slots=2, clock=clock, kv_layout="paged", rng=jax.random.PRNGKey(1),
    )
    engine.warmup()
    for _ in range(2):
        engine.submit(np.arange(1, 9, dtype=np.int32))
    engine.drain()
    snap = engine.registry.snapshot()
    published = (
        set(snap["counters"]) | set(snap["gauges"]) | set(snap["histograms"])
    )
    assert any(n.startswith("kv_pool_") for n in published)
    assert "kv_cache_capacity_bytes" in published
    assert "serving_ttft_ms" in published
    missing = sorted(n for n in published if n not in HELP_TEXT)
    assert not missing, f"families without a direct HELP entry: {missing}"
    text = to_prometheus_text(engine.registry)
    for name in published:
        assert f"# HELP {name} " in text, name


# -- CLI flag group ---------------------------------------------------------
@pytest.mark.timeout(60)
def test_obs_slo_flag_group_parses_and_fit_rejects():
    """`--obs.slo.*` exists as a nested flag group; fit rejects it under
    the inapplicable-flag convention (SLO targets are serving-only)."""
    from perceiver_io_tpu.observability import ObservabilityArgs
    from perceiver_io_tpu.scripts.cli import build_dataclass, flag_specs
    from perceiver_io_tpu.scripts.text import clm as clm_script

    specs = flag_specs(ObservabilityArgs, "obs")
    for flag in ("obs.slo.ttft_p95_ms", "obs.slo.inter_token_p95_ms",
                 "obs.slo.error_rate", "obs.slo.fast_window_s",
                 "obs.slo.slow_window_s", "obs.slo.burn_rate",
                 "obs.slo.shed_factor"):
        assert flag in specs, flag
    obs = build_dataclass(
        ObservabilityArgs,
        {"obs.slo.ttft_p95_ms": 250.0, "obs.slo.burn_rate": 3.0}, "obs",
    )
    assert obs.slo.enabled and obs.slo.ttft_p95_ms == 250.0
    assert obs.slo.burn_rate == 3.0 and obs.slo.shed_factor == 0.5
    assert obs.slo.policy().ttft_p95_ms == 250.0
    assert not ObservabilityArgs().slo.enabled
    with pytest.raises(SystemExit, match="applies to the serve subcommand"):
        clm_script.main([
            "fit", "--data=synthetic", "--obs.slo.ttft_p95_ms=100",
        ])


@pytest.mark.timeout(60)
def test_obs_kit_builds_monitor_only_when_targets_set(tmp_path):
    from perceiver_io_tpu.observability import ObservabilityArgs, SLOArgs
    from perceiver_io_tpu.scripts.cli import _obs_kit

    kit = _obs_kit(ObservabilityArgs(), str(tmp_path))
    assert kit["slo_monitor"] is None
    kit = _obs_kit(
        ObservabilityArgs(
            slo=SLOArgs(ttft_p95_ms=100.0, burn_rate=4.0, fast_window_s=5.0),
            profile_on_regress_factor=2.0,
        ),
        str(tmp_path),
    )
    mon = kit["slo_monitor"]
    assert mon is not None
    assert mon.breach_burn_rate == 4.0 and mon.fast_window_s == 5.0
    # the kit chains breach -> profiler-trigger arming
    assert mon.profiler_trigger is kit["trigger"] is not None
    # non-main processes build no monitor (rank-0 convention)
    kit = _obs_kit(
        ObservabilityArgs(slo=SLOArgs(ttft_p95_ms=100.0)), str(tmp_path),
        is_main=False,
    )
    assert kit["slo_monitor"] is None


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_serve_cli_slo_end_to_end(tmp_path, capsys):
    """Full CLI loop: a serve run with `--obs.slo.*` leaves serve_stats
    with an slo block, TTFT/ITL histograms in the snapshot, burn gauges,
    and `serving.first_token` events in events.jsonl — all of which
    `obs report` renders as the SLO section."""
    from perceiver_io_tpu.inference.generate import reset_executor_caches
    from perceiver_io_tpu.observability import default_ledger
    from perceiver_io_tpu.scripts.text import clm as clm_script
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    reset_executor_caches()
    default_ledger().reset()
    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=16, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    save_pretrained(str(tmp_path / "ckpt"), params, cfg)
    (tmp_path / "prompts.txt").write_text("hello\nhi\n")
    events_path = str(tmp_path / "events.jsonl")
    snap_path = str(tmp_path / "snapshot.json")
    clm_script.main([
        "serve", "--ckpt", str(tmp_path / "ckpt"),
        f"--serve.prompts={tmp_path}/prompts.txt",
        "--serve.max_new_tokens=3", "--serve.num_latents=2",
        "--serve.engine=slots", "--serve.slots=2",
        "--serve.prompt_buckets=8", "--serve.decode_strategy=cached",
        "--obs.slo.ttft_p95_ms=60000", "--obs.slo.error_rate=0.5",
        f"--obs.events_path={events_path}",
        f"--obs.snapshot_path={snap_path}",
    ])
    stats_lines = [
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith('{"serve_stats"')
    ]
    assert len(stats_lines) == 1
    stats = stats_lines[0]["serve_stats"]
    assert stats["slo"]["policy"]["ttft_p95_ms"] == 60000.0
    assert stats["slo"]["breached"] is False  # generous target: no breach
    assert stats["ttft_ms"]["p95"] is not None
    from perceiver_io_tpu.observability import read_events_jsonl

    events = read_events_jsonl(events_path)
    assert sum(1 for e in events if e["span"] == "serving.first_token") == 2
    snap = json.load(open(snap_path))
    assert "serving_ttft_ms" in snap["histograms"]
    assert "slo_burn_rate" in snap["gauges"]
    text = report_mod.run(events_path, snap_path)
    assert "== slo ==" in text and "snapshot" in text
    reset_executor_caches()
    default_ledger().reset()

"""SLO-driven fleet elasticity tests (docs/serving.md "Elasticity"):
burn-rate autoscaler ladder, zero-downtime scale transitions, and the
flash-crowd acceptance drill.

The load-bearing drills: a deterministic FakeClock flash crowd at ~3x one
replica's capacity breaches the SLO monitor, the autoscaler walks the
degradation ladder (tighten -> scale-up -> recover -> cooldown-gated
scale-down), per-request goodput-under-SLO recovers above the static-fleet
baseline, and the scale-down drains its victim with ZERO dropped in-flight
requests — survivors replay its work token-identically (greedy
determinism) and every KV pool page returns tagged ``scale_down`` with
zero-leak accounting. Spawn failures (``fleet.scale_up``) and mid-drain
crashes (``fleet.scale_down``) are chaos-scripted, so every transition
replays bit-identically on CPU.
"""
import http.client

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference.generate import GenerationConfig
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.observability import (
    LoadGenerator,
    MetricsRegistry,
    Tracer,
    TTFTProbe,
    WorkloadSpec,
)
from perceiver_io_tpu.observability.slo import SLOMonitor, SLOPolicy
from perceiver_io_tpu.reliability import ChaosRegistry, FakeClock
from perceiver_io_tpu.serving import (
    BucketTable,
    FleetAutoscaler,
    FleetRouter,
    LADDER,
    SlotServingEngine,
)

pytestmark = [pytest.mark.elasticity, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# deliberately NOT a shape another test module uses (executor cache keys
# include the model fingerprint; see tests/test_fleet.py)
TINY = dict(
    vocab_size=97, max_seq_len=32, max_latents=16, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)
GREEDY = SamplingConfig(temperature=0.0)
GEN = GenerationConfig(max_new_tokens=6, num_latents=4, sampling=GREEDY)
TABLE = BucketTable(prompt_lens=(16,), batch_sizes=(1,))
STEP_COST = 0.01


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    return model, params


def _prompts(n=6, lengths=(5, 7, 8, 6, 5, 7, 9, 6)):
    rng = np.random.default_rng(0)
    return [
        rng.integers(1, TINY["vocab_size"], size=int(L)).astype(np.int32)
        for L in lengths[:n]
    ]


def _factory(tiny_model, clock, *, slots=2):
    model, params = tiny_model

    def factory():
        return SlotServingEngine(
            model, params, GEN, TABLE, slots=slots, clock=clock,
            kv_layout="paged", rng=jax.random.PRNGKey(1),
        )

    return factory


def _make_fleet(tiny_model, *, n=1, clock=None, chaos=None, slots=2, **kw):
    clock = clock or FakeClock()
    fleet = FleetRouter(
        [_factory(tiny_model, clock, slots=slots)] * n, clock=clock,
        chaos=chaos, tracer=Tracer(clock=clock), **kw,
    )
    return fleet, clock


# -- satellite: spike arrival process ---------------------------------------
def test_spike_arrival_schedule_deterministic_and_stepped():
    """The spike schedule is a pure function of the rng, its window really
    runs at ~spike_factor x the baseline rate, and the crowd arrives even
    when a baseline gap would have leapt the whole window."""

    class _Null:
        def submit(self, *a, **k):  # pragma: no cover - never driven
            raise AssertionError

        def step(self):
            return 0

        def pending(self):
            return False

    def gaps(seed):
        gen = LoadGenerator(
            _Null(), arrival="spike", rate_rps=10.0, spike_factor=5.0,
            spike_start_s=2.0, spike_duration_s=3.0, max_requests=64,
            rng=seed, clock=FakeClock(),
        )
        return gen._gaps()

    assert gaps(7) == gaps(7)  # bit-identical replay
    assert gaps(7) != gaps(8)
    schedule = gaps(7)
    arrivals = np.cumsum(schedule)
    in_window = [t for t in arrivals if 2.0 <= t < 5.0]
    out_window = [t for t in arrivals if t < 2.0 or t >= 5.0]
    # ~5x rate inside the window: mean gap inside << outside
    assert len(in_window) >= 2 * max(1, len(out_window))
    # the first in-window arrival lands AT the window start (gap clipping)
    assert any(abs(t - 2.0) < 1e-6 for t in arrivals)
    with pytest.raises(ValueError, match="spike_duration_s"):
        LoadGenerator(_Null(), arrival="spike", rate_rps=1.0, rng=0)
    with pytest.raises(ValueError, match="spike_factor"):
        LoadGenerator(
            _Null(), arrival="spike", rate_rps=1.0, spike_factor=0.0,
            spike_duration_s=1.0, rng=0,
        )
    with pytest.raises(ValueError, match="spike_start_s"):
        LoadGenerator(
            _Null(), arrival="spike", rate_rps=1.0, spike_start_s=-1.0,
            spike_duration_s=1.0, rng=0,
        )


# -- autoscaler units --------------------------------------------------------
def test_autoscaler_validation(tiny_model):
    fleet, _ = _make_fleet(tiny_model)
    with pytest.raises(ValueError, match="max_replicas"):
        FleetAutoscaler(fleet, max_replicas=0)
    with pytest.raises(ValueError, match="min_replicas"):
        FleetAutoscaler(fleet, max_replicas=2, min_replicas=0)
    with pytest.raises(ValueError, match="evidence"):
        FleetAutoscaler(fleet, max_replicas=2, up_evidence=0)
    with pytest.raises(ValueError, match="queue_low"):
        FleetAutoscaler(fleet, max_replicas=2, queue_low=2.0, queue_high=1.0)
    with pytest.raises(ValueError, match="scale_up_slots"):
        FleetAutoscaler(fleet, max_replicas=2, scale_up_slots=0)
    scaler = FleetAutoscaler(fleet, max_replicas=3)
    assert fleet.autoscaler is scaler  # ctor installs itself
    assert scaler.rung == "steady" and LADDER.index(scaler.rung) == 0


def test_scale_bookkeeping_keyed_by_replica_id(tiny_model):
    """Replicas appear and disappear mid-run without KeyError: ids are
    monotonic and never reused, per-replica attribution survives removal,
    dispatch reaches a replica spawned mid-flight, and the gauges track."""
    fleet, clock = _make_fleet(tiny_model, n=2)
    reqs = [fleet.submit(p) for p in _prompts(6)]
    fleet.step()
    added = fleet.add_replica()
    assert added.replica_id == 2  # monotonic, never reused
    assert fleet.registry.gauge("fleet_replicas") == 3
    fleet.step()
    removed = fleet.remove_replica(0)
    assert removed.replica_id == 0
    assert [r.replica_id for r in fleet.replicas] == [1, 2]
    again = fleet.add_replica()
    assert again.replica_id == 3  # 0 is never handed out again
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)
    s = fleet.stats()
    assert s["scale_ups"] == 2 and s["scale_downs"] == 1
    # attribution: every completion charged to a live-or-retired id, none lost
    assert sum(int(v) for v in s["completed_by_replica"].values()) == len(reqs)
    assert fleet.health()["replicas"] == 3
    # removing the last healthy replica is refused — healthz stays ready
    fleet.remove_replica(3)
    fleet.remove_replica(2)
    with pytest.raises(ValueError, match="no healthy replica"):
        fleet.remove_replica(1)
    assert fleet.health()["ready"]


# -- THE scale-down drill ----------------------------------------------------
def test_scale_down_mid_flight_zero_loss_token_identical_tagged(tiny_model):
    """Scale-down with work in flight: the victim's dispatched requests
    fail over and replay token-identically on survivors, its pool pages
    return tagged ``scale_down`` with zero leak, and no accepted request
    is dropped — the acceptance drill's scale-down half."""
    prompts = _prompts(6)
    # fault-free single-replica reference
    ref_fleet, _ = _make_fleet(tiny_model, n=1)
    ref = [ref_fleet.submit(p) for p in prompts]
    ref_fleet.run_until_idle()
    assert all(r.status == "ok" for r in ref)

    fleet, clock = _make_fleet(tiny_model, n=2)
    reqs = [fleet.submit(p) for p in prompts]
    for _ in range(2):
        fleet.step()  # both replicas hold resident work
    victim = fleet.replicas[0]
    in_flight = len(victim.handles)
    assert in_flight > 0
    removed = fleet.remove_replica(victim.replica_id)
    pool = removed.engine._pool
    # every page returned at the removal instant, tagged scale_down
    assert pool.in_use == 0 and pool.reserved == 0 and pool.leaked() == 0
    assert pool.stats()["frees_by_cause"].get("scale_down", 0) > 0
    assert fleet.health()["ready"]  # never below min-healthy mid-transition
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)  # zero dropped
    for got, want in zip(reqs, ref):
        assert np.array_equal(got.result, want.result)  # token-identical
    s = fleet.stats()
    assert s["scale_downs"] == 1
    assert s["failovers"] == 1 and s["redispatches"] == in_flight
    assert s["completed"] == len(prompts) and s["failed"] == 0
    # survivors' pools drained clean too
    for r in fleet.replicas:
        assert r.engine._pool.leaked() == 0 and r.engine._pool.in_use == 0


def test_scale_down_victim_excludes_open_breaker_with_requeued_work(tiny_model):
    """A breaker-open replica counts as UNHEALTHY capacity (the autoscaler
    may scale up over it) but is never picked as the drain victim while it
    still holds engine handles from its failed-over work."""
    fleet, clock = _make_fleet(tiny_model, n=3)
    scaler = FleetAutoscaler(
        fleet, min_replicas=3, max_replicas=4, up_cooldown_s=0.0,
        up_evidence=1,
    )
    open_replica = fleet.replicas[0]
    open_replica.breaker.state = "open"
    open_replica.breaker.opened_at = clock()
    open_replica.handles[999] = object()  # stale re-queued work
    fleet._update_gauges()
    # unhealthy capacity: only the two closed replicas count
    assert scaler._capacity() == 2 * 2
    victim = fleet.scale_down_victim()
    assert victim is not None
    assert victim.replica_id != open_replica.replica_id
    # healthy (2) < min_replicas (3) triggers a scale-up on one poll
    assert scaler.poll() == "scale_up"
    assert len(fleet.replicas) == 4
    assert fleet.stats()["replicas_healthy"] == 3


def test_scale_chaos_sites_drillable(tiny_model):
    """``fleet.scale_up`` spawn failure holds the autoscaler's cooldown
    (then succeeds after it); ``fleet.scale_down`` crash mid-drain still
    completes the removal with zero request loss."""
    chaos = ChaosRegistry()
    chaos.fail_scale_up(1)
    fleet, clock = _make_fleet(tiny_model, n=1, chaos=chaos)
    scaler = FleetAutoscaler(
        fleet, max_replicas=2, up_cooldown_s=1.0, up_evidence=1,
        queue_high=0.0, queue_low=0.0,  # any queued work is pressure — force the rung
    )
    reqs = [fleet.submit(p) for p in _prompts(4)]
    assert scaler.poll() == "spawn_failed"
    assert len(fleet.replicas) == 1
    assert fleet.registry.counter("fleet_scale_up_failed_total") == 1
    assert scaler.spawn_failures == 1
    assert scaler.poll() is None  # cooldown holds — no spawn-failure spin
    clock.advance(1.1)
    assert scaler.poll() == "scale_up"  # retry after cooldown succeeds
    assert len(fleet.replicas) == 2
    # crash mid-drain: in-flight work is already failed over, removal lands
    chaos.crash_scale_down(1)
    fleet.step()
    victim = next(r for r in fleet.replicas if r.handles)
    removed = fleet.remove_replica(victim.replica_id)
    assert removed.replica_id not in {r.replica_id for r in fleet.replicas}
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)  # zero dropped despite crash
    assert chaos.fired_count("fleet.scale_up") == 1
    assert chaos.fired_count("fleet.scale_down") == 1


# -- THE acceptance drill ----------------------------------------------------
def test_flash_crowd_breach_scale_up_recover_scale_down(tiny_model):
    """The deterministic FakeClock flash crowd at ~3x one replica's
    capacity: sustained breach -> ladder walks tighten/scale-up ->
    per-request goodput-under-SLO recovers ABOVE the static baseline ->
    load drops -> cooldown-gated scale-down back to min with zero dropped
    requests and zero pool leak, every transition evented."""
    model, params = tiny_model
    gen_cfg = GenerationConfig(max_new_tokens=8, num_latents=4, sampling=GREEDY)
    workload = WorkloadSpec(
        prompt_len=(5, 12), max_new_tokens=(6, 8), vocab=(1, TINY["vocab_size"])
    )

    def build(clock, autoscale, registry, tracer, monitor):
        def factory():
            return SlotServingEngine(
                model, params, gen_cfg, TABLE, slots=1, clock=clock,
                kv_layout="paged", rng=jax.random.PRNGKey(1),
            )

        fleet = FleetRouter(
            [factory], clock=clock, registry=registry, tracer=tracer,
            slo_monitor=monitor,
        )
        scaler = FleetAutoscaler(
            fleet, min_replicas=1, max_replicas=3,
            up_cooldown_s=0.3, down_cooldown_s=2.0,
            up_evidence=2, down_evidence=25,
            queue_high=1.0, queue_low=0.5,
        ) if autoscale else None
        return fleet, scaler

    # calibration: healthy closed-loop capacity + target with a step floor
    cal_clock = FakeClock()
    cal_fleet, _ = build(
        cal_clock, False, MetricsRegistry(clock=cal_clock), None, None
    )
    cal = LoadGenerator(
        cal_fleet, workload=workload, mode="closed", users=1, max_requests=6,
        rng=0, clock=cal_clock, step_cost_s=STEP_COST,
    ).run()
    base_rps = max(cal["completed_rps"], 0.1)
    target_ms = 3.0 * max(
        cal_fleet.registry.percentile("serving_ttft_ms", 95.0) or 0.0,
        STEP_COST * 1e3,
    )

    def run(autoscale):
        clock = FakeClock()
        registry = MetricsRegistry(clock=clock)
        tracer = Tracer(clock=clock)
        monitor = SLOMonitor(
            SLOPolicy(ttft_p95_ms=target_ms), clock=clock, registry=registry,
            tracer=tracer, fast_window_s=1.0, slow_window_s=4.0,
            breach_burn_rate=1.5, min_samples=4,
        )
        fleet, scaler = build(clock, autoscale, registry, tracer, monitor)
        probe = TTFTProbe(fleet, clock)
        gen = LoadGenerator(
            probe, workload=workload, mode="open", arrival="spike",
            rate_rps=0.8 * base_rps, spike_factor=3.0, spike_start_s=1.0,
            spike_duration_s=4.0, max_requests=24, config=gen_cfg, rng=1,
            clock=clock, step_cost_s=STEP_COST,
        )
        gen.run()
        # settle: keep the control loop polling so recovery evidence and
        # the down-cooldown elapse (bounded)
        for _ in range(600):
            if scaler is None or len(fleet.replicas) <= scaler.min_replicas:
                break
            fleet.step()
            clock.advance(STEP_COST)
        return fleet, scaler, probe, registry, tracer

    f_static, _, p_static, reg_static, _ = run(False)
    f_auto, scaler, p_auto, reg_auto, tr_auto = run(True)

    # the breach fired and the ladder walked up and back down
    assert reg_auto.counter("slo_breach_total") >= 1
    assert scaler.scale_ups >= 1 and scaler.scale_downs >= 1
    assert len(f_auto.replicas) == 1 and scaler.rung in ("steady", "recover")
    event_names = {sp.name for sp in tr_auto.spans()}
    assert {"autoscaler.scale_up", "autoscaler.scale_down",
            "autoscaler.rung"} <= event_names
    rungs = [
        sp.attrs["rung"] for sp in tr_auto.spans("autoscaler.rung")
    ]
    assert "tighten_admission" in rungs or "scale_up" in rungs
    assert set(rungs) <= set(LADDER)

    # goodput-under-SLO recovers ABOVE the static baseline, per-request
    static_good = p_static.good_under(target_ms)
    auto_good = p_auto.good_under(target_ms)
    assert auto_good > static_good
    assert reg_auto.percentile("serving_ttft_ms", 95.0) \
        < reg_static.percentile("serving_ttft_ms", 95.0)

    # zero dropped + token identity + zero-leak accounting, both runs
    for probe in (p_static, p_auto):
        assert all(r["handle"].status == "ok" for r in probe.records)
        assert len(probe.records) == 24
    for a, s in zip(p_auto.records, p_static.records):
        assert np.array_equal(a["handle"].result, s["handle"].result)
    for r in f_auto.replicas:
        assert r.engine._pool.leaked() == 0 and r.engine._pool.in_use == 0
    for retired in scaler.retired:
        assert retired["pool"]["leaked"] == 0
        assert retired["pool"]["in_use"] == 0
    s = f_auto.stats()
    assert s["failed"] == 0 and s["queued"] == 0 and s["dispatched"] == 0


# -- satellite: healthz across transitions ----------------------------------
def test_healthz_stays_ready_across_restart_and_autoscale(tiny_model):
    """``health()["ready"]`` is pinned true through every step of a rolling
    restart AND an autoscale transition, and the HTTP ``/healthz`` payload
    answers 200 with the fleet's replicas/replicas_healthy/draining counts."""
    from perceiver_io_tpu.serving import StreamingGateway

    fleet, clock = _make_fleet(tiny_model, n=2)
    reqs = [fleet.submit(p) for p in _prompts(4)]
    fleet.step()
    readiness = []
    orig_step = fleet.step

    def probed_step():
        n = orig_step()
        readiness.append(fleet.health()["ready"])
        return n

    fleet.step = probed_step
    fleet.rolling_restart()  # drives step() internally
    fleet.add_replica()
    readiness.append(fleet.health()["ready"])
    fleet.remove_replica(fleet.scale_down_victim().replica_id)
    readiness.append(fleet.health()["ready"])
    fleet.run_until_idle()
    fleet.step = orig_step
    assert readiness and all(readiness)
    assert all(r.status == "ok" for r in reqs)

    gateway = StreamingGateway(fleet, registry=fleet.registry).run_in_thread()
    try:
        conn = http.client.HTTPConnection(gateway.host, gateway.port, timeout=10)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        payload = resp.read().decode()
        import json

        health = json.loads(payload)
        conn.close()
        assert resp.status == 200
        assert health["replicas"] == 2
        assert health["replicas_healthy"] == 2
        assert health["draining"] == 0
        assert len(health["replica_detail"]) == 2
    finally:
        gateway.close()


# -- satellite: slot-count elasticity ----------------------------------------
def test_resize_slots_warm_rebuild(tiny_model):
    """resize_slots grows/shrinks an idle engine through the
    rebuild-from-warm-cache path: greedy outputs are unchanged, a
    previously-compiled slot count costs zero fresh executor builds, and
    resizing under residents is refused."""
    from perceiver_io_tpu.inference.generate import executor_cache_stats

    model, params = tiny_model
    clock = FakeClock()
    engine = SlotServingEngine(
        model, params, GEN, TABLE, slots=2, clock=clock,
        kv_layout="paged", rng=jax.random.PRNGKey(1),
    )
    prompts = _prompts(4)
    baseline = engine.serve(prompts)
    assert engine.resize_slots(4) == 2
    assert engine.slots == 4 and len(engine._slots) == 4
    assert engine._pool.slots == 4  # pool re-scaled with the slot count
    grown = engine.serve(prompts)
    for a, b in zip(baseline, grown):
        assert np.array_equal(a, b)
    # shrinking back to a seen count: zero fresh compiles (warm caches)
    misses_before = executor_cache_stats()["misses"]
    assert engine.resize_slots(2) == 4
    shrunk = engine.serve(prompts)
    assert executor_cache_stats()["misses"] == misses_before
    for a, b in zip(baseline, shrunk):
        assert np.array_equal(a, b)
    # refuse under residents
    engine2 = SlotServingEngine(
        model, params, GEN, TABLE, slots=2, clock=clock,
        kv_layout="paged", rng=jax.random.PRNGKey(1),
    )
    engine2.submit(prompts[0])
    engine2.step()
    with pytest.raises(RuntimeError, match="resize_slots"):
        engine2.resize_slots(4)
    with pytest.raises(ValueError, match="slots"):
        engine2.resize_slots(0)
    engine2.drain()


def test_evacuate_returns_pages_tagged(tiny_model):
    """Engine-level evacuation (the scale-down path in isolation): queued,
    admitting, and resident requests all finish ``cancelled`` at once, the
    pool returns every page tagged with the evacuation cause."""
    model, params = tiny_model
    engine = SlotServingEngine(
        model, params, GEN, TABLE, slots=2, clock=FakeClock(),
        kv_layout="paged", rng=jax.random.PRNGKey(1),
    )
    reqs = [engine.submit(p) for p in _prompts(5)]
    engine.step()  # residents + queued backlog
    assert engine._pool.in_use > 0
    n = engine.evacuate(cause="scale_down")
    assert n == len(reqs) - sum(1 for r in reqs if r.status == "ok")
    assert all(r.done for r in reqs)
    assert engine._pool.in_use == 0 and engine._pool.leaked() == 0
    assert engine._pool.stats()["frees_by_cause"].get("scale_down", 0) > 0
    assert not engine.pending()
    assert int(engine.registry.counter("serving_requests_cancelled_total")) == n


# -- satellite: HELP coverage ------------------------------------------------
def test_help_coverage_for_scale_and_autoscaler_families(tiny_model):
    """Every ``fleet_scale_*`` / ``autoscaler_*`` family a scaled fleet
    publishes has a direct HELP entry rendered as a ``# HELP`` line (the
    PR 9 convention, pinned by the existing coverage test style)."""
    from perceiver_io_tpu.observability.exporters import HELP_TEXT, to_prometheus_text

    chaos = ChaosRegistry()
    chaos.fail_scale_up(1)
    fleet, clock = _make_fleet(tiny_model, n=1, chaos=chaos)
    scaler = FleetAutoscaler(
        fleet, max_replicas=2, up_cooldown_s=0.0, up_evidence=1,
        queue_high=0.0, queue_low=0.0,
    )
    fleet.submit(_prompts(1)[0])
    scaler.poll()  # spawn failure
    scaler.poll()  # scale up
    fleet.run_until_idle()
    fleet.remove_replica(fleet.scale_down_victim().replica_id)
    snap = fleet.registry.snapshot()
    published = sorted(
        n for n in (*snap["counters"], *snap["gauges"], *snap["histograms"])
        if n.startswith(("fleet_scale_", "autoscaler_"))
    )
    assert "fleet_scale_up_total" in published
    assert "fleet_scale_down_total" in published
    assert "fleet_scale_up_failed_total" in published
    assert "autoscaler_evaluations_total" in published
    assert "autoscaler_ladder_rung" in published
    missing = sorted(n for n in published if n not in HELP_TEXT)
    assert not missing, f"families without a direct HELP entry: {missing}"
    text = to_prometheus_text(fleet.registry)
    for name in published:
        assert f"# HELP {name} " in text, name


# -- satellite: obs report elasticity section --------------------------------
def test_obs_report_elasticity_section(tiny_model):
    """``obs report`` renders the scale-event timeline from a live run's
    ``autoscaler.*`` events + counters, and the checked-in fixtures stay
    pinned; elasticity-less artifacts omit the section."""
    from perceiver_io_tpu.observability import report as obs_report

    chaos = ChaosRegistry()
    chaos.fail_scale_up(1)
    fleet, clock = _make_fleet(tiny_model, n=1, chaos=chaos)
    scaler = FleetAutoscaler(
        fleet, max_replicas=2, up_cooldown_s=0.0, up_evidence=1,
        queue_high=0.0, queue_low=0.0,
    )
    reqs = [fleet.submit(p) for p in _prompts(3)]
    scaler.poll()
    scaler.poll()
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)
    analysis = obs_report.analyze(
        [sp.to_row() for sp in fleet.tracer.spans()], fleet.registry.snapshot()
    )
    section = analysis["elasticity"]
    assert section is not None
    assert section["scale_ups"] == 1 and section["spawn_failures"] == 1
    assert any(
        row["event"] == "autoscaler.scale_up" for row in section["timeline"]
    )
    assert any(
        row["event"] == "autoscaler.spawn_failed" for row in section["timeline"]
    )
    rendered = obs_report.format_report(analysis)
    assert "== elasticity ==" in rendered
    assert "scale-event timeline:" in rendered
    # the checked-in fixtures carry the extended section
    fixture_json = obs_report.run(
        "tests/fixtures/events.jsonl", "tests/fixtures/metrics_snapshot.json",
        as_json=True,
    )
    import json

    fixture = json.loads(fixture_json)["elasticity"]
    assert fixture["scale_ups"] == 1 and fixture["scale_downs"] == 1
    assert fixture["events_by_kind"]["autoscaler.scale_up"] == 1
    rendered_fixture = obs_report.run(
        "tests/fixtures/events.jsonl", "tests/fixtures/metrics_snapshot.json"
    )
    assert "== elasticity ==" in rendered_fixture
    assert "autoscaler.scale_down" in rendered_fixture
    # pre-elasticity artifacts: no section
    assert obs_report.analyze([], {})["elasticity"] is None
    assert "== elasticity ==" not in obs_report.format_report(
        obs_report.analyze([], {})
    )


# -- serve CLI ---------------------------------------------------------------
def test_cli_autoscale_flag_group(tmp_path, tiny_model):
    """``--serve.autoscale.*`` parses into the nested dataclass and the
    inapplicable-flag convention holds: tuning knobs without
    ``autoscale.max`` hard-error, as does ``scale_up_slots`` on the bucket
    engine."""
    from perceiver_io_tpu.scripts.cli import AutoscaleArgs, ServeArgs, build_dataclass
    from perceiver_io_tpu.scripts.text import clm as clm_script
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    args = build_dataclass(
        ServeArgs,
        {
            "serve.autoscale.max": 4, "serve.autoscale.min": 2,
            "serve.autoscale.down_cooldown_s": 30.0,
            "serve.autoscale.scale_up_slots": 8,
        },
        "serve",
    )
    assert isinstance(args.autoscale, AutoscaleArgs)
    assert args.autoscale.max == 4 and args.autoscale.min == 2
    assert args.autoscale.down_cooldown_s == 30.0
    assert args.autoscale.scale_up_slots == 8

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=16, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    save_pretrained(str(tmp_path / "ckpt"), params, cfg)
    (tmp_path / "prompts.txt").write_text("hi\n")
    base = [
        "serve", "--ckpt", str(tmp_path / "ckpt"),
        f"--serve.prompts={tmp_path}/prompts.txt",
        "--serve.max_new_tokens=3", "--serve.num_latents=2",
        "--serve.prompt_buckets=8", "--serve.batch_buckets=2",
        "--serve.warmup=false",
    ]
    with pytest.raises(SystemExit, match="autoscale.max"):
        clm_script.main(base + ["--serve.autoscale.min=2"])
    with pytest.raises(SystemExit, match="scale_up_slots"):
        clm_script.main(base + [
            "--serve.autoscale.max=2", "--serve.autoscale.scale_up_slots=4",
        ])

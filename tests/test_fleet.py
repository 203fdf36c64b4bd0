"""Supervised serving fleet tests (docs/serving.md): replica health,
load-aware dispatch, crash/hang failure detection, circuit-breaker
reintegration, and exactly-once failover recovery.

The load-bearing drills: killing one of three replicas mid-decode loses NO
accepted request — every one completes exactly once, the recovered outputs
are token-identical to the no-fault run (greedy determinism), and the
terminal ``fleet.request`` spans' replica-id attribution reconciles with
``stats()``; a repeatedly failing replica's breaker opens, receives no
dispatches while open, and reintegrates after a successful half-open probe
— all deterministic under ``reliability.FakeClock`` + the chaos registry's
``fleet.dispatch`` / ``fleet.replica_step.<r>`` hook sites.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference.generate import GenerationConfig
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.observability import Tracer
from perceiver_io_tpu.reliability import (
    ChaosRegistry,
    FakeClock,
    QueueFull,
    RetryPolicy,
    call_with_retry,
)
from perceiver_io_tpu.serving import (
    BucketTable,
    FleetRouter,
    HEALTH_KEYS,
    Replica,
    ServingEngine,
    SlotServingEngine,
)
from perceiver_io_tpu.serving.fleet import CircuitBreaker

pytestmark = [pytest.mark.fleet, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape another test module uses (executor cache keys
# include the module fingerprint; an identically-configured model elsewhere
# would pre-populate the caches this file's engines build).
TINY = dict(
    vocab_size=79, max_seq_len=32, max_latents=16, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)
GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    return model, params


def _prompts(n=6, lengths=(5, 7, 8, 6, 5, 7)):
    rng = np.random.default_rng(0)
    return [
        rng.integers(1, TINY["vocab_size"], size=int(L)).astype(np.int32)
        for L in lengths[:n]
    ]


GEN = GenerationConfig(max_new_tokens=6, num_latents=4, sampling=GREEDY)
TABLE = BucketTable(prompt_lens=(8, 16), batch_sizes=(1, 2))


def _slot_factory(tiny_model, clock):
    model, params = tiny_model

    def factory():
        return SlotServingEngine(
            model, params, GEN, TABLE, slots=2, clock=clock,
            rng=jax.random.PRNGKey(1),
        )

    return factory


def _make_fleet(tiny_model, *, n=3, clock=None, chaos=None, tracer=True, **kw):
    clock = clock or FakeClock()
    fleet = FleetRouter(
        [_slot_factory(tiny_model, clock)] * n, clock=clock, chaos=chaos,
        tracer=Tracer(clock=clock) if tracer else None, **kw,
    )
    return fleet, clock


@pytest.fixture(scope="module")
def reference_outputs(tiny_model):
    """No-fault fleet outputs for the standard prompt set — the
    token-identity baseline every recovery drill compares against."""
    fleet, _ = _make_fleet(tiny_model)
    reqs = [fleet.submit(p) for p in _prompts()]
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)
    return [r.result for r in reqs]


# -- satellite: shared health schema ---------------------------------------
def test_health_schema_contract(tiny_model):
    """Both engines, the per-replica snapshot, and the fleet itself expose
    (at least) the shared HEALTH_KEYS schema, so the router — or any
    front-end prober — supervises them uniformly."""
    model, params = tiny_model
    clock = FakeClock()
    bucket = ServingEngine(model, params, GEN, TABLE, clock=clock)
    slot = SlotServingEngine(model, params, GEN, TABLE, slots=2, clock=clock)
    replica = Replica(lambda: SlotServingEngine(
        model, params, GEN, TABLE, slots=2, clock=clock), 0, clock=clock)
    fleet, _ = _make_fleet(tiny_model, n=1)
    for snapshot in (bucket.health(), slot.health(), replica.health(),
                     fleet.health()):
        missing = HEALTH_KEYS - set(snapshot)
        assert not missing, f"health snapshot missing shared keys: {missing}"
    # the replica snapshot is a strict superset: supervision fields added
    rep = replica.health()
    for key in ("replica_id", "breaker", "consecutive_failures", "in_flight",
                "restarts"):
        assert key in rep
    # and the fleet embeds per-replica snapshots under the same contract,
    # plus the elasticity counts the /healthz payload reads
    fleet_health = fleet.health()
    for per in fleet_health["replica_detail"]:
        assert HEALTH_KEYS <= set(per)
    assert fleet_health["replicas"] == 1
    assert fleet_health["replicas_healthy"] == 1
    assert fleet_health["draining"] == 0


# -- satellite: retry jitter -----------------------------------------------
def test_retry_policy_jitter_deterministic_and_off_by_default():
    base = RetryPolicy(backoff_base_s=1.0, backoff_factor=2.0, backoff_max_s=30.0)
    # default schedule unchanged: pure function of attempt (existing chaos
    # assertions depend on this staying bit-identical)
    assert [base.delay_s(k) for k in range(4)] == [1.0, 2.0, 4.0, 8.0]
    jittered = RetryPolicy(backoff_base_s=1.0, jitter=0.5)
    # jitter without an rng is inert
    assert jittered.delay_s(0) == 1.0
    # with an injected seeded rng: deterministic, inside [base, base*(1+j)]
    d1 = [jittered.delay_s(k, rng=random.Random(7)) for k in range(3)]
    d2 = [jittered.delay_s(k, rng=random.Random(7)) for k in range(3)]
    assert d1 == d2
    for k, d in enumerate(d1):
        lo = jittered.delay_s(k)
        assert lo <= d <= lo * 1.5
    with pytest.raises(ValueError, match="jitter"):
        RetryPolicy(jitter=-0.1)


def test_call_with_retry_forwards_rng():
    sleeps = []
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise OSError("transient")
        return "ok"

    policy = RetryPolicy(max_retries=3, backoff_base_s=1.0, jitter=1.0)
    out = call_with_retry(
        flaky, policy, sleep=sleeps.append, rng=random.Random(3)
    )
    assert out == "ok"
    expected_rng = random.Random(3)
    expected = [policy.delay_s(k, rng=expected_rng) for k in range(2)]
    assert sleeps == expected
    assert all(s > policy.delay_s(k) for k, s in enumerate(sleeps))


# -- circuit breaker unit ---------------------------------------------------
def test_circuit_breaker_lifecycle_deterministic():
    clock = FakeClock()
    br = CircuitBreaker(failure_threshold=2, cooldown_s=10.0, clock=clock)
    assert br.poll() == "closed"
    assert br.record_failure() is False  # 1 of 2
    br.record_success()  # run of failures must be CONSECUTIVE
    assert br.record_failure() is False
    assert br.record_failure() is True  # opened
    assert br.poll() == "open"
    clock.advance(9.0)
    assert br.poll() == "open"  # cooldown not elapsed
    clock.advance(1.0)
    assert br.poll() == "half_open"
    assert br.record_failure() is True  # failed probe re-opens (and counts)
    assert br.opened_total == 2
    clock.advance(10.0)
    assert br.poll() == "half_open"
    br.record_success()
    assert br.state == "closed" and br.consecutive_failures == 0


# -- behavior identity ------------------------------------------------------
def test_single_replica_no_failover_behavior_identical(tiny_model):
    """Acceptance: with 1 replica and failover disabled, the fleet layer
    adds no semantic drift — greedy outputs and accounting match driving
    the engine directly."""
    model, params = tiny_model
    prompts = _prompts()
    direct_clock = FakeClock()
    engine = ServingEngine(
        model, params, GEN, TABLE, clock=direct_clock, rng=jax.random.PRNGKey(1)
    )
    direct = engine.serve(prompts)

    clock = FakeClock()

    def factory():
        return ServingEngine(
            model, params, GEN, TABLE, clock=clock, rng=jax.random.PRNGKey(1)
        )

    fleet = FleetRouter([factory], clock=clock, failover=False)
    via_fleet = fleet.serve(prompts)
    assert all(np.array_equal(a, b) for a, b in zip(direct, via_fleet))
    s, es = fleet.stats(), engine.stats()
    assert s["submitted"] == es["requests"] == len(prompts)
    assert s["completed"] == es["completed"] == len(prompts)
    assert s["failovers"] == s["redispatches"] == s["breaker_opens"] == 0
    assert s["completed_by_replica"] == {"0": len(prompts)}


def test_load_aware_dispatch_spreads_and_attributes(tiny_model):
    fleet, _ = _make_fleet(tiny_model, n=3)
    reqs = [fleet.submit(p) for p in _prompts()]
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)
    by_replica = fleet.stats()["completed_by_replica"]
    # least-loaded dispatch over 3 idle 2-slot replicas spreads 6 requests
    assert sorted(by_replica) == ["0", "1", "2"]
    assert all(v > 0 for v in by_replica.values())
    assert sum(by_replica.values()) == len(reqs)


# -- THE drill: mid-decode replica kill ------------------------------------
def test_replica_crash_mid_decode_exactly_once_token_identical(
        tiny_model, reference_outputs):
    """Kill one of 3 replicas mid-decode: every accepted request completes
    exactly once, recovered outputs are token-identical to the no-fault
    run, and failover/span replica-id accounting reconciles with stats()."""
    chaos = ChaosRegistry()
    chaos.crash_replica(0, 3)  # replica 0's 3rd supervised step: mid-decode
    fleet, _ = _make_fleet(tiny_model, chaos=chaos)
    reqs = [fleet.submit(p) for p in _prompts()]
    fleet.run_until_idle()

    assert chaos.fired_count("fleet.replica_step.0") == 1
    assert [r.status for r in reqs] == ["ok"] * len(reqs)
    for got, want in zip(reqs, reference_outputs):
        assert np.array_equal(got.result, want)

    s = fleet.stats()
    # exactly once: every submission has ONE terminal disposition
    assert s["submitted"] == s["completed"] == len(reqs)
    assert s["failovers"] == 1
    assert s["replica_restarts"] == 1
    assert s["redispatches"] >= 1
    assert s["queued"] == s["dispatched"] == 0
    # the crashed replica's work moved: re-dispatched requests record > 1
    # dispatch attempts
    assert max(r.dispatches for r in reqs) > 1

    # span accounting closes: one terminal fleet.request span per
    # submission, and per-replica ok-span attribution == stats()
    spans = fleet.tracer.spans("fleet.request")
    assert len(spans) == len(reqs)
    by_replica = {}
    for sp in spans:
        assert sp.status == "ok"
        by_replica[str(sp.attrs["replica"])] = (
            by_replica.get(str(sp.attrs["replica"]), 0) + 1
        )
    # span attribution == stats attribution (stats also lists 0-completion
    # replicas, which emit no ok spans — the crashed replica is avoided by
    # every re-dispatch, so it may finish with 0)
    assert by_replica == {
        k: v for k, v in s["completed_by_replica"].items() if v
    }
    assert s["fleet_failover_total"] == 1  # canonical name mirrors short key


def test_hung_replica_failover_and_duplicate_dedupe(tiny_model,
                                                    reference_outputs):
    """A hung replica (step wall time past ``step_timeout_s``) fails over
    its in-flight work; its slow copies may still complete after breaker
    reintegration — those late duplicates are deduped by request id, never
    double-completing a request."""
    chaos = ChaosRegistry()
    chaos.hang_replica(1, 2, delay_s=50.0)
    fleet, clock = _make_fleet(
        tiny_model, chaos=chaos, step_timeout_s=10.0,
        breaker_threshold=1, breaker_cooldown_s=5.0,
    )
    reqs = [fleet.submit(p) for p in _prompts()]
    for _ in range(80):
        fleet.step()
        clock.advance(1.0)
        if not fleet.pending():
            break
    assert all(r.status == "ok" for r in reqs)
    for got, want in zip(reqs, reference_outputs):
        assert np.array_equal(got.result, want)
    # drain retires the hung replica's surviving stale copies; their late
    # completions land in the dedupe counter instead of the completed one
    fleet.drain()
    s = fleet.stats()
    assert s["failovers"] == 1
    assert s["breaker_opens"] == 1
    assert s["completed"] == len(reqs)  # exactly once, duplicates absorbed
    assert s["duplicate_results_ignored"] >= 1


def test_stale_copy_completion_wins_without_replay(tiny_model,
                                                   reference_outputs):
    """First-copy-wins even when the 'first copy' is the hung replica's own:
    with no survivor to re-dispatch to (1-replica fleet), the failed-over
    requests wait re-queued, the hung-but-alive replica keeps decoding its
    stale copies, and their completions FINALIZE the waiting requests —
    no duplicate counted, no wasted replay, never a second dispatch to the
    replica still holding the stale handle."""
    chaos = ChaosRegistry()
    chaos.hang_replica(0, 3, delay_s=50.0)
    fleet, clock = _make_fleet(
        tiny_model, n=1, chaos=chaos, step_timeout_s=10.0,
        breaker_threshold=2,  # one hang must not open the only replica
    )
    reqs = [fleet.submit(p) for p in _prompts(2, lengths=(5, 7))]
    for _ in range(40):
        fleet.step()
        clock.advance(0.1)
        if not fleet.pending():
            break
    assert [r.status for r in reqs] == ["ok", "ok"]
    for got, want in zip(reqs, reference_outputs):
        assert np.array_equal(got.result, want)
    s = fleet.stats()
    assert s["failovers"] == 1
    assert s["redispatches"] == 2  # both victims re-queued...
    assert all(r.dispatches == 1 for r in reqs)  # ...but never re-dispatched
    assert s["duplicate_results_ignored"] == 0  # a win is not a duplicate
    assert s["completed"] == 2


# -- circuit breaker drill --------------------------------------------------
def test_breaker_opens_blocks_dispatch_reintegrates(tiny_model):
    """A replica failing repeatedly is opened, receives no dispatches while
    open, and is reintegrated after a successful half-open probe —
    deterministic under FakeClock."""
    chaos = ChaosRegistry()
    chaos.crash_replica(0, 1, count=2)  # fails its first two steps
    fleet, clock = _make_fleet(
        tiny_model, n=2, chaos=chaos,
        breaker_threshold=2, breaker_cooldown_s=30.0,
    )
    replica0 = fleet.replicas[0]
    reqs = [fleet.submit(p) for p in _prompts(4)]
    # first crash: one breaker charge, victims steered AWAY from replica 0
    for _ in range(30):
        fleet.step()
        if chaos.fired_count("fleet.replica_step.0") >= 1:
            break
    assert replica0.breaker.state == "closed"  # 1 of 2 consecutive failures
    # fresh submissions carry no avoidance history, so they land on the
    # now-idle replica 0 — whose second scripted crash opens the breaker
    reqs += [fleet.submit(p) for p in _prompts()[4:6]]
    for _ in range(30):
        fleet.step()
        if replica0.breaker.state == "open":
            break
    assert replica0.breaker.state == "open"
    assert fleet.stats()["breaker_opens"] == 1
    assert fleet.registry.gauge("fleet_replicas_healthy") == 1

    # while open: no dispatches reach it — all remaining work lands on (and
    # completes via) replica 1
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)
    assert replica0.breaker.state == "open"
    assert not replica0.handles
    s = fleet.stats()
    assert s["completed_by_replica"]["0"] == 0
    assert s["completed_by_replica"]["1"] == len(reqs)

    # reintegration: cooldown elapses -> half_open -> ONE probe request ->
    # clean step closes the breaker and traffic returns
    clock.advance(30.0)
    probe = fleet.submit(_prompts()[0])
    fleet.step()
    assert replica0.breaker.state in ("half_open", "closed")
    fleet.run_until_idle()
    assert probe.status == "ok"
    assert replica0.breaker.state == "closed"
    assert fleet.registry.gauge("fleet_replicas_healthy") == 2
    assert fleet.stats()["completed_by_replica"]["0"] == 1


def test_dispatch_fault_redispatches_with_backoff(tiny_model):
    """A failed dispatch attempt (``fleet.dispatch`` chaos) charges the
    chosen replica's breaker and re-queues the request under the
    redispatch policy's backoff gate."""
    chaos = ChaosRegistry()
    chaos.fail_dispatch(1)  # the fleet's very first dispatch attempt
    fleet, clock = _make_fleet(
        tiny_model, n=2, chaos=chaos,
        redispatch_policy=RetryPolicy(max_retries=3, backoff_base_s=2.0),
    )
    req = fleet.submit(_prompts()[0])
    fleet.step()
    assert req.status == "queued" and req.dispatches == 1
    assert req.not_before == pytest.approx(2.0)  # backoff gate, FakeClock t0=0
    s = fleet.stats()
    assert s["redispatches"] == 1 and s["replica_failures"] == 1
    fleet.step()  # clock frozen: still gated
    assert req.status == "queued"
    clock.advance(2.0)
    fleet.run_until_idle()
    assert req.status == "ok" and req.dispatches == 2


def test_poisoned_replica_opens_breaker_and_retries_avoid_it(
        tiny_model, reference_outputs):
    """The module's motivating fault domain: one replica's executor fails
    every request (engine-level failures, step() itself returns normally).
    Those failures must charge the replica's breaker until it opens, and
    each retry must prefer any OTHER replica — never bounce straight back
    onto the poisoned executor until the fleet degrades below a single
    healthy engine."""
    model, params = tiny_model
    clock = FakeClock()

    def poisoned_factory():
        poison = ChaosRegistry()
        poison.add("serving.batch", "error", 1, count=10**6)
        return SlotServingEngine(
            model, params, GEN, TABLE, slots=2, clock=clock,
            rng=jax.random.PRNGKey(1), chaos=poison,
        )

    good = _slot_factory(tiny_model, clock)
    fleet = FleetRouter(
        [poisoned_factory, good, good], clock=clock,
        breaker_threshold=2, breaker_cooldown_s=1000.0,
    )
    reqs = [fleet.submit(p) for p in _prompts(4)]
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)  # nothing burned its budget
    for got, want in zip(reqs, reference_outputs):
        assert np.array_equal(got.result, want)
    s = fleet.stats()
    assert s["breaker_opens"] == 1  # the poisoned replica was taken out
    assert fleet.replicas[0].breaker.state == "open"
    assert s["completed_by_replica"]["0"] == 0
    assert s["redispatches"] >= 1
    # the retries went elsewhere on their SECOND attempt — not after
    # exhausting the budget against the same poisoned executor
    assert max(r.dispatches for r in reqs) == 2


def test_dispatch_fault_opening_breaker_fails_over_inflight(tiny_model):
    """A breaker opened from the DISPATCH-fault path must fail over the
    replica's in-flight requests too (an open replica is not stepped —
    without the failover they'd be stranded for the whole cooldown), and
    run_until_idle must raise the stall guard instead of spinning forever
    on a frozen clock."""
    chaos = ChaosRegistry()
    chaos.fail_dispatch(2)  # the dispatch of the SECOND request faults
    fleet, clock = _make_fleet(
        tiny_model, n=1, chaos=chaos,
        breaker_threshold=1, breaker_cooldown_s=5.0,
    )
    a = fleet.submit(_prompts()[0])
    fleet.step()  # dispatch attempt 1: A placed, replica decoding
    assert a.status == "dispatched"
    b = fleet.submit(_prompts()[1])
    fleet.step()  # attempt 2 faults -> breaker opens -> A failed over too
    s = fleet.stats()
    assert s["breaker_opens"] == 1 and s["failovers"] == 1
    assert a.status == "queued" and b.status == "queued"
    # frozen clock + only replica open: stall guard, not an infinite spin
    with pytest.raises(RuntimeError, match="fleet stalled"):
        fleet.run_until_idle()
    # cooldown elapses -> half-open -> the replica's surviving engine copy
    # of A finishes and WINS for the re-queued request (stale-copy dedupe),
    # the clean step closes the breaker, and B completes normally
    clock.advance(5.0)
    fleet.run_until_idle()
    assert a.status == "ok" and b.status == "ok"
    assert fleet.replicas[0].breaker.state == "closed"
    assert fleet.stats()["completed"] == 2


# -- fleet-level admission --------------------------------------------------
def test_fleet_admission_shed_deadline_and_reject(tiny_model):
    fleet, clock = _make_fleet(
        tiny_model, n=2, max_pending=2, default_deadline_s=5.0,
    )
    prompts = _prompts()
    fleet.submit(prompts[0])
    fleet.submit(prompts[1])
    with pytest.raises(QueueFull, match="max_pending=2") as exc_info:
        fleet.submit(prompts[2])
    assert exc_info.value.trace_id is not None  # joins against events.jsonl
    # infeasible prompts reject at the fleet front door (the engines'
    # shared check_feasible), before any replica sees them
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        fleet.submit(np.arange(1, 30, dtype=np.int32))
    # deadline: queued requests expire fleet-side without a dispatch
    clock.advance(6.0)
    fleet.step()
    s = fleet.stats()
    assert s["timed_out"] == 2 and s["shed"] == 1 and s["rejected"] == 1
    assert s["dispatches"] == 0
    # accounting closes: submitted == terminal dispositions (shed/rejected
    # never entered the queue)
    assert s["submitted"] == s["timed_out"] == 2
    # one terminal fleet.request span per queue entry + one per shed/reject
    spans = fleet.tracer.spans("fleet.request")
    assert sorted(sp.status for sp in spans) == [
        "rejected", "shed", "timed_out", "timed_out"
    ]


def test_failover_disabled_fails_inflight_terminally(tiny_model):
    chaos = ChaosRegistry()
    chaos.crash_replica(0, 2)
    fleet, _ = _make_fleet(tiny_model, n=2, chaos=chaos, failover=False)
    reqs = [fleet.submit(p) for p in _prompts(4)]
    fleet.run_until_idle()
    statuses = sorted(r.status for r in reqs)
    assert "failed" in statuses and "ok" in statuses
    s = fleet.stats()
    assert s["failovers"] == 0 and s["redispatches"] == 0
    assert s["completed"] + s["failed"] == len(reqs)
    failed = [r for r in reqs if r.status == "failed"]
    assert all("failover disabled" in r.error for r in failed)


def test_fleet_stall_guard_raises_instead_of_spinning(tiny_model):
    """All replicas scripted to crash on every step + a frozen FakeClock:
    run_until_idle raises instead of spinning on breaker cooldowns that can
    never elapse."""
    chaos = ChaosRegistry()
    chaos.crash_replica(0, 1, count=100)
    chaos.crash_replica(1, 1, count=100)
    fleet, _ = _make_fleet(
        tiny_model, n=2, chaos=chaos, breaker_threshold=1,
        breaker_cooldown_s=100.0,
        redispatch_policy=RetryPolicy(max_retries=10, backoff_base_s=0.0),
    )
    fleet.submit(_prompts()[0])
    with pytest.raises(RuntimeError, match="fleet stalled"):
        fleet.run_until_idle()


# -- operations -------------------------------------------------------------
def test_rolling_restart_completes_all_requests(tiny_model, reference_outputs):
    fleet, _ = _make_fleet(tiny_model, n=3)
    reqs = [fleet.submit(p) for p in _prompts()]
    for _ in range(2):
        fleet.step()  # work resident on every replica before the restart
    restarted = fleet.rolling_restart()
    assert restarted == 3
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)
    for got, want in zip(reqs, reference_outputs):
        assert np.array_equal(got.result, want)
    s = fleet.stats()
    assert s["replica_restarts"] == 3
    assert all(r.restarts == 1 for r in fleet.replicas)
    assert s["completed"] == len(reqs)


# -- satellite: slot-engine drain parity -----------------------------------
def test_slot_engine_drain_parity(tiny_model):
    """SlotServingEngine.drain(): queued AND resident (mid-generation)
    requests run to completion, new submissions are rejected, second call
    is a no-op — the same contract as ServingEngine.drain()."""
    model, params = tiny_model
    engine = SlotServingEngine(
        model, params, GEN, TABLE, slots=2, clock=FakeClock(),
        rng=jax.random.PRNGKey(1),
    )
    prompts = _prompts(4)
    reqs = [engine.submit(p) for p in prompts]
    engine.step()  # two requests now resident mid-generation, two queued
    assert engine.pending()
    drained = engine.drain()
    assert drained >= len(prompts) - 0  # every request disposed of
    assert all(r.status == "ok" for r in reqs)
    assert not engine.pending()
    with pytest.raises(RuntimeError, match="draining"):
        engine.submit(prompts[0])
    assert engine.drain() == 0  # idempotent


# -- obs report fleet section ----------------------------------------------
@pytest.mark.observability
def test_obs_report_fleet_section(tiny_model):
    """``obs report`` renders a fleet section from fleet.request spans +
    snapshot counters, and omits it for fleet-less artifacts."""
    from perceiver_io_tpu.observability.report import analyze, format_report

    chaos = ChaosRegistry()
    chaos.crash_replica(0, 3)
    fleet, _ = _make_fleet(tiny_model, chaos=chaos)
    reqs = [fleet.submit(p) for p in _prompts()]
    fleet.run_until_idle()
    assert all(r.status == "ok" for r in reqs)

    events = [sp.to_row() for sp in fleet.tracer.spans()]
    snapshot = fleet.registry.snapshot()
    analysis = analyze(events, snapshot)
    fl = analysis["fleet"]
    assert fl is not None
    s = fleet.stats()
    assert fl["terminal_spans"] == len(reqs)
    assert fl["by_status"] == {"ok": len(reqs)}
    assert fl["completed_by_replica"] == {
        k: v for k, v in s["completed_by_replica"].items() if v
    }
    assert fl["failovers"] == 1
    assert fl["replicas_healthy"] == 3
    rendered = format_report(analysis)
    assert "== fleet ==" in rendered
    assert "failovers" in rendered
    # fleet-less artifacts: no section
    assert analyze([], {})["fleet"] is None
    assert "== fleet ==" not in format_report(analyze([], {}))


# -- serve CLI --------------------------------------------------------------
@pytest.mark.slow
def test_serve_cli_fleet(tmp_path):
    """`clm serve --serve.replicas=2` routes through the FleetRouter: one
    JSON record per prompt, fleet-shaped serve stats."""
    from perceiver_io_tpu.scripts.text import clm as clm_script
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=16, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    save_pretrained(str(tmp_path / "ckpt"), params, cfg)
    (tmp_path / "prompts.txt").write_text("hello\nhi\nok\n")

    results = clm_script.main([
        "serve", "--ckpt", str(tmp_path / "ckpt"),
        f"--serve.prompts={tmp_path}/prompts.txt",
        "--serve.max_new_tokens=3", "--serve.num_latents=2",
        "--serve.prompt_buckets=8", "--serve.batch_buckets=2",
        "--serve.warmup=false", "--serve.replicas=2",
    ])
    assert [r["prompt"] for r in results] == ["hello", "hi", "ok"]
    assert all(r["status"] == "ok" for r in results)
    assert all(isinstance(r["completion"], str) for r in results)
    # fleet-supervision flags without a fleet hard-error instead of being
    # silently ignored (the CLI's inapplicable-flag convention)
    with pytest.raises(SystemExit, match="serve.replicas > 1"):
        clm_script.main([
            "serve", "--ckpt", str(tmp_path / "ckpt"),
            f"--serve.prompts={tmp_path}/prompts.txt",
            "--serve.max_new_tokens=3", "--serve.num_latents=2",
            "--serve.prompt_buckets=8", "--serve.batch_buckets=2",
            "--serve.warmup=false", "--serve.step_timeout_s=5",
        ])

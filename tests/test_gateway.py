"""HTTP/SSE streaming gateway tests (docs/serving.md "Streaming"): the
per-request incremental token sink on both engines, the cancellation-safe
slot-retirement route (slot + pool pages freed mid-generation, exactly one
terminal ``cancelled`` span), the asyncio gateway over real sockets
(greedy outputs token-identical to in-process ``generate()``, including
fleet-routed and paged-KV configurations), client-disconnect propagation
with the zero-leak invariant, the scripted mass-abandonment chaos drill,
socket-anchored TTFT, the loadgen HTTP client mode, and the ``obs report``
gateway section.

All CPU, tiny shapes, tier-1 under tight per-test budgets; socket tests
bind ephemeral localhost ports and run the gateway's event loop in a
daemon thread (the engine's single driver).
"""
import dataclasses
import http.client
import json
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference.generate import GenerationConfig, generate
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import (
    CausalLanguageModel,
    CausalLanguageModelConfig,
)
from perceiver_io_tpu.observability import (
    GatewayHttpClient,
    LoadGenerator,
    MetricsRegistry,
    Tracer,
    WorkloadSpec,
    to_prometheus_text,
)
from perceiver_io_tpu.observability import report as report_mod
from perceiver_io_tpu.observability.exporters import HELP_TEXT
from perceiver_io_tpu.reliability import ChaosRegistry, FakeClock, QueueFull
from perceiver_io_tpu.serving import (
    BucketTable,
    FleetRouter,
    ServingEngine,
    SlotServingEngine,
    StreamingGateway,
)
from perceiver_io_tpu.serving.gateway import GATEWAY_COUNTERS

pytestmark = [pytest.mark.gateway, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape another test module uses: executor cache keys
# include the module fingerprint, and an identically-configured model in
# another file would pre-populate the caches this file relies on warming.
TINY = dict(
    vocab_size=89, max_seq_len=32, max_latents=8, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)
GREEDY = SamplingConfig(temperature=0.0)
TABLE = BucketTable(prompt_lens=(8,), batch_sizes=(1,))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    return model, params


def _gcfg(max_new=4, num_latents=2, **kw):
    return GenerationConfig(
        max_new_tokens=max_new, num_latents=num_latents, sampling=GREEDY, **kw
    )


def _ref(model, params, prompt, cfg):
    """Unbucketed per-request generate(): the parity oracle."""
    return np.asarray(
        generate(model, params, jnp.asarray(np.asarray(prompt, np.int32)[None]), cfg)
    )[0]


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 80, size=int(n)).astype(np.int32) for n in lengths]


# -- http helpers -----------------------------------------------------------
def _post_generate(host, port, payload, timeout=60):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request(
        "POST", "/v1/generate", body=json.dumps(payload),
        headers={"Content-Type": "application/json"},
    )
    return conn, conn.getresponse()


def _read_stream(resp):
    """(tokens, terminal_record) off an SSE or JSON-lines response."""
    toks, term = [], None
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if not line:
            continue
        if line.startswith(b"data:"):
            line = line[5:].strip()
        rec = json.loads(line)
        if rec.get("done"):
            term = rec
            break
        toks.append(int(rec["token"]))
    return toks, term


def _get(host, port, path, timeout=30):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def _wait_for(predicate, timeout_s=20.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# -- the incremental token sink --------------------------------------------
@pytest.mark.timeout(120)
def test_slot_engine_on_token_streams_incrementally(tiny_model):
    """The engine-surface half of the tentpole: the slot engine delivers
    each token to the per-request sink the same step() that produced it —
    never all at retirement — and the streamed (index, token) sequence is
    exactly the final result's real tokens."""
    model, params = tiny_model
    cfg = _gcfg(max_new=5)
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, rng=jax.random.PRNGKey(1)
    )
    engine.warmup()
    prompts = _prompts(0, [4, 7])
    sinks = [[], []]
    reqs = [
        engine.submit(p, on_token=lambda i, t, s=sinks[j]: s.append((i, t)))
        for j, p in enumerate(prompts)
    ]
    growth = []
    while engine.pending():
        before = sum(len(s) for s in sinks)
        engine.step()
        growth.append(sum(len(s) for s in sinks) - before)
    # tokens arrived incrementally: at most one per resident per step,
    # across more than one step
    assert max(growth) <= 2 and sum(1 for g in growth if g > 0) >= 5
    for req, sink, p in zip(reqs, sinks, prompts):
        assert req.status == "ok"
        expect = _ref(model, params, p, cfg)
        np.testing.assert_array_equal(req.result, expect)
        assert sink == [(i, int(t)) for i, t in enumerate(expect)]


@pytest.mark.timeout(120)
def test_bucket_engine_on_token_batch_granular(tiny_model):
    """The bucket engine powers the same sink at batch granularity: no
    tokens until its micro-batch fence, then every real token in order
    (trimmed at EOS — pad filler after EOS never reaches the sink)."""
    model, params = tiny_model
    cfg = _gcfg(max_new=4)
    probe = _prompts(1, [5])[0]
    eos = int(_ref(model, params, probe, cfg)[1])  # greedy token at step 1
    cfg_eos = dataclasses.replace(cfg, eos_token_id=eos)
    engine = ServingEngine(model, params, cfg_eos, TABLE, rng=jax.random.PRNGKey(1))
    sink = []
    req = engine.submit(probe, on_token=lambda i, t: sink.append((i, t)))
    assert sink == []  # nothing streams before the batch runs
    engine.step()
    assert req.status == "ok"
    toks = req.result.tolist()
    expect = toks[: toks.index(eos) + 1]
    assert sink == [(i, int(t)) for i, t in enumerate(expect)]
    assert sink[-1][1] == eos and len(sink) < cfg.max_new_tokens


@pytest.mark.timeout(120)
def test_raising_sink_is_isolated(tiny_model):
    """A torn-down stream consumer (raising sink) must not fail the
    request it observes — counted, isolated, request completes ok."""
    model, params = tiny_model
    cfg = _gcfg(max_new=3)
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, rng=jax.random.PRNGKey(1)
    )

    def bad_sink(i, t):
        raise RuntimeError("consumer gone")

    req = engine.submit(_prompts(2, [4])[0], on_token=bad_sink)
    engine.run_until_idle()
    assert req.status == "ok"
    assert engine.registry.counter("serving_token_sink_errors_total") == 3


# -- cancel(): the new retirement route -------------------------------------
@pytest.mark.timeout(180)
def test_cancel_resident_frees_slot_and_pool_immediately(tiny_model):
    """The acceptance drill, engine-level: cancelling a resident request
    mid-generation frees its slot and returns ALL pool pages at the cancel
    instant (zero-leak via kv_pool_blocks_in_use), ends exactly one
    terminal ``cancelled`` span + one ``serving.cancelled`` event, never
    perturbs the surviving resident's tokens, and the freed slot admits
    the next queued request."""
    model, params = tiny_model
    cfg = _gcfg(max_new=8)
    tracer = Tracer()
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, kv_layout="paged",
        tracer=tracer, rng=jax.random.PRNGKey(1),
    )
    engine.warmup()
    prompts = _prompts(3, [5, 8, 6])
    reqs = [engine.submit(p) for p in prompts]
    for _ in range(2):
        engine.step()  # both residents admitted, 2 tokens each
    victim, survivor, queued = reqs
    in_use_before = engine._pool.in_use
    assert in_use_before > 0 and engine._pool.mapped_blocks(0) > 0
    assert engine.cancel(victim.request_id) is True
    # pages back the same instant — BEFORE the next step() runs
    assert engine._pool.mapped_blocks(0) == 0
    assert engine._pool.in_use < in_use_before
    assert engine._pool.frees_by_cause.get("cancelled", 0) > 0
    assert victim.status == "cancelled" and victim.result is None
    engine.run_until_idle()
    # survivors token-identical to the oracle, queued request admitted
    # into the freed slot and also identical
    np.testing.assert_array_equal(
        survivor.result, _ref(model, params, prompts[1], cfg)
    )
    np.testing.assert_array_equal(
        queued.result, _ref(model, params, prompts[2], cfg)
    )
    assert engine._pool.in_use == 0 and engine._pool.reserved == 0
    assert engine._pool.leaked() == 0
    terminal = [
        sp for sp in tracer.spans("serving.request") if sp.status == "cancelled"
    ]
    assert len(terminal) == 1 and terminal[0].trace_id == victim.trace_id
    events = tracer.spans("serving.cancelled")
    assert len(events) == 1 and events[0].attrs["stage"] == "resident"
    assert engine.health()["cancelled"] == 1
    stats = engine.stats()
    assert stats["cancelled"] == 1 and stats["completed"] == 2
    # cancelling an already-terminal request is a no-op
    assert engine.cancel(victim.request_id) is False


@pytest.mark.timeout(120)
def test_cancel_queued_and_mid_chunked_admission(tiny_model):
    """The other two lifecycle stages: a queued request leaves the queue
    (base-class route), and an in-flight chunked admission is dropped with
    its reserved pages returned before the row ever enters the state."""
    model, params = tiny_model
    cfg = _gcfg(max_new=3)
    tracer = Tracer()
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=1, kv_layout="paged",
        prefill_chunk=2, tracer=tracer, rng=jax.random.PRNGKey(1),
    )
    prompts = _prompts(4, [8, 4])
    admitting, queued = [engine.submit(p) for p in prompts]
    engine.step()  # starts the chunked admission for the 8-token prompt
    assert engine._admitting is not None
    assert engine.cancel(queued.request_id) is True  # still queued
    assert queued.status == "cancelled"
    assert engine.cancel(admitting.request_id) is True  # mid-admission
    assert admitting.status == "cancelled"
    assert engine._admitting is None
    assert engine._pool.in_use == 0 and engine._pool.reserved == 0
    stages = sorted(sp.attrs["stage"] for sp in tracer.spans("serving.cancelled"))
    assert stages == ["admitting", "queued"]
    assert not engine.pending()


@pytest.mark.timeout(180)
def test_fleet_cancel_and_ttft_anchor(tiny_model):
    """Fleet-level cancel reaches the dispatched copy's replica (slot +
    pages freed there) and finalizes exactly once; ttft_anchor_s passes
    through dispatch so a socket-accept anchor backdates the SLO-judged
    TTFT by exactly the anchor offset under FakeClock."""
    model, params = tiny_model
    cfg = _gcfg(max_new=4)
    clock = FakeClock(100.0)

    def factory():
        return SlotServingEngine(
            model, params, cfg, TABLE, slots=2, clock=clock,
            rng=jax.random.PRNGKey(1),
        )

    fleet = FleetRouter([factory, factory], clock=clock)
    prompts = _prompts(5, [5, 6])
    # anchored 3s before the fleet submit: the recorded TTFT must be
    # exactly 3000ms more than an unanchored request's (all other time is
    # frozen under FakeClock)
    anchored = fleet.submit(prompts[0], ttft_anchor_s=clock() - 3.0)
    plain = fleet.submit(prompts[1])
    fleet.run_until_idle()
    assert anchored.status == "ok" and plain.status == "ok"
    p_hi = fleet.registry.percentile("serving_ttft_ms", 100.0)
    p_lo = fleet.registry.percentile("serving_ttft_ms", 0.0)
    assert p_hi == pytest.approx(p_lo + 3000.0)
    # cancel a dispatched request mid-generation
    sink = []
    victim = fleet.submit(prompts[0], on_token=lambda i, t: sink.append(t))
    survivor = fleet.submit(prompts[1])
    fleet.step()
    fleet.step()
    assert victim.status == "dispatched" and len(sink) >= 1
    assert fleet.cancel(victim.request_id) is True
    assert victim.status == "cancelled"
    assert fleet.registry.counter("fleet_requests_cancelled_total") == 1
    replica_cancels = sum(
        r.engine.registry.counter("serving_requests_cancelled_total")
        for r in fleet.replicas
    )
    assert replica_cancels == 1
    fleet.run_until_idle()
    np.testing.assert_array_equal(
        survivor.result, _ref(model, params, prompts[1], cfg)
    )
    assert fleet.cancel(victim.request_id) is False
    assert fleet.stats()["cancelled"] == 1
    assert fleet.health()["cancelled"] == 1


# -- the gateway over real sockets ------------------------------------------
@pytest.mark.timeout(300)
def test_gateway_http_token_identity_paged(tiny_model):
    """THE acceptance pin: greedy outputs streamed over HTTP are
    token-identical to in-process generate() — through the paged-KV slot
    engine, with concurrent connections, both wire framings, and a
    per-request max_new_tokens override."""
    model, params = tiny_model
    cfg = _gcfg(max_new=5)
    tracer = Tracer()
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, kv_layout="paged",
        tracer=tracer, rng=jax.random.PRNGKey(1),
    )
    engine.warmup()
    gw = StreamingGateway(engine, tracer=tracer).run_in_thread()
    try:
        prompts = _prompts(6, [4, 7, 6])
        payloads = [
            {"prompt_ids": prompts[0].tolist()},  # default sse
            {"prompt_ids": prompts[1].tolist(), "stream": "jsonl"},
            {"prompt_ids": prompts[2].tolist(), "max_new_tokens": 3},
        ]
        results = [None] * 3

        def run_one(i):
            conn, resp = _post_generate(gw.host, gw.port, payloads[i])
            try:
                assert resp.status == 200
                results[i] = _read_stream(resp)
            finally:
                conn.close()

        threads = [
            threading.Thread(target=run_one, args=(i,)) for i in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        cfgs = [cfg, cfg, dataclasses.replace(cfg, max_new_tokens=3)]
        for (toks, term), p, c in zip(results, prompts, cfgs):
            assert term is not None and term["status"] == "ok"
            assert term["trace_id"] is not None
            np.testing.assert_array_equal(
                np.asarray(toks, np.int32), _ref(model, params, p, c)
            )
        assert engine._pool.in_use == 0 and engine._pool.leaked() == 0
        stats = gw.stats()
        assert stats["streams"] == 3 and stats["streams_completed"] == 3
        assert stats["streams_cancelled"] == 0 and stats["bytes_sent"] > 0
        # the stream's gateway.request event joins the engine trace
        gw_events = tracer.spans("gateway.request")
        assert len(gw_events) == 3
        assert {e.trace_id for e in gw_events} == {
            sp.trace_id for sp in tracer.spans("serving.request")
        }
        # socket TTFT (accept -> first byte out) is never below the
        # engine-side TTFT anchored at the same accept instant
        sock_p50 = engine.registry.percentile("gateway_socket_ttft_ms", 50.0)
        eng_p50 = engine.registry.percentile("serving_ttft_ms", 50.0)
        assert sock_p50 is not None and sock_p50 >= eng_p50 > 0.0
    finally:
        gw.close()


@pytest.mark.timeout(300)
def test_gateway_http_token_identity_fleet_and_bucket(tiny_model):
    """The same identity bar through a 2-replica fleet (the gateway's
    submit rides the router's dispatch + anchor plumbing) and through the
    bucket engine (batch-granular streaming)."""
    model, params = tiny_model
    cfg = _gcfg(max_new=4)

    def factory():
        return SlotServingEngine(
            model, params, cfg, TABLE, slots=2, rng=jax.random.PRNGKey(1)
        )

    fleet = FleetRouter([factory, factory], registry=MetricsRegistry())
    fleet.warmup()
    gw = StreamingGateway(fleet).run_in_thread()
    prompts = _prompts(7, [5, 7])
    try:
        for p in prompts:
            conn, resp = _post_generate(
                gw.host, gw.port, {"prompt_ids": p.tolist(), "stream": "jsonl"}
            )
            toks, term = _read_stream(resp)
            conn.close()
            assert term["status"] == "ok"
            np.testing.assert_array_equal(
                np.asarray(toks, np.int32), _ref(model, params, p, cfg)
            )
    finally:
        gw.close()
    # bucket engine: same wire protocol, tokens land in one burst
    engine = ServingEngine(model, params, cfg, TABLE, rng=jax.random.PRNGKey(1))
    gw2 = StreamingGateway(engine).run_in_thread()
    try:
        p = prompts[0]
        conn, resp = _post_generate(gw2.host, gw2.port, {"prompt_ids": p.tolist()})
        toks, term = _read_stream(resp)
        conn.close()
        assert term["status"] == "ok"
        np.testing.assert_array_equal(
            np.asarray(toks, np.int32), _ref(model, params, p, cfg)
        )
    finally:
        gw2.close()


# -- speculative burst flush ------------------------------------------------
def _read_stream_indexed(resp):
    """((index, token) pairs, terminal_record) — keeps the wire indices
    the per-stream ``sent`` cursor orders (``_read_stream`` drops them)."""
    pairs, term = [], None
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if not line:
            continue
        if line.startswith(b"data:"):
            line = line[5:].strip()
        rec = json.loads(line)
        if rec.get("done"):
            term = rec
            break
        pairs.append((int(rec["index"]), int(rec["token"])))
    return pairs, term


@pytest.mark.speculative
@pytest.mark.timeout(300)
@pytest.mark.slow  # 2026-08 audit: ~9s; burst-frame ordering is re-proved at
# the engine layer (test_speculative burst/ITL drill) — the SSE composition
# re-proof moves to `slow` depth
def test_gateway_speculative_burst_flushes_frames_in_index_order(tiny_model):
    """A speculative round that accepts a burst flushes one SSE frame PER
    token, in index order — never a coalesced multi-token frame, never out
    of order. On this 1-layer model a d=1 draft IS the full stack, so every
    proposal verifies and every non-tail round lands k+1 tokens at once."""
    model, params = tiny_model
    cfg = _gcfg(max_new=6)
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, kv_layout="paged",
        speculation="k4d1", rng=jax.random.PRNGKey(1),
    )
    engine.warmup()
    gw = StreamingGateway(engine).run_in_thread()
    try:
        for p in _prompts(11, [4, 7]):
            conn, resp = _post_generate(
                gw.host, gw.port, {"prompt_ids": p.tolist(), "stream": "jsonl"}
            )
            pairs, term = _read_stream_indexed(resp)
            conn.close()
            assert term is not None and term["status"] == "ok"
            # exact-once per index: the burst arrived as len(pairs) separate
            # frames numbered 0..n-1 in order
            assert [i for i, _ in pairs] == list(range(cfg.max_new_tokens))
            np.testing.assert_array_equal(
                np.asarray([t for _, t in pairs], np.int32),
                _ref(model, params, p, cfg),
            )
    finally:
        gw.close()
    spec = engine.stats()["speculation"]
    assert spec["mode"] == "k4d1" and spec["acceptance_rate"] == 1.0
    # far fewer verify rounds ran than frames hit the wire: the per-token
    # frames above really were flushed from multi-token engine steps
    assert spec["emitted"] == 2 * cfg.max_new_tokens
    assert spec["rounds"] < spec["emitted"] and spec["tokens_per_round"] > 1.0
    assert engine._pool.in_use == 0 and engine._pool.leaked() == 0


@pytest.mark.speculative
@pytest.mark.timeout(300)
@pytest.mark.slow  # 2026-08 audit: ~9s; replay dedup stays tier-1 in
# test_fleet.py (hung-replica failover drill) — the speculative-burst
# variant of the same cursor invariant moves to `slow` depth
def test_gateway_speculative_failover_replay_no_duplicate_indices(tiny_model):
    """Crash a replica mid-burst: the fleet re-runs the stream's request on
    the survivor, whose replay re-emits indices from 0 — the gateway's
    per-stream ``sent`` cursor drops the already-written prefix, so the wire
    sees every index exactly once and tokens stay identical to generate()."""
    model, params = tiny_model
    cfg = _gcfg(max_new=12)
    reg = MetricsRegistry()  # shared: outlives the crashed replica's restart

    def factory():
        return SlotServingEngine(
            model, params, cfg, TABLE, slots=2, speculation="k4d1",
            registry=reg, rng=jax.random.PRNGKey(1),
        )

    chaos = ChaosRegistry()
    chaos.crash_replica(0, 3)  # 3rd supervised step: >=1 burst already out
    fleet = FleetRouter([factory, factory], chaos=chaos)
    fleet.warmup()
    gw = StreamingGateway(fleet).run_in_thread()
    prompts = _prompts(12, [5, 7])
    results = [None, None]

    def run_one(i):
        conn, resp = _post_generate(
            gw.host, gw.port,
            {"prompt_ids": prompts[i].tolist(), "stream": "jsonl"},
        )
        try:
            results[i] = _read_stream_indexed(resp)
        finally:
            conn.close()

    try:
        threads = [
            threading.Thread(target=run_one, args=(i,)) for i in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        gw.close()

    assert chaos.fired_count("fleet.replica_step.0") == 1
    assert fleet.stats()["failovers"] >= 1
    for (pairs, term), p in zip(results, prompts):
        assert term is not None and term["status"] == "ok"
        # no duplicate indices across the replay: exactly 0..n-1, in order
        assert [i for i, _ in pairs] == list(range(cfg.max_new_tokens))
        np.testing.assert_array_equal(
            np.asarray([t for _, t in pairs], np.int32),
            _ref(model, params, p, cfg),
        )
    # the replay DID re-offer indices the wire already had: the engines
    # emitted strictly more on_token calls than frames were written
    emitted = reg.snapshot()["counters"]["spec_tokens_emitted_total"]
    assert emitted > sum(len(pairs) for pairs, _ in results)


@pytest.mark.timeout(300)
def test_gateway_client_disconnect_cancels_and_frees(tiny_model):
    """A real client disconnect mid-generation: the gateway notices the
    socket EOF, cancels the request (slot + every pool page freed, one
    terminal cancelled span), and the concurrent surviving stream's
    tokens are unchanged."""
    model, params = tiny_model
    cfg = _gcfg(max_new=16)
    tracer = Tracer()
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, kv_layout="paged",
        tracer=tracer, rng=jax.random.PRNGKey(1),
    )
    engine.warmup()
    orig_step = engine.step
    engine.step = lambda: (time.sleep(0.03), orig_step())[1]  # widen the window
    gw = StreamingGateway(engine, tracer=tracer).run_in_thread()
    prompts = _prompts(8, [5, 7])
    survivor_out = {}

    def survive():
        conn, resp = _post_generate(
            gw.host, gw.port, {"prompt_ids": prompts[1].tolist(), "stream": "jsonl"}
        )
        try:
            survivor_out["result"] = _read_stream(resp)
        finally:
            conn.close()

    t = threading.Thread(target=survive)
    try:
        # the victim: raw socket, read the response head + first token,
        # then vanish
        s = socket.create_connection((gw.host, gw.port), timeout=30)
        body = json.dumps(
            {"prompt_ids": prompts[0].tolist(), "stream": "jsonl"}
        ).encode()
        s.sendall(
            b"POST /v1/generate HTTP/1.1\r\nHost: t\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body
        )
        t.start()
        buf = b""
        while b'"token"' not in buf:
            chunk = s.recv(4096)
            assert chunk, "gateway closed the victim stream prematurely"
            buf += chunk
        s.close()  # the client vanishes mid-generation
        _wait_for(
            lambda: engine.registry.counter("serving_requests_cancelled_total") >= 1,
            what="disconnect-propagated cancellation",
        )
        t.join(60)
    finally:
        gw.close()
    toks, term = survivor_out["result"]
    assert term["status"] == "ok"
    np.testing.assert_array_equal(
        np.asarray(toks, np.int32), _ref(model, params, prompts[1], cfg)
    )
    assert engine._pool.in_use == 0 and engine._pool.reserved == 0
    assert engine._pool.leaked() == 0
    assert engine._pool.frees_by_cause.get("cancelled", 0) > 0
    terminal = [
        sp for sp in tracer.spans("serving.request") if sp.status == "cancelled"
    ]
    assert len(terminal) == 1
    stats = gw.stats()
    assert stats["streams_cancelled"] == 1 and stats["streams_completed"] == 1
    assert stats["streams"] == 2


@pytest.mark.timeout(300)
def test_gateway_chaos_mass_abandonment(tiny_model):
    """The chaos drill (acceptance): scripted ``gateway.disconnect`` faults
    abandon 50% of in-flight streams mid-generation; every survivor
    completes token_identical, zero slot/page leak, and disposition
    accounting reconciles (completed + cancelled == accepted streams)."""
    model, params = tiny_model
    cfg = _gcfg(max_new=10)
    chaos = ChaosRegistry()
    # streams are numbered in accept order: cut 1 and 3 before their 2nd token
    chaos.disconnect_stream(1, after_tokens=2)
    chaos.disconnect_stream(3, after_tokens=2)
    tracer = Tracer()
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, kv_layout="paged",
        tracer=tracer, rng=jax.random.PRNGKey(1),
    )
    engine.warmup()
    gw = StreamingGateway(engine, tracer=tracer, chaos=chaos).run_in_thread()
    prompts = _prompts(9, [5, 6, 7, 8])
    results = []
    try:
        conns = []
        # sequential connects pin the accept-order stream ids 1..4
        for p in prompts:
            conns.append(_post_generate(
                gw.host, gw.port, {"prompt_ids": p.tolist(), "stream": "jsonl"}
            ))
        for conn, resp in conns:
            results.append(_read_stream(resp))
            conn.close()
        _wait_for(
            lambda: gw.stats()["streams_completed"]
            + gw.stats()["streams_cancelled"] >= 4,
            what="all streams terminal",
        )
    finally:
        gw.close()
    victims = [results[0], results[2]]
    survivors = [(results[1], prompts[1]), (results[3], prompts[3])]
    for toks, term in victims:
        assert term is None  # cut before the terminal record
        assert len(toks) == 1  # exactly after_tokens - 1 made the wire
    for (toks, term), p in survivors:
        assert term is not None and term["status"] == "ok"
        np.testing.assert_array_equal(
            np.asarray(toks, np.int32), _ref(model, params, p, cfg)
        )
    # zero-leak + closed accounting
    assert engine._pool.in_use == 0 and engine._pool.reserved == 0
    assert engine._pool.leaked() == 0
    stats = gw.stats()
    assert stats["streams"] == 4
    assert stats["streams_cancelled"] == 2 and stats["streams_completed"] == 2
    counts = engine.registry.counters()
    assert counts["serving_requests_cancelled_total"] == 2
    assert counts["serving_requests_completed_total"] == 2
    assert counts["serving_requests_submitted_total"] == 4
    assert chaos.fired_count() == 2
    cancelled_events = [
        sp for sp in tracer.spans("gateway.request")
        if sp.status == "cancelled"
    ]
    assert len(cancelled_events) == 2


@pytest.mark.timeout(180)
def test_gateway_endpoints_and_rejections(tiny_model):
    """The non-streaming surface: /healthz LB semantics, /metrics with
    HELP lines, 404/405, 400 on bad JSON and infeasible prompts (engine
    rejection counters move), 503 + Retry-After on backpressure."""
    model, params = tiny_model
    cfg = _gcfg(max_new=3)
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, rng=jax.random.PRNGKey(1)
    )
    gw = StreamingGateway(engine).run_in_thread()
    try:
        status, body = _get(gw.host, gw.port, "/healthz")
        health = json.loads(body)
        assert status == 200 and health["ready"] is True
        assert "cancelled" in health  # the extended shared schema
        status, body = _get(gw.host, gw.port, "/metrics")
        assert status == 200
        text = body.decode()
        for family in GATEWAY_COUNTERS:
            assert f"# HELP {family} " in text, family
        status, _ = _get(gw.host, gw.port, "/nope")
        assert status == 404
        conn = http.client.HTTPConnection(gw.host, gw.port, timeout=30)
        conn.request("GET", "/v1/generate")
        assert conn.getresponse().status == 405
        conn.close()
        conn, resp = _post_generate(gw.host, gw.port, None)  # "null" body
        assert resp.status == 400
        conn.close()
        # infeasible: longer than the largest bucket -> 400 with the
        # engine's own error + trace id, rejected counters on both layers
        conn, resp = _post_generate(
            gw.host, gw.port, {"prompt_ids": list(range(1, 20))}
        )
        assert resp.status == 400
        detail = json.loads(resp.read())
        assert "exceeds the largest bucket" in detail["error"]
        conn.close()
        assert engine.registry.counter("serving_requests_rejected_total") == 1
        assert engine.registry.counter("gateway_streams_rejected_total") == 2
        # malformed FIELDS are clean 400s too, never a bare connection
        # reset out of a dead handler (review hardening)
        for bad in ({"prompt_ids": [1, 2], "deadline_s": "5"},
                    {"prompt_ids": [1, 2], "max_new_tokens": [4]},
                    {"prompt_ids": "not-ids"},
                    # remote buffer-sizing is bounded: absurd or
                    # non-positive max_new overrides are 400s, never an
                    # allocation (review hardening)
                    {"prompt_ids": [1, 2], "max_new_tokens": 10**9},
                    {"prompt_ids": [1, 2], "max_new_tokens": 0}):
            conn, resp = _post_generate(gw.host, gw.port, bad)
            assert resp.status == 400, bad
            assert "error" in json.loads(resp.read())
            conn.close()
        assert engine.registry.counter("gateway_streams_rejected_total") == 7
        # an attacker-sized Content-Length is answered 413 and never
        # buffered
        s = socket.create_connection((gw.host, gw.port), timeout=30)
        s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                  b"Content-Length: 9999999999\r\n\r\n")
        head = s.recv(4096)
        assert b"413" in head.split(b"\r\n", 1)[0]
        s.close()
    finally:
        gw.close()

    # backpressure -> 503 (stubbed engine: deterministic without racing a
    # real queue)
    class SheddingStub:
        registry = MetricsRegistry()
        tracer = None

        def submit(self, *a, **k):
            raise QueueFull("stub at capacity")

        def pending(self):
            return False

        def step(self):
            return 0

        def health(self):
            return {"ready": False}

        def cancel(self, request_id):
            return False

    gw2 = StreamingGateway(SheddingStub()).run_in_thread()
    try:
        conn, resp = _post_generate(gw2.host, gw2.port, {"prompt_ids": [1, 2, 3]})
        assert resp.status == 503
        assert resp.getheader("Retry-After") == "1"
        assert "stub at capacity" in json.loads(resp.read())["error"]
        conn.close()
        status, _ = _get(gw2.host, gw2.port, "/healthz")
        assert status == 503  # not ready -> LB pulls the backend
    finally:
        gw2.close()
    with pytest.raises(ValueError, match="stream must be one of"):
        StreamingGateway(SheddingStub(), stream="bogus")


# -- loadgen http client mode -----------------------------------------------
@pytest.mark.timeout(300)
def test_loadgen_http_mode_over_gateway(tiny_model):
    """The loadgen satellite: the same LoadGenerator drives the full
    network path through GatewayHttpClient — goodput accounting via the
    shared slo.py helpers, bytes-on-wire reported beside offered/completed."""
    model, params = tiny_model
    cfg = _gcfg(max_new=3)
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, tracer=Tracer(),
        rng=jax.random.PRNGKey(1),
    )
    engine.warmup()
    gw = StreamingGateway(engine).run_in_thread()
    try:
        client = GatewayHttpClient(gw.host, gw.port)
        gen = LoadGenerator(
            client,
            workload=WorkloadSpec(prompt_len=(4, 8), max_new_tokens=(2, 3),
                                  vocab=(1, 80)),
            mode="open", arrival="uniform", rate_rps=50.0, max_requests=5,
            config=cfg, rng=3,
        )
        report = gen.run()
    finally:
        gw.close()
    assert report["offered"] == 5 and report["completed"] == 5
    assert report["goodput_ratio"] == 1.0
    assert report["bytes_on_wire"] > 0
    assert all(h.status == "ok" for h in gen.handles)
    # streamed tokens round-trip: each handle's result matches the oracle
    for h in gen.handles:
        assert h.result is not None and h.result.size >= 2
        assert h.trace_id is not None
    # shed maps back to QueueFull at submit (503), reject to ValueError
    # (400) — over a stub gateway so the mapping is deterministic
    class SheddingStub:
        registry = MetricsRegistry()
        tracer = None

        def submit(self, *a, **k):
            raise QueueFull("stub at capacity")

        def pending(self):
            return False

        def step(self):
            return 0

        def health(self):
            return {"ready": False}

        def cancel(self, request_id):
            return False

    gw2 = StreamingGateway(SheddingStub()).run_in_thread()
    try:
        client2 = GatewayHttpClient(gw2.host, gw2.port)
        with pytest.raises(QueueFull, match="503"):
            client2.submit(np.asarray([1, 2, 3], np.int32))
    finally:
        gw2.close()
    # a transport-level failure is ONE failed request, not a crashed run:
    # the client returns a terminal handle the generator's accounting
    # absorbs (review hardening)
    dead = GatewayHttpClient("127.0.0.1", 9, timeout_s=0.5)  # discard port
    handle = dead.submit(np.asarray([1, 2], np.int32))
    assert handle.status == "failed" and handle.error
    assert not dead.pending()


# -- obs report + HELP satellites -------------------------------------------
@pytest.mark.timeout(60)
def test_report_gateway_section_pinned_over_fixtures():
    """The fixture satellite: the checked-in artifacts render the gateway
    section with pinned values — connection/stream table, cancellation
    counts, socket-vs-engine TTFT deltas."""
    analysis = json.loads(report_mod.run(
        "tests/fixtures/events.jsonl",
        "tests/fixtures/metrics_snapshot.json", as_json=True,
    ))
    gw = analysis["gateway"]
    assert gw["connections"] == {"total": 5, "active": 0}
    assert gw["streams"]["total"] == 5
    assert gw["streams"]["completed"] == 4
    assert gw["streams"]["cancelled"] == 1
    assert gw["streams"]["by_status"] == {"cancelled": 1, "ok": 4}
    assert gw["streams"]["tokens_streamed"] == 12
    assert gw["cancellations"]["events"] == 1
    assert gw["cancellations"]["requests_cancelled"] == 1
    assert gw["socket_ttft"]["p50_ms"] == 42.0
    assert gw["socket_vs_engine_ttft_delta_ms"] == {
        "p50_ms": 2.0, "p95_ms": 3.0,
    }
    # the cancelled request reached the terminal-span table too
    assert analysis["requests"]["by_status"]["cancelled"] == 1
    text = report_mod.run(
        "tests/fixtures/events.jsonl", "tests/fixtures/metrics_snapshot.json"
    )
    assert "== gateway ==" in text
    assert "streams: 5 accepted  completed=4  cancelled=1  rejected=0" in text
    assert "socket-vs-engine ttft delta ms: p50=2.0 p95=3.0" in text
    # artifacts without a gateway render no section (old runs unchanged)
    assert report_mod.analyze([], {})["gateway"] is None
    # events-only fallback (no snapshot): stream counts derive from the
    # gateway.request events' terminal statuses, no literal None rendering
    rows = [
        {"span": "gateway.request", "trace_id": "t1", "start_s": 0.0,
         "duration_ms": 0.0, "status": "ok", "attrs": {"tokens": 3, "bytes": 10}},
        {"span": "gateway.request", "trace_id": "t2", "start_s": 0.0,
         "duration_ms": 0.0, "status": "cancelled",
         "attrs": {"tokens": 1, "bytes": 4}},
    ]
    fallback = report_mod.analyze(rows, None)["gateway"]
    assert fallback["source"] == "events"
    assert fallback["streams"]["total"] == 2
    assert fallback["streams"]["completed"] == 1
    assert fallback["streams"]["cancelled"] == 1
    rendered = report_mod.format_report(report_mod.analyze(rows, None))
    section = rendered.split("== gateway ==")[1].split("\n==")[0]
    assert "(from events)" in section and "None" not in section


@pytest.mark.timeout(180)
def test_every_gateway_family_has_direct_help(tiny_model):
    """The HELP satellite (PR 9 convention): every family a
    traffic-bearing gateway + engine publishes — gateway_* and the new
    cancelled counters included — has a non-fallback # HELP line."""
    model, params = tiny_model
    cfg = _gcfg(max_new=3)
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, kv_layout="paged",
        rng=jax.random.PRNGKey(1),
    )
    gw = StreamingGateway(engine).run_in_thread()
    try:
        conn, resp = _post_generate(
            gw.host, gw.port, {"prompt_ids": _prompts(12, [5])[0].tolist()}
        )
        _read_stream(resp)
        conn.close()
    finally:
        gw.close()
    snap = engine.registry.snapshot()
    published = (
        set(snap["counters"]) | set(snap["gauges"]) | set(snap["histograms"])
    )
    assert set(GATEWAY_COUNTERS) <= published
    assert "gateway_socket_ttft_ms" in published
    assert "serving_requests_cancelled_total" in published
    missing = sorted(n for n in published if n not in HELP_TEXT)
    assert not missing, f"families without a direct HELP entry: {missing}"
    text = to_prometheus_text(engine.registry)
    for name in published:
        assert f"# HELP {name} " in text, name


# -- CLI flag surface --------------------------------------------------------
@pytest.mark.timeout(60)
def test_serve_http_flag_group():
    """--serve.http.* is a real nested flag group: specs exist, values
    build, defaults keep the gateway off."""
    from perceiver_io_tpu.scripts.cli import ServeArgs, build_dataclass, flag_specs

    specs = flag_specs(ServeArgs, "serve")
    for flag in ("serve.http.port", "serve.http.host", "serve.http.stream",
                 "serve.http.max_streams"):
        assert flag in specs, flag
    args = build_dataclass(ServeArgs, {
        "serve.http.port": "0", "serve.http.stream": "jsonl",
        "serve.http.max_streams": "3",
    }, "serve")
    assert args.http.port == 0 and args.http.stream == "jsonl"
    assert args.http.max_streams == 3
    assert build_dataclass(ServeArgs, {}, "serve").http.port is None

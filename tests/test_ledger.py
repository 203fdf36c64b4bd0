"""Device-cost ledger + `obs report` suite (docs/observability.md:
``observability/ledger.py``, ``observability/report.py``).

The load-bearing acceptance tests:

- every executor build in a warmed-up slot-engine run appears in the
  ledger with compile time and XLA memory analysis, steady-state traffic
  adds NOTHING, and a post-warmup rebuild (a flipped trace-env knob)
  carries an attributed retrace reason;
- ``obs report`` over a recorded ``events.jsonl`` + snapshot reproduces
  the request-latency breakdown ``stats()`` reported at record time —
  exactly under FakeClock, to rounding on the wall clock;
- with an injected clock the ledger's records are a pure function of the
  build sequence (the determinism contract the module docstring pins);
- observation never changes execution semantics: an un-lowerable or
  strict-signature-drifting executor silently demotes to plain jit.

All pure-CPU, tiny shapes — tier-1 under the ``observability`` marker.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    ledger_model_id,
    reset_executor_caches,
)
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.observability import (
    CompileLedger,
    JsonlSpanSink,
    MetricsRegistry,
    SnapshotWriter,
    Tracer,
    default_ledger,
    read_events_jsonl,
)
from perceiver_io_tpu.observability import report as report_mod
from perceiver_io_tpu.reliability import FakeClock
from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

pytestmark = [pytest.mark.observability, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use (vocab 59): executor
# cache keys and ledger identities include the module fingerprint, and an
# identically configured model elsewhere would pre-populate what this
# file counts.
TINY = dict(
    vocab_size=59, max_seq_len=16, max_latents=8, num_channels=8,
    num_heads=1, num_self_attention_layers=1, cross_attention_dropout=0.0,
)
GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 16), jnp.int32), 8)["params"]
    return model, params


def _prompts(lengths, vocab=59):
    rng = np.random.default_rng(0)
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lengths]


class _ScriptClock:
    """Pops pre-scripted monotonic times — two reads per ledger build
    (compile t0/t1), so compile_ms values are exact."""

    def __init__(self, times):
        self._times = list(times)

    def __call__(self):
        return self._times.pop(0)


def _build_sequence(ledger):
    """One fixed build sequence: cold, bucket retrace, double retrace,
    duplicate key, and a second (independent) identity."""
    specs = [
        ("generate", {"model": "m1", "bucket_shape": "1x4", "trace_env": "a"}),
        ("generate", {"model": "m1", "bucket_shape": "1x8", "trace_env": "a"}),
        ("generate", {"model": "m1", "bucket_shape": "1x4", "trace_env": "b"}),
        ("generate", {"model": "m1", "bucket_shape": "1x4", "trace_env": "b"}),
        ("generate", {"model": "m2", "bucket_shape": "1x4", "trace_env": "b"}),
    ]
    for i, (site, comps) in enumerate(specs):
        # distinct constants => distinct programs, so jit caching between
        # repeated sequences never skips a build
        fn = jax.jit(lambda x, k=i: x + k)
        ledger.wrap(fn, site=site, components=comps)(jnp.float32(1.0))


# -- retrace attribution ------------------------------------------------------
def test_cold_compile_and_retrace_attribution():
    """First build of an identity is a cold compile; rebuilds count under
    every changed component; an unchanged rebuild is ``duplicate_key``; a
    different model is a fresh identity (docs/observability.md reason names)."""
    reg = MetricsRegistry()
    ledger = CompileLedger(registry=reg, clock=FakeClock())
    _build_sequence(ledger)
    recs = ledger.records()
    assert [r["retrace_reasons"] for r in recs] == [
        [], ["bucket_shape"], ["bucket_shape", "trace_env"],
        ["duplicate_key"], [],
    ]
    assert [r["retrace"] for r in recs] == [False, True, True, True, False]
    assert reg.counter("compile_total") == 5
    assert reg.counter("retrace_total") == 3
    assert reg.counter("retrace_reason_bucket_shape_total") == 2
    assert reg.counter("retrace_reason_trace_env_total") == 1
    assert reg.counter("retrace_reason_duplicate_key_total") == 1
    snap = ledger.snapshot()
    assert snap["compiles"] == 5 and snap["retraces"] == 3
    assert snap["retrace_reasons"] == {
        "bucket_shape": 2, "duplicate_key": 1, "trace_env": 1,
    }


def test_ledger_determinism_under_injected_clock():
    """With an injected clock the records — ordering, sequence numbers,
    reasons, compile_ms — are a pure function of the build sequence: two
    fresh ledgers fed the same sequence produce identical tables."""
    def run(clock):
        ledger = CompileLedger(registry=MetricsRegistry(), clock=clock)
        _build_sequence(ledger)
        return ledger.records()

    assert run(FakeClock()) == run(FakeClock())
    # scripted compile times survive into the records exactly
    times = [0.0, 0.5, 1.0, 1.25, 2.0, 2.75, 3.0, 3.001, 4.0, 4.25]
    recs = run(_ScriptClock(times))
    assert [r["compile_ms"] for r in recs] == [500.0, 250.0, 750.0, 1.0, 250.0]
    assert [r["seq"] for r in recs] == [1, 2, 3, 4, 5]
    assert recs == run(_ScriptClock(times))


def test_wrapped_executor_result_and_memory_analysis():
    """The wrapper is semantically transparent and the record carries the
    XLA cost/memory analysis (CPU implements both; gauges come along)."""
    reg = MetricsRegistry()
    ledger = CompileLedger(registry=reg)
    w = jnp.arange(16.0, dtype=jnp.float32).reshape(4, 4)
    fn = jax.jit(lambda x: x @ x.T)
    wrapped = ledger.wrap(fn, site="bench", components={"model": "t"})
    x = jnp.ones((4, 4), jnp.float32) + w
    np.testing.assert_allclose(np.asarray(wrapped(x)), np.asarray(fn(x)))
    np.testing.assert_allclose(np.asarray(wrapped(x)), np.asarray(fn(x)))
    (rec,) = ledger.records()
    assert rec["site"] == "bench" and rec["compile_ms"] >= 0.0
    assert rec["flops"] and rec["flops"] > 0
    assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
    assert isinstance(rec["output_bytes"], int) and rec["output_bytes"] > 0
    assert isinstance(rec["argument_bytes"], int)
    assert isinstance(rec["temp_bytes"], int)
    assert reg.gauge("executor_resident_bytes") == (
        rec["temp_bytes"] + rec["output_bytes"]
    )
    # a rebuild of the SAME (site, components) executor replaces its bytes
    # in the gauge rather than double-counting (exactly one is live)
    ledger.wrap(
        jax.jit(lambda x: x @ x.T), site="bench", components={"model": "t"}
    )(x)
    assert len(ledger.records()) == 2
    assert reg.gauge("executor_resident_bytes") == (
        rec["temp_bytes"] + rec["output_bytes"]
    )
    # CPU has no device memory_stats(): the HBM gauge is skipped, not faked
    assert ledger.update_device_gauges() is None or reg.gauge("hbm_bytes_in_use") > 0
    ledger.set_kv_cache_bytes(4096)
    assert reg.gauge("kv_cache_resident_bytes") == 4096


def test_fallback_never_changes_semantics():
    """An un-lowerable callable and a strict-signature drift both demote to
    the plain path with the fallback counter bumped — the run proceeds
    exactly as before the ledger existed."""
    reg = MetricsRegistry()
    ledger = CompileLedger(registry=reg)
    plain = ledger.wrap(lambda x: x + 1, site="generate", components={})
    assert plain(41) == 42 and plain(1) == 2
    assert reg.counter("compile_ledger_fallback_total") == 1
    assert ledger.records() == []

    # AOT executables are shape-strict; a drifting call demotes to jit
    drifting = ledger.wrap(
        jax.jit(lambda x: x * 2), site="generate", components={"model": "d"}
    )
    np.testing.assert_allclose(np.asarray(drifting(jnp.ones(3))), 2.0)
    assert reg.gauge("executor_resident_bytes") > 0
    np.testing.assert_allclose(np.asarray(drifting(jnp.ones(5))), 2.0)
    np.testing.assert_allclose(np.asarray(drifting(jnp.ones(7))), 2.0)
    assert reg.counter("compile_ledger_fallback_total") == 2
    # the demoted executor's AOT executable is gone — so are its bytes
    assert reg.gauge("executor_resident_bytes") == 0


def test_records_bound_attach_and_reset():
    reg = MetricsRegistry()
    ledger = CompileLedger(registry=reg, clock=FakeClock(), keep=2)
    seen = []
    detach = ledger.attach(seen.append)
    boom = ledger.attach(lambda rec: 1 / 0)  # raising callback is swallowed
    _build_sequence(ledger)
    assert len(ledger.records()) == 2  # FIFO bound
    assert reg.counter("compile_total") == 5  # counters keep counting past it
    # the rollup is lifetime too — it must agree with the registry, not
    # with the keep-bounded table
    roll = ledger.rollup()
    assert roll["compiles"] == 5 and roll["retraces"] == 3
    assert roll["compile_ms_total"] == 0.0  # FakeClock: every build 0 ms
    assert [r["seq"] for r in seen] == [1, 2, 3, 4, 5]
    detach()
    boom()
    jj = jax.jit(lambda x: x - 9)
    ledger.wrap(jj, site="generate", components={"model": "m3"})(jnp.float32(1))
    assert len(seen) == 5  # detached
    ledger.reset()
    assert ledger.records() == []
    assert ledger.rollup()["compiles"] == 0
    # the executors the gauge described are gone with the reset
    assert reg.gauge("executor_resident_bytes") == 0
    # post-reset, the same components are a cold compile again, not a retrace
    ledger.wrap(
        jax.jit(lambda x: x - 9.5), site="generate",
        components={"model": "m3"},
    )(jnp.float32(1))
    assert ledger.records()[0]["retrace_reasons"] == []


# -- warmed-up engine acceptance ---------------------------------------------
def test_warmed_slot_engine_builds_all_in_ledger_and_report(
        tiny_model, tmp_path, monkeypatch):
    """The tentpole acceptance run, end to end: warmup puts EVERY executor
    build in the ledger with compile time + memory analysis (bucket/boundary
    retraces attributed), steady-state traffic adds nothing, a flipped
    trace-env knob is attributed as ``trace_env``, stats() carries the
    rollup, and `obs report` over the recorded events + snapshot reproduces
    the request-latency breakdown stats() reports."""
    monkeypatch.delenv("PERCEIVER_RAGGED_KERNEL", raising=False)
    reset_executor_caches()
    default_ledger().reset()
    model, params = tiny_model
    mid = ledger_model_id(model)
    events_path = str(tmp_path / "events.jsonl")
    sink = JsonlSpanSink(events_path)
    tracer = Tracer(sink=sink)
    reg = MetricsRegistry()
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(4, 8), batch_sizes=(1,)),
        slots=2, registry=reg, tracer=tracer,
    )
    # the engine published its analytic KV footprint at construction
    kv_bytes = reg.gauge("kv_cache_resident_bytes")
    assert kv_bytes and kv_bytes > 0
    assert default_ledger().registry.gauge("kv_cache_resident_bytes") == kv_bytes

    builds = engine.warmup()
    ledger = default_ledger()
    mine = [r for r in ledger.records() if r["components"].get("model") == mid]
    # every build the warmup counted appears in the ledger, analyzed
    assert len(mine) == builds == 4  # prefill x2 buckets + decode x2 variants
    assert {r["site"] for r in mine} == {"slot_prefill", "slot_decode"}
    for rec in mine:
        assert rec["compile_ms"] >= 0.0
        assert isinstance(rec["output_bytes"], int)
        assert isinstance(rec["temp_bytes"], int)
        assert rec["flops"] is None or rec["flops"] > 0
    prefills = [r for r in mine if r["site"] == "slot_prefill"]
    decodes = [r for r in mine if r["site"] == "slot_decode"]
    assert prefills[0]["retrace_reasons"] == []
    assert prefills[1]["retrace_reasons"] == ["bucket_shape"]
    assert decodes[0]["retrace_reasons"] == []
    assert decodes[1]["retrace_reasons"] == ["boundary"]

    # steady-state mixed traffic compiles NOTHING new
    for p in _prompts((3, 4, 7)):
        engine.submit(p)
    engine.run_until_idle()
    assert len([r for r in ledger.records()
                if r["components"].get("model") == mid]) == 4

    # a post-warmup trace-env flip rebuilds, attributed as trace_env
    monkeypatch.setenv("PERCEIVER_RAGGED_KERNEL", "1")
    engine.submit(_prompts((4,))[0])
    engine.run_until_idle()
    rebuilt = [r for r in ledger.records()
               if r["components"].get("model") == mid][4:]
    assert rebuilt and all(r["retrace"] for r in rebuilt)
    assert all("trace_env" in r["retrace_reasons"] for r in rebuilt)

    # stats() ships the rollup (no per-record bulk); reasons surfaced
    stats = engine.stats()
    roll = stats["compile_ledger"]
    assert "records" not in roll
    assert roll["compiles"] == len(ledger.records())
    assert roll["retrace_reasons"]["bucket_shape"] >= 1
    assert roll["retrace_reasons"]["trace_env"] >= 1
    assert stats["completed"] == 4

    # `obs report` over the recorded artifacts reproduces the
    # request-latency breakdown stats() reports (same Histogram, same
    # nearest-rank; the span end re-reads the clock after the backdated
    # start, so durations sit a few tens of µs above the histogram values)
    sink.close()
    snap_path = str(tmp_path / "snapshot.json")
    SnapshotWriter(
        reg, snap_path,
        extra=lambda: {"compile_ledger": ledger.snapshot()},
    ).maybe_write(force=True)
    text = report_mod.run(events_path, snap_path)
    analysis = report_mod.analyze(
        read_events_jsonl(events_path), json.load(open(snap_path))
    )
    lat = analysis["requests"]["latency"]
    assert analysis["requests"]["terminal_spans"] == 4
    assert analysis["requests"]["by_status"] == {"ok": 4}
    for p, key in ((50.0, "p50_ms"), (95.0, "p95_ms")):
        assert lat[key] == pytest.approx(
            reg.percentile("serving_request_latency_ms", p), abs=0.5
        )
    comp = analysis["compiles"]
    assert comp["source"] == "snapshot"
    assert comp["count"] == len(ledger.records())
    assert comp["retrace_reasons"] == roll["retrace_reasons"]
    assert "== compile/memory ledger ==" in text
    assert "slot_prefill[1x4]" in text and "trace_env" in text
    reset_executor_caches()


# -- the offline analyzer -----------------------------------------------------
def test_report_latency_breakdown_matches_registry_exactly():
    """Under FakeClock the analyzer's request-latency percentiles equal the
    registry's bit-for-bit: both run the same nearest-rank Histogram."""
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    tracer = Tracer(clock=clock)
    rows = []
    for ms in (100.0, 40.0, 250.0, 10.0, 75.0):
        span = tracer.start_span("serving.request")
        clock.advance(ms / 1e3)
        rows.append(tracer.end_span(span).to_row())
        reg.observe("serving_request_latency_ms", ms)
    analysis = report_mod.analyze(rows)
    lat = analysis["requests"]["latency"]
    assert lat["count"] == 5
    assert lat["p50_ms"] == reg.percentile("serving_request_latency_ms", 50.0)
    assert lat["p95_ms"] == reg.percentile("serving_request_latency_ms", 95.0)
    assert lat["max_ms"] == 250.0
    # the waterfall picks the slowest trace and offsets spans from submit
    worst = analysis["worst_request"]
    assert worst["duration_ms"] == 250.0
    assert worst["spans"][0]["offset_ms"] == 0.0


def test_report_compile_table_falls_back_to_events():
    """Without a snapshot the compile table is rebuilt from the
    ``ledger.compile`` events the serve CLI forwards; reasons re-aggregate
    from the rows."""
    rows = [
        {"span": "ledger.compile", "trace_id": "t1", "duration_ms": 0.0,
         "status": "ok", "attrs": {
             "site": "slot_prefill", "compile_ms": 12.5, "flops": 100.0,
             "bytes_accessed": 64.0, "temp_bytes": 8, "output_bytes": 16,
             "argument_bytes": 4, "retrace": False, "reasons": "",
             "bucket_shape": "1x4"}},
        {"span": "ledger.compile", "trace_id": "t1", "duration_ms": 0.0,
         "status": "ok", "attrs": {
             "site": "slot_prefill", "compile_ms": 7.5, "retrace": True,
             "reasons": "bucket_shape,trace_env"}},
    ]
    analysis = report_mod.analyze(rows)
    comp = analysis["compiles"]
    assert comp["source"] == "events"
    assert comp["count"] == 2 and comp["retraces"] == 1
    assert comp["retrace_reasons"] == {"bucket_shape": 1, "trace_env": 1}
    assert comp["compile_ms_total"] == 20.0
    # the forwarded bucket_shape survives, so per-bucket rows render tagged
    assert comp["records"][0]["components"] == {"bucket_shape": "1x4"}
    assert "slot_prefill[1x4]" in report_mod.format_report(analysis)
    # no ledger data at all renders a hint, not a crash
    empty = report_mod.analyze([])
    assert empty["compiles"]["source"] is None
    assert "no ledger data" in report_mod.format_report(empty)
    # a keep-truncated snapshot: the header trusts the LIFETIME rollup
    # fields, not a sum over the surviving record rows
    truncated = report_mod.analyze([], {"compile_ledger": {
        "compiles": 600, "retraces": 90, "compile_ms_total": 1234.5,
        "retrace_reasons": {"bucket_shape": 90},
        "records": [{"site": "slot_decode", "compile_ms": 1.0,
                     "retrace": True, "retrace_reasons": ["bucket_shape"]}],
    }})["compiles"]
    assert truncated["count"] == 600 and truncated["retraces"] == 90
    assert truncated["compile_ms_total"] == 1234.5


def test_report_padding_waste_from_snapshot_counters():
    snapshot = {"counters": {
        "serving_prompt_tokens_real_total": 75.0,
        "serving_prompt_tokens_padded_total": 100.0,
        "serving_decode_rows_total": 40.0,
        "serving_decode_rows_padded_total": 10.0,
    }}
    pad = report_mod.analyze([], snapshot)["padding"]
    assert pad["prompt_padding_efficiency"] == 0.75
    assert pad["decode_rows_padding_waste"] == 0.25
    assert report_mod.analyze([], {})["padding"] is None


def test_checked_in_fixtures_stay_reportable():
    """`make obs-report` contract: the committed fixture artifacts render
    every section (a stale fixture schema fails here, not in CI's make)."""
    text = report_mod.run(
        "tests/fixtures/events.jsonl",
        "tests/fixtures/metrics_snapshot.json",
    )
    for section in ("== per-phase latency breakdown ==", "== requests ==",
                    "== worst-request waterfall ==",
                    "== compile/memory ledger ==", "== padding waste =="):
        assert section in text
    assert "from snapshot" in text and "retrace reasons:" in text
    assert "slot_prefill[1x8]" in text


@pytest.mark.slow
def test_serve_cli_run_is_obs_reportable(tmp_path, capsys):
    """The full acceptance loop through the real CLI: a warmed-up `serve`
    run's serve_stats embeds the ledger table, its events.jsonl carries
    forwarded ``ledger.compile`` events, the final snapshot embeds the
    table, and `obs report` over the run's own artifacts renders the
    compile/memory section from the snapshot."""
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
        f"--obs.events_path={events_path}",
        f"--obs.snapshot_path={snap_path}",
    ])
    stats_lines = [
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith('{"serve_stats"')
    ]
    assert len(stats_lines) == 1
    embedded = stats_lines[0]["serve_stats"]["compile_ledger"]
    assert embedded["compiles"] >= 3 and embedded["records"]
    assert any(r["site"] == "slot_prefill" for r in embedded["records"])
    # the ledger's counter families live on the process-wide registry, not
    # the run-scoped one — serve_stats and the snapshot carry them too
    process = stats_lines[0]["serve_stats"]["process_metrics"]
    assert process["counters"]["compile_total"] == embedded["compiles"]
    assert "compile_ms" in process["histograms"]

    forwarded = [r for r in read_events_jsonl(events_path)
                 if r["span"] == "ledger.compile"]
    assert len(forwarded) == embedded["compiles"]
    snap = json.load(open(snap_path))
    assert snap["compile_ledger"]["records"]
    assert snap["process_metrics"]["counters"]["compile_total"] == embedded["compiles"]
    text = report_mod.run(events_path, snap_path)
    assert "== compile/memory ledger ==" in text and "from snapshot" in text
    assert "slot_prefill" in text
    reset_executor_caches()
    default_ledger().reset()


def test_serve_cli_failure_detaches_ledger_callback(tmp_path):
    """A serve run that dies during setup (bad checkpoint) must not leak
    its ledger->events forwarding callback: a leaked callback would stream
    every LATER run's compiles into the dead run's events file."""
    from perceiver_io_tpu.scripts.text import clm as clm_script

    ledger = default_ledger()
    before = len(ledger._on_record)
    with pytest.raises((SystemExit, OSError, ValueError)):
        clm_script.main([
            "serve", "--ckpt", str(tmp_path / "nonexistent"),
            f"--obs.events_path={tmp_path}/events.jsonl",
        ])
    assert len(ledger._on_record) == before


def test_cli_obs_report_subcommand(capsys):
    """The family CLI's `obs report` path: no checkpoint, no datamodule —
    artifacts in, report out (and --json emits the analysis object)."""
    from perceiver_io_tpu.scripts.text import clm as clm_script

    text = clm_script.main([
        "obs", "report", "--events=tests/fixtures/events.jsonl",
        "--snapshot=tests/fixtures/metrics_snapshot.json",
    ])
    assert "== compile/memory ledger ==" in text
    assert "== compile/memory ledger ==" in capsys.readouterr().out
    as_json = clm_script.main([
        "obs", "report", "--events=tests/fixtures/events.jsonl",
        "--json=true",
    ])
    # 4 ok + 1 cancelled (the gateway-era fixture extension)
    assert json.loads(as_json)["requests"]["terminal_spans"] == 5
    with pytest.raises(SystemExit, match="requires --events"):
        clm_script.main(["obs", "report"])
    with pytest.raises(SystemExit, match="usage: obs report"):
        clm_script.main(["obs", "nope"])
    # bad artifact paths are clean one-line errors, not tracebacks
    with pytest.raises(SystemExit, match="obs report:"):
        clm_script.main(["obs", "report", "--events=/nonexistent/e.jsonl"])
    with pytest.raises(SystemExit, match="not valid JSON"):
        clm_script.main([
            "obs", "report", "--events=tests/fixtures/events.jsonl",
            "--snapshot=tests/fixtures/events.jsonl",  # JSONL, not JSON
        ])
    with pytest.raises(SystemExit, match="obs report:"):
        report_mod.main(["/nonexistent/e.jsonl"])

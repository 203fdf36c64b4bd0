"""Decode-strategy + chunked-prefill tests (``inference/decode_strategy.py``,
``serving/slots.py``; docs/serving.md).

The load-bearing assertions:

- greedy output is **token-identical across every strategy setting** —
  cached, recompute, auto, env override — including generations that cross
  latent → boundary → window phases mid-run (both boundary implementations
  are exact by construction);
- the autotuner is deterministic under ``reliability.FakeClock`` (ties
  break to cached), memoizes per (shape, platform, env fingerprint), and
  round-trips through the JSON persistence artifact;
- the slot engine with chunked prefill is token-identical to per-request
  ``generate()`` on the three admission geometries the satellite names
  (admit during decode, chunk boundary == prompt end, chunk > prompt), its
  chunk-built row state matches the one-shot prefill (exactly for token and
  bookkeeping state, to float32 rounding for the projected caches — the two
  paths compile as different XLA programs), and the compile count after
  warmup is exactly ``len(prompt_buckets) + 3``.

All pure-CPU, tiny shapes, tier-1, with a per-test time budget.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference import decode_strategy as strategy_mod
from perceiver_io_tpu.inference.decode_strategy import (
    DecodeStrategy,
    autotune_boundary,
    load_registry,
    resolve_decode_strategy,
    save_registry,
)
from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    executor_cache_stats,
    generate,
    reset_executor_caches,
)
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.reliability import FakeClock
from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

pytestmark = [pytest.mark.decode_strategy, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use: executor caches and the
# strategy registry are keyed by shape, and sharing one would couple counts
# across files.
TINY = dict(
    vocab_size=73, max_seq_len=28, max_latents=6, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 28), jnp.int32), 22)["params"]
    return model, params


@pytest.fixture(autouse=True)
def _fresh_strategy_registry():
    strategy_mod.reset_registry()
    yield
    strategy_mod.reset_registry()


def _ref(model, params, prompt, cfg, **kw):
    return np.asarray(generate(model, params, jnp.asarray(prompt[None, :]), cfg, **kw))[0]


# -- strategy resolution ----------------------------------------------------
def test_resolution_order_and_validation(tiny_model, monkeypatch):
    model, _ = tiny_model
    monkeypatch.delenv(strategy_mod.ENV_VAR, raising=False)
    # untuned auto == the cached status quo
    assert resolve_decode_strategy(None, model) == DecodeStrategy()
    assert resolve_decode_strategy("recompute", model).boundary == "recompute"
    # env var beats the default, an explicit argument beats the env var
    monkeypatch.setenv(strategy_mod.ENV_VAR, "recompute")
    assert resolve_decode_strategy(None, model).boundary == "recompute"
    assert resolve_decode_strategy("cached", model).boundary == "cached"
    # a measured verdict flips auto
    monkeypatch.delenv(strategy_mod.ENV_VAR, raising=False)
    strategy_mod.record(model, "recompute")
    assert resolve_decode_strategy(None, model).boundary == "recompute"
    with pytest.raises(ValueError, match="decode strategy"):
        resolve_decode_strategy("sometimes", model)
    with pytest.raises(ValueError, match="pinned to 'recompute'"):
        DecodeStrategy(window="cached")
    # latent recompute forces the boundary to recompute (stale-cache guard)
    assert not DecodeStrategy(latent="recompute").boundary_cached


def test_greedy_token_identity_across_strategies_and_phases(tiny_model, monkeypatch):
    """Prompt 12 / max_new 16 on a 28-ctx, 6-latent model crosses all three
    phases (4 latent-growth + 12 boundary + 0..., then window): every
    strategy setting must emit identical greedy tokens."""
    model, params = tiny_model
    monkeypatch.delenv(strategy_mod.ENV_VAR, raising=False)
    cfg = GenerationConfig(max_new_tokens=20, num_latents=2, sampling=GREEDY)
    prompt = np.random.default_rng(0).integers(1, 73, size=12).astype(np.int32)
    # 20 new tokens: s1 = 4 (latent), boundary to window-full (16), then the
    # sliding-window phase — the full phase crossing
    ref = _ref(model, params, prompt, cfg, use_cache=False)
    for mode in ("cached", "recompute", "auto", None,
                 DecodeStrategy(latent="recompute", boundary="recompute")):
        np.testing.assert_array_equal(
            _ref(model, params, prompt, cfg, decode_strategy=mode), ref
        )
    # env override path is exact too
    monkeypatch.setenv(strategy_mod.ENV_VAR, "recompute")
    np.testing.assert_array_equal(_ref(model, params, prompt, cfg), ref)


# -- autotuner --------------------------------------------------------------
def test_autotuner_deterministic_under_fake_clock(tiny_model):
    """Under FakeClock both measurements read 0 ms — the tie must break to
    cached, identically on every run, and the verdict memoizes (the second
    call returns without touching the clock)."""
    model, params = tiny_model
    for _ in range(2):
        strategy_mod.reset_registry()
        clock = FakeClock()
        assert autotune_boundary(model, params, clock=clock) == "cached"
    calls = []

    def counting_clock():
        calls.append(1)
        return 0.0

    assert autotune_boundary(model, params, clock=counting_clock) == "cached"
    assert not calls  # memoized: no re-measurement


def test_autotuner_picks_recompute_on_scripted_clock(tiny_model):
    """A deterministic clock that charges the cached pass more than the
    recompute pass must flip the verdict — replayably."""
    model, params = tiny_model

    class ScriptClock(FakeClock):
        # t0/t1 per mode, cached measured first: gaps of 10s then 1s
        script = [0.0, 10.0, 10.0, 11.0]

        def __init__(self):
            super().__init__()
            self._i = 0

        def __call__(self):
            t = self.script[self._i % len(self.script)]
            self._i += 1
            return t

    for _ in range(2):
        strategy_mod.reset_registry()
        winner = autotune_boundary(model, params, clock=ScriptClock())
        assert winner == "recompute"
        entry = strategy_mod._REGISTRY[strategy_mod.registry_key(model)]
        assert entry["cached_ms_per_token"] > entry["recompute_ms_per_token"]
    # and generate's auto mode now follows the measured verdict
    assert resolve_decode_strategy("auto", model).boundary == "recompute"


def test_registry_persistence_roundtrip(tiny_model, tmp_path):
    model, params = tiny_model
    path = str(tmp_path / "strategy.json")
    winner = autotune_boundary(model, params, clock=FakeClock(), persist=path)
    assert winner == "cached"
    data = json.loads((tmp_path / "strategy.json").read_text())
    assert data["version"] == 1 and len(data["entries"]) == 1
    assert data["entries"][0]["boundary"] == "cached"
    strategy_mod.reset_registry()
    assert strategy_mod.lookup(model) is None
    assert load_registry(path) == 1
    assert strategy_mod.lookup(model) == "cached"
    # a persisted verdict short-circuits re-measurement in a fresh process
    strategy_mod.reset_registry()
    calls = []

    def counting_clock():
        calls.append(1)
        return 0.0

    assert autotune_boundary(model, params, clock=counting_clock, persist=path) == "cached"
    assert not calls
    # corrupt files degrade to zero entries, not a crash — including
    # structurally-wrong valid JSON (list top level, non-dict entries,
    # malformed keys): serve startup must fall back to re-measurement
    strategy_mod.reset_registry()
    for i, bad in enumerate(
        ["{nope", "[]", '{"entries": [42]}', '{"entries": 7}',
         '{"entries": [{"key": 3, "boundary": "cached"}]}']
    ):
        (tmp_path / f"bad{i}.json").write_text(bad)
        assert load_registry(str(tmp_path / f"bad{i}.json")) == 0


def test_env_file_feeds_auto_resolution(tiny_model, tmp_path, monkeypatch):
    model, params = tiny_model
    path = str(tmp_path / "deploy.json")
    strategy_mod.record(model, "recompute")
    save_registry(path)
    strategy_mod.reset_registry()
    monkeypatch.setenv(strategy_mod.ENV_FILE, path)
    assert resolve_decode_strategy("auto", model).boundary == "recompute"


# -- slot engine: strategy --------------------------------------------------
def test_slot_engine_recompute_boundary_parity(tiny_model):
    """The recompute boundary decode variant must stay token-identical to
    per-request generate() across boundary-crossing mid-flight admits."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8, 16), batch_sizes=(1,)),
        slots=2, decode_strategy="recompute",
    )
    assert engine.stats()["decode_strategy_boundary"] == "recompute"
    prompts = [
        np.random.default_rng(1).integers(1, 73, size=int(n)).astype(np.int32)
        for n in [3, 11, 3]
    ]
    outs = engine.serve(prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))


# -- slot engine: chunked prefill ------------------------------------------
def test_chunked_prefill_parity_three_geometries(tiny_model):
    """The satellite's three admission geometries, all token-identical to
    per-request generate(): (a) a long admit during resident decode, (b) a
    prefix that is an exact multiple of the chunk (chunk boundary == prompt
    end), (c) a prompt smaller than one chunk (sync fast path)."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8, 16), batch_sizes=(1,)),
        slots=2, prefill_chunk=4,
    )
    rng = np.random.default_rng(2)
    # lengths: 3 (< chunk: sync), 10 (prefix 8 = 2 exact chunks), 14 and 13
    # (admitted mid-decode into recycled slots)
    prompts = [rng.integers(1, 73, size=int(n)).astype(np.int32)
               for n in [3, 10, 14, 13]]
    outs = engine.serve(prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))
    stats = engine.stats()
    assert stats["completed"] == 4 and stats["prefills"] == 4
    # the three >1-chunk admissions went through the chunk executor
    assert stats["prefill_chunks"] >= 3 * 2
    assert stats["prefill_chunk_ms"]["p95"] is not None
    hist = engine.registry.histogram("serving_prefill_chunks")
    assert hist is not None and hist.count == 3


def test_chunked_admission_interleaves_with_resident_decode(tiny_model):
    """While a long admission is chunking, the resident slot must keep
    emitting one token per step — the stall the tentpole removes — and the
    trace must carry one serving.prefill_chunk event per chunk call."""
    from perceiver_io_tpu.observability import Tracer

    model, params = tiny_model
    tracer = Tracer()
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8, 16), batch_sizes=(1,)),
        slots=2, prefill_chunk=4, tracer=tracer,
    )
    rng = np.random.default_rng(3)
    resident = engine.submit(rng.integers(1, 73, size=5).astype(np.int32))
    engine.step()  # admit resident (sync), decode token 1
    engine.step()  # token 2
    emitted_before = len(engine._slots[0].emitted)
    long_req = engine.submit(rng.integers(1, 73, size=14).astype(np.int32))
    engine.step()  # first chunk + resident token
    assert engine.health()["admitting"] is True
    assert len(engine._slots[0].emitted) == emitted_before + 1
    engine.step()  # second chunk + resident token
    assert len(engine._slots[0].emitted) == emitted_before + 2
    engine.run_until_idle()
    assert resident.status == "ok" and long_req.status == "ok"
    np.testing.assert_array_equal(
        long_req.result, _ref(model, params, long_req.prompt, cfg)
    )
    chunks = tracer.spans("serving.prefill_chunk")
    # prefix 12 over chunk 4: three staging chunks + one pure finalize call
    assert len(chunks) == 4
    assert [c.attrs["final"] for c in chunks] == [False, False, False, True]
    assert all(c.trace_id == long_req.trace_id for c in chunks)


def test_chunked_row_state_matches_sync_prefill(tiny_model):
    """After admission plus one decode step, the chunk-built slot row must
    equal the one-shot prefill's: exactly for every token/bookkeeping array,
    and to float32 rounding for the projected caches and logits. The chunk
    executor and the full-window prefill are the same per-position math but
    compile as different XLA programs, so their matmul reduction orders —
    and hence the last couple of mantissa bits — may differ."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(16,), batch_sizes=(1,))
    prompt = np.random.default_rng(4).integers(1, 73, size=13).astype(np.int32)
    chunked = SlotServingEngine(model, params, cfg, table, slots=1, prefill_chunk=4)
    sync = SlotServingEngine(model, params, cfg, table, slots=1)
    chunked.submit(prompt)
    sync.submit(prompt)
    sync.step()  # sync: admit + first decode step
    while chunked._slots[0] is None:
        chunked.step()  # chunks ... finalize (+ first decode step)
    a, b = chunked._state, sync._state
    for key in ("window", "pad", "length", "m", "steps"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))
    np.testing.assert_allclose(
        np.asarray(a["logits"]), np.asarray(b["logits"]), rtol=1e-5, atol=1e-6
    )
    valid = int(np.asarray(a["length"])[0])
    for key in ("cross_k", "cross_v"):
        np.testing.assert_allclose(
            np.asarray(a[key])[:, :, :valid], np.asarray(b[key])[:, :, :valid],
            rtol=1e-5, atol=1e-6,
        )
    for key in ("stack_k", "stack_v"):
        for la, lb in zip(a[key], b[key]):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-6
            )


def test_chunked_compile_bound_and_zero_retrace(tiny_model):
    """warmup() with chunked prefill compiles exactly len(prompt_buckets)
    + 3 executors (prefills + decode + boundary + ONE chunk executor), and
    mixed chunked/sync traffic afterwards retraces nothing — the ISSUE 5
    acceptance bound."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    reset_executor_caches()
    engine = SlotServingEngine(model, params, cfg, table, slots=2, prefill_chunk=4)
    compiled = engine.warmup()
    assert compiled == len(table.prompt_lens) + 3
    before = executor_cache_stats()["misses"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 73, size=int(n)).astype(np.int32)
               for n in [3, 5, 9, 10, 13, 14, 16, 8]]
    for i, p in enumerate(prompts):
        engine.submit(p, config=dataclasses.replace(cfg, max_new_tokens=2 + (i % 3)))
    engine.run_until_idle()
    assert executor_cache_stats()["misses"] == before
    assert engine.stats()["completed"] == len(prompts)


def test_chunked_admission_deadline_and_drain(tiny_model):
    """A deadline expiring mid-admission ends the request timed_out without
    touching residents; drain still empties everything."""
    model, params = tiny_model
    clock = FakeClock()
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8, 16), batch_sizes=(1,)),
        slots=2, prefill_chunk=4, clock=clock,
    )
    rng = np.random.default_rng(6)
    resident = engine.submit(rng.integers(1, 73, size=4).astype(np.int32))
    engine.step()
    doomed = engine.submit(
        rng.integers(1, 73, size=14).astype(np.int32), deadline_s=5.0
    )
    engine.step()  # first chunk of the doomed admission
    assert engine.health()["admitting"]
    clock.advance(10.0)
    engine.run_until_idle()
    assert doomed.status == "timed_out"
    assert "prefill chunks" in doomed.error
    assert resident.status == "ok"
    np.testing.assert_array_equal(
        resident.result, _ref(model, params, resident.prompt, cfg)
    )
    assert not engine.pending() and engine.health()["admitting"] is False


# -- generate-side plan accounting -----------------------------------------
def test_recompute_strategy_drops_boundary_segment(tiny_model):
    """decode_strategy='recompute' must compile a different phase plan
    (s2 == s1) — observable as a fresh executor-cache entry — while 'auto'
    without a verdict reuses the cached plan's executor."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)
    prompt = jnp.asarray(
        np.random.default_rng(7).integers(1, 73, size=(1, 12), dtype=np.int32)
    )
    reset_executor_caches()
    generate(model, params, prompt, cfg, decode_strategy="cached")
    baseline = executor_cache_stats()["misses"]
    generate(model, params, prompt, cfg, decode_strategy="auto")
    assert executor_cache_stats()["misses"] == baseline  # same plan, cache hit
    generate(model, params, prompt, cfg, decode_strategy="recompute")
    assert executor_cache_stats()["misses"] == baseline + 1  # new plan


def test_slot_engine_pins_boundary_mode_until_warmup(tiny_model, monkeypatch):
    """A mid-serving registry change (late autotune, a strategy file
    appearing) must NOT swap the boundary executor under resident rows —
    under recompute their cross caches are deliberately stale, so a flip to
    cached would read garbage. The verdict is pinned at first use and only
    re-resolved by warmup(), which refuses to run with residents."""
    model, params = tiny_model
    monkeypatch.delenv(strategy_mod.ENV_VAR, raising=False)
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
        slots=1,
    )
    assert engine.stats()["decode_strategy_boundary"] == "cached"  # pins here
    strategy_mod.record(model, "recompute")
    assert engine.stats()["decode_strategy_boundary"] == "cached"  # still pinned
    engine.warmup()  # no residents: re-resolves against the fresh verdict
    assert engine.stats()["decode_strategy_boundary"] == "recompute"
    # and the re-resolved engine still matches per-request generate()
    prompt = np.random.default_rng(11).integers(1, 73, size=7).astype(np.int32)
    np.testing.assert_array_equal(
        engine.serve([prompt])[0], _ref(model, params, prompt, cfg)
    )


def test_serve_cli_decode_mode_env_deference(monkeypatch):
    """The serve flag's 'auto' default defers to PERCEIVER_DECODE_STRATEGY
    (the documented process-wide override); a pinned flag beats the env;
    bad values from either source reject at the CLI boundary."""
    from perceiver_io_tpu.scripts.cli import _serve_decode_mode

    monkeypatch.delenv(strategy_mod.ENV_VAR, raising=False)
    assert _serve_decode_mode("auto") == "auto"
    assert _serve_decode_mode("cached") == "cached"
    monkeypatch.setenv(strategy_mod.ENV_VAR, "recompute")
    assert _serve_decode_mode("auto") == "recompute"
    assert _serve_decode_mode("cached") == "cached"  # explicit flag wins
    with pytest.raises(SystemExit, match="decode_strategy"):
        _serve_decode_mode("sometimes")
    monkeypatch.setenv(strategy_mod.ENV_VAR, "sometimes")
    with pytest.raises(SystemExit, match=strategy_mod.ENV_VAR):
        _serve_decode_mode("auto")

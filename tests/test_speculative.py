"""Speculative-decoding tests (docs/serving.md "Speculative decoding",
``inference/speculative.py``, ``serving/slots.py``).

The load-bearing assertions:

- greedy output is **token-identical** to the non-speculative path — the
  standalone ``speculative_generate`` vs ``generate``, and the slot
  engine with ``speculation`` on vs off across every serving geometry:
  mid-flight admits into recycled slots, latent-boundary crossings,
  chunked prefill, dense/paged/int8/prefix-shared KV, and the 2x2
  data x model mesh;
- the compile bound grows by EXACTLY two executors (the draft + verify
  pair) and mixed traffic after warmup retraces nothing;
- an accepted burst emits one ``on_token`` callback, one ITL sample, and
  one timeline event PER TOKEN in index order — ttft + sum(itl)
  telescopes exactly under FakeClock (``unattributed_ms == 0.0``);
- accepted bursts crossing paged block boundaries map every page they
  need up front (``ensure_many``) and the pool is zero-leak even under a
  scripted ``kv.exhaust`` storm with preemption on;
- the autotuner picks a draft geometry where drafting pays and declines
  (``"off"``) where it structurally cannot, and verdicts round-trip
  through the registry artifact.

All pure-CPU, tiny shapes, fast — tier-1.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference import decode_strategy as strategy_mod
from perceiver_io_tpu.inference import speculative as speculative_mod
from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    executor_cache_stats,
    generate,
    reset_executor_caches,
)
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.inference.speculative import SpecConfig, speculative_generate
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.reliability import ChaosRegistry, FakeClock
from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

pytestmark = [pytest.mark.speculative, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use: executor cache keys
# include the module fingerprint, and an identically-configured model in
# another file would pre-populate the cache this file counts.
TINY = dict(
    vocab_size=101, max_seq_len=32, max_latents=8, num_channels=16,
    num_heads=2, num_self_attention_layers=2, cross_attention_dropout=0.0,
)

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    return model, params


def _ragged_prompts(rng, lengths, vocab=101):
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lengths]


def _ref(model, params, prompt, cfg):
    """Unbucketed per-request generate(): the parity oracle."""
    return np.asarray(generate(model, params, jnp.asarray(prompt[None, :]), cfg))[0]


# -- standalone exactness --------------------------------------------------
@pytest.mark.parametrize("k,d", [
    # 2026-08 runtime audit: ~16-19s per geometry (draft+verify compiles);
    # the whole grid is `slow` depth — tier-1 parity coverage lives in
    # test_speculative_generate_batch_parity plus the engine/mesh
    # token-identity drills below, which re-prove the same oracle
    pytest.param(2, 1, marks=pytest.mark.slow),
    pytest.param(4, 1, marks=pytest.mark.slow),
    pytest.param(2, 2, marks=pytest.mark.slow),
    pytest.param(4, 2, marks=pytest.mark.slow),
], ids=lambda v: str(v))
def test_speculative_generate_parity(tiny_model, k, d):
    """speculative_generate == generate token-for-token across draft
    geometries (k x d) and prompt lengths straddling the latent boundary."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=10, num_latents=2, sampling=GREEDY)
    prompts = _ragged_prompts(np.random.default_rng(0), [3, 11, 8])
    spec = SpecConfig(k, d)
    for p in prompts:
        ref = _ref(model, params, p, cfg)
        got = np.asarray(
            speculative_generate(
                model, params, jnp.asarray(p[None, :]), cfg, spec
            )
        )[0]
        np.testing.assert_array_equal(ref, got, err_msg=f"k{k}d{d}")


def test_speculative_generate_batch_parity(tiny_model):
    """Batched rows accept DIFFERENT prefix lengths per round; outputs
    still match the per-row oracle exactly."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=10, num_latents=2, sampling=GREEDY)
    rng = np.random.default_rng(1)
    batch = np.stack(_ragged_prompts(rng, [7, 7]))
    ref = np.asarray(generate(model, params, jnp.asarray(batch), cfg))
    got = np.asarray(
        speculative_generate(model, params, jnp.asarray(batch), cfg, SpecConfig(4, 1))
    )
    np.testing.assert_array_equal(ref, got)


# -- slot-engine token identity across geometries --------------------------
def _serve(tiny_model, cfg, prompts, **kw):
    model, params = tiny_model
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8, 16), batch_sizes=(1,)),
        slots=2, **kw,
    )
    return engine, [np.asarray(o) for o in engine.serve(prompts)]


@pytest.mark.parametrize("geometry", [
    {},
    {"kv_layout": "paged", "kv_block_size": 4},
    {"kv_layout": "paged_int8", "kv_block_size": 4},
    {"kv_layout": "paged", "kv_block_size": 4, "prefix_cache": "on"},
    {"prefill_chunk": 4},
])
def test_slot_engine_token_identity(tiny_model, geometry):
    """5 ragged requests through 2 slots with speculation on — mid-flight
    admits into recycled slots, boundary crossings at different steps, and
    (paged) accepted bursts crossing block boundaries — all emit exactly
    the non-speculative engine's greedy tokens, in every KV geometry."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=10, num_latents=2, sampling=GREEDY)
    prompts = _ragged_prompts(np.random.default_rng(0), [3, 11, 8, 3, 11])
    engine, outs = _serve(tiny_model, cfg, prompts, speculation="k4d1", **geometry)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))
    st = engine.stats()["speculation"]
    assert st["rounds"] > 0
    assert 0.0 <= st["acceptance_rate"] <= 1.0
    assert st["emitted"] == sum(len(o) for o in outs)


def test_mesh_2x2_token_identity(tiny_model):
    """Speculation composes with the sharded runtime: the draft's candidate
    block shards along data like the window, verify reuses the decode-state
    shardings, and a 2x2 data x model mesh over the 8 virtual CPU devices
    emits the oracle's exact tokens."""
    from perceiver_io_tpu.serving.sharding import ServingMeshSpec

    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=10, num_latents=2, sampling=GREEDY)
    prompts = _ragged_prompts(np.random.default_rng(0), [3, 11, 8, 3, 11])
    _, outs = _serve(
        tiny_model, cfg, prompts, speculation="k4d1",
        mesh=ServingMeshSpec(data=2, model=2),
    )
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))


# -- burst latency accounting ----------------------------------------------
def test_burst_emits_per_token_callbacks_and_itl_samples(tiny_model):
    """A round accepting n_e tokens delivers n_e ``on_token`` callbacks in
    index order, one ITL sample per non-first token, and telescopes exactly
    under FakeClock: analyze_timeline attributes every request millisecond
    (``unattributed_ms == 0.0``) and ttft.count + itl.count equals the
    total emitted tokens."""
    from perceiver_io_tpu.observability import MetricsRegistry, StepTimeline
    from perceiver_io_tpu.observability.report import analyze_timeline
    from perceiver_io_tpu.observability.tracing import (
        JsonlSpanSink,
        Tracer,
        read_events_jsonl,
    )

    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    prompts = _ragged_prompts(np.random.default_rng(3), [5, 7, 6, 4])
    clock = FakeClock()
    reg = MetricsRegistry()
    ev_path = os.path.join(
        os.environ.get("PYTEST_TMPDIR", "/tmp"), "spec_events.jsonl"
    )
    sink = JsonlSpanSink(ev_path)
    tracer = Tracer(clock=clock, sink=sink)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
        slots=2, clock=clock, registry=reg, tracer=tracer, speculation="k4d1",
    )
    engine.timeline = StepTimeline(cap=256, registry=reg)
    streams = {}
    handles = []
    for i, p in enumerate(prompts):
        streams[i] = []
        handles.append(
            engine.submit(
                p,
                on_token=lambda idx, tok, i=i: streams[i].append((idx, tok)),
            )
        )
    while engine.pending():
        engine.step()
        clock.advance(0.01)
    sink.close()
    assert all(h.status == "ok" for h in handles)
    for i, h in enumerate(handles):
        # exactly one callback per emitted token, indices contiguous from 0
        assert [idx for idx, _ in streams[i]] == list(range(len(h.result)))
        assert [tok for _, tok in streams[i]] == [int(t) for t in h.result]
    an = analyze_timeline(
        engine.timeline.records(), read_events_jsonl(ev_path),
        snapshot=reg.snapshot(),
    )
    for row in an["requests"]:
        assert row["unattributed_ms"] == 0.0, row
    ttft = reg.histogram("serving_ttft_ms")
    itl = reg.histogram("serving_inter_token_ms")
    emitted = sum(len(h.result) for h in handles)
    assert ttft.count == len(prompts)
    assert ttft.count + itl.count == emitted
    assert reg.counters()["spec_tokens_emitted_total"] == emitted


# -- paged pool integrity under pressure -----------------------------------
@pytest.mark.slow  # 2026-08 audit: ~15s; the preemption and swap exhaust
# storms keep zero-leak-under-kv.exhaust in tier-1 — this re-proves it with
# speculation in the mix, which stays `slow` depth
def test_zero_leak_under_kv_exhaust_storm(tiny_model):
    """A scripted kv.exhaust storm against a speculative paged engine with
    preemption on: accepted bursts map multiple pages per round via
    ensure_many, forced exhaustions preempt victims mid-burst, and every
    request still completes token-identically with a zero-leak pool."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    prompts = _ragged_prompts(np.random.default_rng(3), [5, 7, 6, 4, 6, 5])
    chaos = ChaosRegistry()
    # fire while >= 2 residents are live: speculation compresses the
    # schedule (~2 rounds per request at k=4), and a forced exhaustion
    # against a sole resident is the engine's designed "stuck" raise
    chaos.exhaust_kv(1, count=3)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
        slots=4, kv_layout="paged", kv_block_size=4, kv_blocks=24,
        preemption="recompute", clock=FakeClock(), chaos=chaos,
        speculation="k4d1",
    )
    handles = [engine.submit(p) for p in prompts]
    engine.run_until_idle()
    for h, p in zip(handles, prompts):
        assert h.status == "ok", h.status
        np.testing.assert_array_equal(h.result, _ref(model, params, p, cfg))
    assert chaos.fired_count("kv.exhaust") == 3
    assert engine.stats()["preemption"]["preemptions"] > 0
    pool = engine._pool
    assert pool.in_use == 0 and pool.leaked() == 0
    assert pool.allocs_total == pool.frees_total


# -- autotune + registry ---------------------------------------------------
class _ScriptClock:
    """Sampled twice per arm ("off" first): charges off 10s, the draft 1s —
    the decode-strategy suite's scripted-clock discipline, so the decision
    logic pins replayably while the engines (and the acceptance gate they
    feed) run for real. The real-clock "drafting pays" direction is not
    measured (no serving cell yet: PERF.md section 7)."""
    script = [0.0, 10.0, 10.0, 11.0]

    def __init__(self):
        self._i = 0

    def __call__(self):
        t = self.script[self._i % len(self.script)]
        self._i += 1
        return t


def test_autotune_pays_declines_and_roundtrips(tiny_model, tmp_path):
    """Both verdict directions, pinned: a strict-truncation draft whose
    measured acceptance clears the floor wins when its timed pass is
    faster (scripted clock — deterministic under CI noise); a draft as
    deep as the model is skipped so the verdict stays off. Verdicts
    survive a save/load round-trip."""
    model, params = tiny_model
    clean = strategy_mod.registry_key(model) not in getattr(
        strategy_mod, "_SPEC_REGISTRY"
    )
    verdict = strategy_mod.autotune_speculation(
        model, params, candidates=("k4d1",), clock=_ScriptClock(),
        force=True,
    )
    entry = strategy_mod.spec_entry(model)
    assert verdict == "k4d1", entry
    # the acceptance gate input is REAL: the probe engines decoded the
    # shared workload and this is their measured draft-acceptance rate
    assert entry["acceptance"]["k4d1"] >= entry["accept_floor"]
    assert (
        entry["timings_ms_per_token"]["k4d1"]
        < entry["timings_ms_per_token"]["off"]
    )
    path = str(tmp_path / "strategy.json")
    strategy_mod.save_registry(path)
    assert "spec_entries" in json.load(open(path))
    # the structural decline: d == num_self_attention_layers is the full
    # model, so the candidate is skipped and "off" wins unopposed
    decline = strategy_mod.autotune_speculation(
        model, params, candidates=("k4d2",), force=True
    )
    assert decline == "off"
    assert strategy_mod.spec_entry(model)["skipped"] == ["k4d2"]
    strategy_mod.load_registry(path)
    assert strategy_mod.lookup_speculation(model) == "k4d1"
    if clean:
        # leave the process-global registry as this test found it
        strategy_mod._SPEC_REGISTRY.pop(strategy_mod.registry_key(model), None)


def test_resolution_env_and_registry(tiny_model, monkeypatch):
    """auto defers to PERCEIVER_SPECULATION, then the measured registry,
    then off; an explicit mode beats the env var."""
    model, params = tiny_model
    monkeypatch.delenv(strategy_mod.ENV_SPECULATION, raising=False)
    assert strategy_mod.resolve_speculation(None, model) == "off"
    monkeypatch.setenv(strategy_mod.ENV_SPECULATION, "k2d1")
    assert strategy_mod.resolve_speculation(None, model) == "k2d1"
    assert strategy_mod.resolve_speculation("off", model) == "off"
    engine = SlotServingEngine(
        model, params,
        GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY),
        BucketTable(prompt_lens=(8,), batch_sizes=(1,)), slots=2,
    )
    assert engine.speculation == "k2d1"
    assert engine.health()["speculation"] == "k2d1"


def test_loud_rejects(tiny_model):
    """Invalid speculation configs fail at construction, not mid-serve:
    sampling (greedy-only), an unknown mode, and a draft deeper than the
    latent stack."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8,), batch_sizes=(1,))
    sampled = dataclasses.replace(
        cfg, sampling=SamplingConfig(temperature=1.0, do_sample=True)
    )
    with pytest.raises(ValueError, match="greedy-only"):
        SlotServingEngine(
            model, params, sampled, table, slots=2, speculation="k4d1"
        )
    with pytest.raises(ValueError, match="speculation must be one of"):
        SlotServingEngine(
            model, params, cfg, table, slots=2, speculation="bogus"
        )
    shallow = CausalLanguageModel(
        CausalLanguageModelConfig(**{**TINY, "num_self_attention_layers": 1})
    )
    with pytest.raises(ValueError, match="draft_layers"):
        speculative_mod.validate_spec(SpecConfig(4, 2), shallow, cfg)


# -- compile bound ---------------------------------------------------------
# Runs LAST: reset_executor_caches() wipes every warm executor this module
# built, so an earlier position would force the later drills to recompile.
def test_compile_bound_plus_two_and_zero_retrace(tiny_model):
    """Speculation adds EXACTLY two executors (draft + verify) to the
    engine's warmup compile bound, and post-warmup speculative traffic
    retraces nothing."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=10, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    reset_executor_caches()
    base = SlotServingEngine(model, params, cfg, table, slots=2)
    base.warmup()
    # same shape, speculation on: warmup reuses every non-spec executor
    # from the cache and compiles EXACTLY the draft + verify pair
    miss0 = executor_cache_stats()["misses"]
    spec = SlotServingEngine(
        model, params, cfg, table, slots=2, speculation="k4d1"
    )
    spec.warmup()
    assert executor_cache_stats()["misses"] == miss0 + 2
    before = executor_cache_stats()["misses"]
    spec.serve(_ragged_prompts(np.random.default_rng(0), [3, 11, 8, 3, 11]))
    assert executor_cache_stats()["misses"] == before, "retraced after warmup"

"""Serving-layer tests: bucket grid arithmetic, the micro-batching
scheduler, executor-cache observability, ahead-of-time warmup, and the
pipeline/CLI surfaces (docs/serving.md).

The load-bearing assertions: a mixed-length workload (>= 8 distinct prompt
lengths, ragged batch sizes) compiles at most ``len(bucket_table)``
executors — not one per distinct shape — and greedy output is
token-identical to the unbucketed per-request path. All pure-CPU, tiny
shapes: this is the fast serving-scheduler smoke the CI tier runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    cached_executor,
    executor_cache_stats,
    generate,
    reset_executor_caches,
)
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.serving import BucketTable, ServingEngine

# Per-test deadline guard (tests/conftest.py): a scheduler regression that
# wedges the queue loop fails THAT test instead of eating the suite budget.
pytestmark = pytest.mark.timeout(300)

KEY = jax.random.PRNGKey(0)

# Deliberately NOT the shape other test modules use (vocab 67): executor
# cache keys include the module fingerprint, and an identically-configured
# model in another file would pre-populate the cache this file counts.
TINY = dict(
    vocab_size=67, max_seq_len=32, max_latents=16, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    return model, params


def _ragged_prompts(rng, lengths, vocab=67):
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lengths]


# -- bucket table ----------------------------------------------------------
def test_bucket_rounding_and_grid():
    table = BucketTable(prompt_lens=(8, 16, 32), batch_sizes=(1, 2, 4))
    assert table.prompt_bucket(1) == 8
    assert table.prompt_bucket(8) == 8
    assert table.prompt_bucket(9) == 16
    assert table.prompt_bucket(32) == 32
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        table.prompt_bucket(33)
    assert table.batch_bucket(1) == 1
    assert table.batch_bucket(3) == 4
    assert table.batch_bucket(99) == 4  # oversized groups chunk across batches
    assert len(table) == 9
    assert set(table.grid()) == {(b, L) for b in (1, 2, 4) for L in (8, 16, 32)}


def test_bucket_table_validation():
    with pytest.raises(ValueError, match="strictly increasing"):
        BucketTable(prompt_lens=(16, 8), batch_sizes=(1,))
    with pytest.raises(ValueError, match="strictly increasing"):
        BucketTable(prompt_lens=(8,), batch_sizes=())
    with pytest.raises(ValueError, match="strictly increasing"):
        BucketTable(prompt_lens=(0, 8), batch_sizes=(1,))


def test_bucket_table_for_model(tiny_model):
    model, _ = tiny_model
    table = BucketTable.for_model(model, max_batch_size=8)
    assert table.prompt_lens[-1] == model.max_seq_len
    assert table.batch_sizes == (1, 2, 4, 8)


# -- executor cache observability -----------------------------------------
def test_cached_executor_fifo_eviction_counts():
    cache: dict = {}
    before = executor_cache_stats()
    for key in ("a", "b", "c"):
        cached_executor(cache, key, lambda k=key: f"built-{k}", max_entries=2)
    assert "a" not in cache and set(cache) == {"b", "c"}  # FIFO: oldest out
    assert cached_executor(cache, "b", lambda: "rebuilt", max_entries=2) == "built-b"
    delta = {k: executor_cache_stats()[k] - before[k] for k in before}
    # legacy short keys and canonical registry names move in lockstep
    assert delta["hits"] == delta["executor_cache_hits_total"] == 1
    assert delta["misses"] == delta["executor_cache_misses_total"] == 3
    assert delta["evictions"] == delta["executor_cache_evictions_total"] == 1


# -- scheduler: the mixed-length acceptance workload ----------------------
def test_mixed_length_workload_bounded_compiles_and_greedy_parity(tiny_model):
    """>= 8 distinct prompt lengths / ragged batch sizes through the
    bucketed engine: executor compiles == distinct buckets hit (3, not 10),
    bounded by len(table); greedy output token-identical to the unbucketed
    path (one ragged batch, left-padded to its own max width)."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=5, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(2, 4))
    reset_executor_caches()  # before the engine snapshots its counters
    engine = ServingEngine(model, params, cfg, table)

    lengths = [3, 4, 5, 6, 7, 8, 9, 10, 11, 12]  # 10 distinct lengths
    prompts = _ragged_prompts(np.random.default_rng(0), lengths)

    outs = engine.serve(prompts)
    stats = engine.stats()

    # FIFO packing: (4 reqs -> bucket (4, 8)), (4 -> (4, 16)), (2 -> (2, 16))
    assert stats["batches"] == 3
    assert executor_cache_stats()["misses"] == 3  # == buckets hit, not 10
    assert stats["compiles"] <= len(table)
    assert stats["requests"] == len(prompts) and stats["queued"] == 0

    # Token-identical to the unbucketed path: one ragged batch left-padded
    # to its own max width (what TextGenerationPipeline does today).
    width = max(lengths)
    ids = np.zeros((len(prompts), width), np.int32)
    pad_count = np.zeros((len(prompts),), np.int32)
    for i, p in enumerate(prompts):
        ids[i, width - p.size:] = p
        pad_count[i] = width - p.size
    ref = np.asarray(generate(
        model, params, jnp.asarray(ids), cfg,
        prompt_pad_count=jnp.asarray(pad_count),
    ))
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, ref[i])


def test_distinct_lengths_single_bucket_single_build(tiny_model):
    """N distinct prompt lengths inside ONE bucket => exactly one executor
    build — the unbounded-retracing failure mode, fixed."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=3, num_latents=2, sampling=GREEDY)
    engine = ServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8,), batch_sizes=(1,))
    )
    prompts = _ragged_prompts(np.random.default_rng(1), [2, 3, 4, 5, 6, 7, 8])
    before = executor_cache_stats()["misses"]
    for p in prompts:  # one request per serve call: 7 micro-batches
        engine.serve([p])
    assert executor_cache_stats()["misses"] - before == 1
    assert engine.stats()["batches"] == len(prompts)


def test_second_engine_over_the_same_traffic_compiles_nothing(tiny_model):
    """The executor cache is the process's, not the engine's: a first engine
    pays every bucket's compile, and a fresh engine on the same model, config
    and table serves the same traffic with zero builds and the same tokens."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=2, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(4, 8, 16), batch_sizes=(2, 4))
    prompts = _ragged_prompts(np.random.default_rng(4), [2, 3, 5, 7, 9, 16])
    first = ServingEngine(model, params, cfg, table)
    cold = first.serve(prompts)
    assert 1 <= first.stats()["compiles"] <= len(table)
    second = ServingEngine(model, params, cfg, table)
    warm = second.serve(prompts)
    stats = second.stats()
    assert stats["compiles"] == 0 and stats["requests"] == len(prompts)
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_warmup_precompiles_all_buckets(tiny_model):
    """After warmup, a mixed workload (including the pad-overflow phase
    plan) triggers zero fresh executor builds."""
    model, params = tiny_model
    # max_new_tokens > max_latents - num_latents: the zero-pad and
    # pad-overflow phase plans genuinely differ (s2 > s1), so warmup must
    # cover both variants per cell.
    cfg = GenerationConfig(max_new_tokens=20, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(16,), batch_sizes=(2,))
    engine = ServingEngine(model, params, cfg, table)
    compiled = engine.warmup()
    assert compiled >= 1
    before = executor_cache_stats()["misses"]
    engine.serve(_ragged_prompts(np.random.default_rng(2), [2, 5, 9, 16]))
    assert executor_cache_stats()["misses"] == before  # all warm
    assert engine.stats()["executor_cache"]["hits"] > 0


@pytest.mark.slow
def test_underfilled_batch_keeps_cached_phase_plan(tiny_model):
    """Filler rows must not demote the micro-batch's generation plan: an
    underfilled bucket (dummy rows padding the batch dim) hits the SAME
    executor as a full bucket of the same shapes. Regression: max-padded
    fillers used to flip ``phase2_ok`` off for the whole batch, silently
    replacing the cached prefix-growth phase with windowed recompute."""
    model, params = tiny_model
    # plans differ when max_new_tokens overruns the latent-growth phase:
    # full-pad rows would force s2 == s1 (a second, slower executor)
    cfg = GenerationConfig(max_new_tokens=20, num_latents=2, sampling=GREEDY)
    engine = ServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(16,), batch_sizes=(4,))
    )
    rng = np.random.default_rng(5)
    full = engine.serve(_ragged_prompts(rng, [4, 6, 8, 10]))
    before = executor_cache_stats()["misses"]
    underfilled = engine.serve(_ragged_prompts(rng, [4, 6, 8]))  # +1 filler row
    assert executor_cache_stats()["misses"] == before  # same plan, same executor
    assert all(r.shape == (20,) for r in full + underfilled)


def test_stats_queue_waits_and_padding(tiny_model):
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=2, num_latents=2, sampling=GREEDY)
    engine = ServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8,), batch_sizes=(2,))
    )
    engine.serve(_ragged_prompts(np.random.default_rng(3), [4, 4, 4]))
    stats = engine.stats()
    waits = stats["queue_wait_ms"]
    assert waits["p50"] is not None and waits["p95"] >= waits["p50"] >= 0.0
    assert 0.0 < stats["prompt_padding_efficiency"] <= 1.0
    assert stats["tokens_generated"] == 3 * 2


def test_infeasible_bucket_rejected(tiny_model):
    model, params = tiny_model
    # bucket 32 with num_latents=2: nominal prefix 30 > max_prefix_len 16
    cfg = GenerationConfig(max_new_tokens=2, num_latents=2, sampling=GREEDY)
    engine = ServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8, 32), batch_sizes=(1,))
    )
    with pytest.raises(ValueError, match="no feasible prompt bucket"):
        engine.submit(np.arange(1, 12, dtype=np.int32))  # needs the 32 bucket
    engine.submit(np.arange(1, 6, dtype=np.int32))  # 8-bucket still fine
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="exceed the model context"):
        ServingEngine(model, params, cfg, BucketTable(prompt_lens=(64,), batch_sizes=(1,)))


@pytest.mark.slow
def test_mixed_configs_not_packed_together(tiny_model):
    """Only identical-config requests share a micro-batch; a config change
    mid-queue splits the batch instead of mixing generation plans."""
    model, params = tiny_model
    cfg_a = GenerationConfig(max_new_tokens=2, num_latents=2, sampling=GREEDY)
    cfg_b = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    engine = ServingEngine(
        model, params, cfg_a, BucketTable(prompt_lens=(8,), batch_sizes=(4,))
    )
    rng = np.random.default_rng(4)
    r1 = engine.submit(_ragged_prompts(rng, [4])[0])
    r2 = engine.submit(_ragged_prompts(rng, [5])[0], config=cfg_b)
    r3 = engine.submit(_ragged_prompts(rng, [6])[0])
    engine.run_until_idle()
    assert engine.stats()["batches"] == 2  # {r1, r3} then {r2}
    assert r1.result.shape == (2,) and r3.result.shape == (2,)
    assert r2.result.shape == (4,)


# -- pipeline + CLI surfaces ----------------------------------------------
@pytest.mark.slow
def test_pipeline_bucketing_greedy_parity():
    from perceiver_io_tpu.data.text.tokenizers import ByteTokenizer
    from perceiver_io_tpu.inference import pipeline

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=16, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    tok = ByteTokenizer(padding_side="left")
    prompts = ["hello", "hi", "what is up", "ok"]
    kwargs = dict(max_new_tokens=4, num_latents=2, temperature=0.0)

    plain = pipeline("text-generation", model, params, tok)(prompts, **kwargs)
    bucketed_pipe = pipeline(
        "text-generation", model, params, tok,
        bucketing=True, bucket_table=BucketTable(prompt_lens=(8, 16), batch_sizes=(2, 4)),
    )
    bucketed = bucketed_pipe(prompts, **kwargs)
    assert bucketed == plain
    stats = bucketed_pipe.serving_stats()
    assert stats is not None and stats["requests"] == len(prompts)
    # a second identical call is fully warm: same bucket, zero new builds
    before = executor_cache_stats()["misses"]
    assert bucketed_pipe(prompts, **kwargs) == plain
    assert executor_cache_stats()["misses"] == before


def test_pipeline_warmup_requires_bucketing(tiny_model):
    from perceiver_io_tpu.data.text.tokenizers import ByteTokenizer
    from perceiver_io_tpu.inference import pipeline

    model, params = tiny_model
    pipe = pipeline("text-generation", model, params, ByteTokenizer(padding_side="left"))
    with pytest.raises(ValueError, match="bucketing=True"):
        pipe.warmup(max_new_tokens=2)


@pytest.mark.slow
def test_serve_cli_subcommand(tmp_path):
    """`clm serve --ckpt ...` end to end: checkpoint -> bucketed engine ->
    one JSON-able result per prompt line."""
    from perceiver_io_tpu.scripts.text import clm as clm_script
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=16, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    save_pretrained(str(tmp_path / "ckpt"), params, cfg)
    (tmp_path / "prompts.txt").write_text("hello\nhi\n")

    results = clm_script.main([
        "serve", "--ckpt", str(tmp_path / "ckpt"),
        f"--serve.prompts={tmp_path}/prompts.txt",
        "--serve.max_new_tokens=3", "--serve.num_latents=2",
        "--serve.prompt_buckets=8", "--serve.batch_buckets=2",
        "--serve.warmup=false",
    ])
    assert [r["prompt"] for r in results] == ["hello", "hi"]
    assert all(isinstance(r["completion"], str) for r in results)


def test_serve_cli_requires_ckpt():
    from perceiver_io_tpu.scripts.text import clm as clm_script

    with pytest.raises(SystemExit, match="requires --ckpt"):
        clm_script.main(["serve", "--serve.max_new_tokens=2"])


@pytest.mark.chaos
@pytest.mark.slow
def test_serve_cli_maps_infeasible_prompt_to_error_record(tmp_path):
    """A prompt longer than the largest bucket becomes a per-line
    ``{"error": ...}`` JSON record; the rest of the run still completes."""
    from perceiver_io_tpu.scripts.text import clm as clm_script
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=16, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 16)["params"]
    save_pretrained(str(tmp_path / "ckpt"), params, cfg)
    (tmp_path / "prompts.txt").write_text(
        "hi\n" + "x" * 50 + "\nok\n"  # line 2 exceeds the 8-token bucket
    )

    results = clm_script.main([
        "serve", "--ckpt", str(tmp_path / "ckpt"),
        f"--serve.prompts={tmp_path}/prompts.txt",
        "--serve.max_new_tokens=2", "--serve.num_latents=2",
        "--serve.prompt_buckets=8", "--serve.batch_buckets=2",
        "--serve.warmup=false",
    ])
    assert [r["prompt"] for r in results] == ["hi", "x" * 50, "ok"]
    assert "completion" in results[0] and "completion" in results[2]
    assert results[1]["status"] == "rejected"
    assert "exceeds the largest bucket" in results[1]["error"]

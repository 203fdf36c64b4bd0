"""Block-paged KV pool + ragged paged decode attention (docs/serving.md
"Block-paged KV"; ``serving/kv_pool.py``, ``serving/slots.py``,
``ops/paged_attention.py``).

The load-bearing assertions:

- greedy output under ``kv_layout="paged"`` is **token-identical** to the
  dense layout (and therefore to per-request ``generate()``) across
  mid-flight admits, boundary crossings, chunked prefill, and recycled
  slots — the gather-based paged attend is bitwise-identical math;
- the allocator leaks nothing across admit/retire/failover cycles, hands
  out blocks in deterministic lowest-id order, and reproduces identical
  block-table histories for identical FakeClock-driven schedules;
- compiles stay bounded (``len(prompt_buckets) + 2`` / ``+3`` with
  chunked prefill — the same bound as dense) and steady-state traffic
  retraces nothing;
- ``check_feasible`` rejects requests that could NEVER fit the pool at
  submit, while requests that transiently don't fit queue and complete;
- ``kv_cache_resident_bytes`` tracks live pages (capacity stays on
  ``kv_cache_capacity_bytes``), and the ``kv_pool_*`` families balance.

All pure-CPU, tiny shapes, fast — tier-1 (marker ``paged_kv``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference import decode_strategy as strategy_mod
from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    executor_cache_stats,
    generate,
    reset_executor_caches,
)
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.reliability import FakeClock
from perceiver_io_tpu.serving import BucketTable, KVPagePool, SlotServingEngine
from perceiver_io_tpu.serving.kv_pool import PoolExhausted

pytestmark = [pytest.mark.paged_kv, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use (executor cache keys
# include the module fingerprint; an identically-configured model in
# another file would pre-populate the cache this file counts).
TINY = dict(
    vocab_size=73, max_seq_len=32, max_latents=8, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    return model, params


def _ragged_prompts(rng, lengths, vocab=73):
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lengths]


def _ref(model, params, prompt, cfg):
    return np.asarray(generate(model, params, jnp.asarray(prompt[None, :]), cfg))[0]


# -- the allocator as a unit ------------------------------------------------
def test_allocator_deterministic_order_and_zero_leak():
    """Lowest-free-id-first allocation, lazy mapping consuming the
    reservation, and release returning everything: admit/retire cycles in
    any interleaving leave zero leaked pages."""
    pool = KVPagePool(num_blocks=6, block_size=4, slots=3, max_len=16)
    assert pool.pages_per_slot == 4
    assert pool.blocks_needed(9) == 3 and pool.blocks_needed(0) == 0
    pool.reserve(0, 9)   # 3 blocks
    pool.reserve(1, 5)   # 2 blocks
    assert pool.reserved == 5 and pool.in_use == 0
    assert pool.ensure(0, 4)  # maps 1 block -> lowest id 1
    assert pool.table_row(0)[0] == 1
    assert pool.ensure(1, 5)  # maps 2 -> ids 2, 3
    assert list(pool.table_row(1)[:2]) == [2, 3]
    assert pool.ensure(0, 9)  # maps 2 more -> ids 4, 5
    assert list(pool.table_row(0)[:3]) == [1, 4, 5]
    assert not pool.ensure(0, 9)  # idempotent: nothing new
    assert pool.in_use == 5 and pool.high_water == 5
    # slot 2 cannot reserve 2 blocks: only 1 unreserved
    assert not pool.can_reserve(2)
    with pytest.raises(PoolExhausted):
        pool.reserve(2, 8)
    # release slot 0: its 3 blocks return; lowest-first reuse
    assert pool.release(0) == 3
    assert list(pool.table_row(0)) == [0, 0, 0, 0]
    pool.reserve(2, 8)
    pool.ensure(2, 8)
    assert list(pool.table_row(2)[:2]) == [1, 4]  # freed ids reused, lowest first
    pool.release(1)
    pool.release(2)
    assert pool.in_use == 0 and pool.reserved == 0 and pool.leaked() == 0
    assert pool.allocs_total == pool.frees_total == 7
    # double-reserve on an occupied slot is an engine bug, not load
    pool.reserve(0, 4)
    with pytest.raises(ValueError, match="already holds"):
        pool.reserve(0, 4)
    # mapping past the reservation is an accounting bug
    with pytest.raises(ValueError, match="past its reservation"):
        pool.ensure(0, 16)


def test_allocator_schedule_determinism_under_fakeclock(tiny_model):
    """Two engines driven through an identical FakeClock schedule —
    admits, a mid-generation deadline retirement, refills — produce
    IDENTICAL block-table histories (the allocator is part of the
    compiled-program inputs, so this is also a determinism claim about
    serving itself), and drain leak-free."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)

    def run():
        clock = FakeClock()
        engine = SlotServingEngine(
            model, params, cfg, BucketTable(prompt_lens=(16,), batch_sizes=(1,)),
            slots=2, clock=clock, kv_layout="paged", kv_block_size=8,
        )
        rng = np.random.default_rng(7)
        prompts = _ragged_prompts(rng, [5, 9, 7])
        engine.submit(prompts[0], deadline_s=5.0)
        engine.submit(prompts[1])
        engine.submit(prompts[2])
        history = []
        engine.step(); history.append(engine._pool.table().copy())
        engine.step(); history.append(engine._pool.table().copy())
        clock.advance(10.0)  # expires request 0 mid-generation
        while engine.pending():
            engine.step()
            history.append(engine._pool.table().copy())
        return engine, history

    e1, h1 = run()
    e2, h2 = run()
    assert len(h1) == len(h2)
    for a, b in zip(h1, h2):
        np.testing.assert_array_equal(a, b)
    assert e1._pool.in_use == 0 and e1._pool.leaked() == 0
    assert e1._pool.allocs_total == e1._pool.frees_total > 0


# -- greedy token parity ----------------------------------------------------
@pytest.mark.slow  # 16s; still in the `-m paged_kv` lane (runtime audit)
def test_paged_parity_mid_flight_admit_boundary_recycled(tiny_model):
    """5 ragged requests through 2 paged slots: mid-flight admits into
    recycled slots, rows crossing the latent boundary at different steps
    (the write-routing select), heterogeneous max_new — every output
    token-identical to per-request generate() AND to the dense layout."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=10, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    prompts = _ragged_prompts(np.random.default_rng(0), [3, 11, 8, 3, 11])
    news = [10, 4, 10, 7, 10]

    def serve(layout):
        # sizing args imply paged (the engine rejects sizing a dense pool)
        sizing = {"kv_block_size": 8} if layout == "paged" else {}
        engine = SlotServingEngine(
            model, params, cfg, table, slots=2, kv_layout=layout, **sizing,
        )
        reqs = [
            engine.submit(p, config=dataclasses.replace(cfg, max_new_tokens=k))
            for p, k in zip(prompts, news)
        ]
        engine.run_until_idle()
        return engine, [r.result for r in reqs]

    paged_engine, paged = serve("paged")
    _, dense = serve("dense")
    for p, k, out_p, out_d in zip(prompts, news, paged, dense):
        ref = _ref(model, params, p, dataclasses.replace(cfg, max_new_tokens=k))
        np.testing.assert_array_equal(out_p, ref)
        np.testing.assert_array_equal(out_p, out_d)
    assert paged_engine.stats()["kv_layout"] == "paged"
    assert paged_engine._pool.in_use == 0 and paged_engine._pool.leaked() == 0


@pytest.mark.slow  # 2026-08 audit: ~10s; chunked parity stays tier-1 via the
# decode-strategy three-geometry drill (still in the `-m paged_kv` lane)
def test_paged_parity_chunked_prefill_geometries(tiny_model):
    """Chunked admission under the paged layout — pages mapped per chunk
    call, the finalize scattering the staged row through the block table —
    across the three geometries the dense chunk tests pin (admit during
    decode, chunk == prompt end, prompt < chunk)."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=5, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 24), batch_sizes=(1,))
    prompts = _ragged_prompts(np.random.default_rng(1), [22, 5, 18, 24])
    engine = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged",
        kv_block_size=4, prefill_chunk=4,
    )
    outs = engine.serve(prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))
    assert engine.stats()["prefill_chunks"] > 0
    assert engine._pool.in_use == 0 and engine._pool.leaked() == 0


# -- compile-count guarantee ------------------------------------------------
def test_paged_compile_bound_and_zero_retrace(tiny_model):
    """Paged warmup compiles exactly the dense bound — len(prompt_buckets)
    prefills + decode + boundary variant (+1 chunk executor when chunked
    prefill is on) — and mixed traffic afterwards retraces NOTHING: block
    tables are traced arguments, never cache keys."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    reset_executor_caches()
    engine = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged", kv_block_size=8,
    )
    assert engine.warmup() == len(table.prompt_lens) + 2

    chunked = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged", kv_block_size=8,
        prefill_chunk=4,
    )
    # prefill/decode executors are shared with the unchunked engine (same
    # cache keys); the chunk executor is the one fresh build (the +3 bound)
    assert chunked.warmup() == 1
    before = executor_cache_stats()["misses"]
    rng = np.random.default_rng(4)
    for i, p in enumerate(_ragged_prompts(rng, [3, 4, 8, 12, 16, 9, 5])):
        engine.submit(p, config=dataclasses.replace(cfg, max_new_tokens=2 + (i % 4)))
    engine.run_until_idle()
    chunked.serve(_ragged_prompts(rng, [14, 16]))
    assert executor_cache_stats()["misses"] == before  # zero retraces
    assert engine.stats()["completed"] == 7


# -- feasibility ------------------------------------------------------------
def test_pool_capacity_feasibility_and_queueing(tiny_model):
    """A request whose worst case can NEVER fit the pool rejects at submit
    with the pool's own reason; requests that fit but not right now queue
    (kv_pool_admit_waits_total counts the head-of-line waits) and all
    complete token-identically once residents retire."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(16,), batch_sizes=(1,))
    engine = SlotServingEngine(
        model, params, cfg, table, slots=4, kv_layout="paged",
        kv_block_size=8, kv_blocks=2,  # one 9..10-token request at a time
    )
    with pytest.raises(ValueError, match="can never be admitted"):
        engine.submit(np.arange(1, 12, dtype=np.int32))  # 11 + 6 = 17 > 16
    assert engine.stats()["rejected"] == 1

    prompts = _ragged_prompts(np.random.default_rng(2), [9, 9, 9])
    outs = engine.serve(prompts)  # 15 positions -> 2 blocks each: serialized
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))
    stats = engine.stats()
    assert stats["kv_pool"]["admit_waits"] > 0
    assert stats["kv_pool"]["high_water"] == 2  # never over the pool
    assert engine._pool.in_use == 0 and engine._pool.leaked() == 0


# -- observability ----------------------------------------------------------
def test_kv_gauges_resident_vs_capacity(tiny_model):
    """kv_cache_resident_bytes tracks LIVE pages (admit grows it, retire
    shrinks it back to the dense-stack floor); the analytic worst case
    stays constant on kv_cache_capacity_bytes; the alloc/free counters
    balance at idle."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(16,), batch_sizes=(1,)),
        slots=2, kv_layout="paged", kv_block_size=8,
    )
    reg = engine.registry
    capacity = reg.gauge("kv_cache_capacity_bytes")
    floor = reg.gauge("kv_cache_resident_bytes")  # stack caches only
    assert 0 < floor < capacity
    assert reg.gauge("kv_pool_blocks") == engine._pool.num_blocks

    req = engine.submit(np.arange(1, 10, dtype=np.int32))
    engine.step()  # admit + first token
    mid = reg.gauge("kv_cache_resident_bytes")
    assert floor < mid <= capacity
    assert reg.gauge("kv_pool_blocks_in_use") > 0
    assert reg.gauge("kv_cache_capacity_bytes") == capacity
    engine.run_until_idle()
    assert req.status == "ok"
    assert reg.gauge("kv_cache_resident_bytes") == floor
    assert reg.gauge("kv_pool_blocks_in_use") == 0
    assert reg.counter("kv_pool_block_allocs_total") == \
        reg.counter("kv_pool_block_frees_total") > 0
    assert reg.gauge("kv_pool_blocks_high_water") > 0
    # the dense layout keeps the old behavior: resident == capacity
    dense = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(16,), batch_sizes=(1,)),
        slots=2, kv_layout="dense",
    )
    assert dense.registry.gauge("kv_cache_resident_bytes") == \
        dense.registry.gauge("kv_cache_capacity_bytes")


# -- kv-layout resolution / autotune ---------------------------------------
def test_kv_layout_resolution_autotune_and_persistence(tiny_model, tmp_path,
                                                       monkeypatch):
    """Resolution precedence (explicit > env > measured > dense), the
    FakeClock tie breaking toward dense deterministically, and the
    registry artifact round-tripping kv_entries beside the boundary
    entries (corrupt files degrade to re-measurement)."""
    model, params = tiny_model
    strategy_mod.reset_registry()
    try:
        assert strategy_mod.resolve_kv_layout(None, model) == "dense"  # untuned
        monkeypatch.setenv(strategy_mod.ENV_KV_LAYOUT, "paged")
        assert strategy_mod.resolve_kv_layout(None, model) == "paged"
        assert strategy_mod.resolve_kv_layout("dense", model) == "dense"  # explicit wins
        monkeypatch.delenv(strategy_mod.ENV_KV_LAYOUT)
        with pytest.raises(ValueError, match="kv layout"):
            strategy_mod.resolve_kv_layout("blocky", model)

        # FakeClock: both arms measure 0.0 -> tie -> dense, deterministically
        clock = FakeClock()
        verdict = strategy_mod.autotune_kv_layout(
            model, params, block_size=8, clock=clock, new_tokens=2,
        )
        assert verdict == "dense"
        assert strategy_mod.lookup_kv_layout(model) == "dense"
        # memoized: a second call does not re-measure (flip the stored
        # verdict and observe it is returned untouched)
        strategy_mod.record_kv_layout(model, "paged", note="pinned by test")
        assert strategy_mod.autotune_kv_layout(model, params, block_size=8) == "paged"

        path = str(tmp_path / "strategy.json")
        strategy_mod.record(model, "recompute")  # boundary entry rides along
        strategy_mod.save_registry(path)
        strategy_mod.reset_registry()
        assert strategy_mod.load_registry(path) == 2
        assert strategy_mod.lookup_kv_layout(model) == "paged"
        assert strategy_mod.lookup(model) == "recompute"

        corrupt = tmp_path / "corrupt.json"
        corrupt.write_text("{not json")
        assert strategy_mod.load_registry(str(corrupt)) == 0
    finally:
        strategy_mod.reset_registry()


def test_engine_kv_layout_env_resolution(tiny_model, monkeypatch):
    """An engine constructed without kv_layout obeys PERCEIVER_KV_LAYOUT."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=3, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(16,), batch_sizes=(1,))
    monkeypatch.setenv(strategy_mod.ENV_KV_LAYOUT, "paged")
    engine = SlotServingEngine(model, params, cfg, table, slots=2)
    assert engine.kv_layout == "paged" and engine._pool is not None
    monkeypatch.delenv(strategy_mod.ENV_KV_LAYOUT)
    assert SlotServingEngine(model, params, cfg, table, slots=2).kv_layout == "dense"
    with pytest.raises(ValueError, match="kv_layout"):
        SlotServingEngine(model, params, cfg, table, slots=2, kv_layout="nope")
    with pytest.raises(ValueError, match="kv_blocks"):
        SlotServingEngine(model, params, cfg, table, slots=2, kv_blocks=0)
    # sizing the pool IS choosing paged: a dense resolution must reject
    # loudly instead of silently discarding the operator's HBM budget
    with pytest.raises(ValueError, match="choosing the paged layout"):
        SlotServingEngine(
            model, params, cfg, table, slots=2, kv_layout="dense",
            kv_block_size=8,
        )
    with pytest.raises(ValueError, match="choosing the paged layout"):
        SlotServingEngine(model, params, cfg, table, slots=2, kv_blocks=4)

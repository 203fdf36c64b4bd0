"""Cross-request prefix sharing: copy-on-write blocks in the KV pool
(docs/serving.md "Prefix sharing"; ``serving/kv_pool.py``,
``serving/slots.py``, ``ops/paged_attention.py``).

The load-bearing assertions:

- greedy output under ``prefix_cache="on"`` is **token-identical** to the
  unshared paged path (and to per-request ``generate()``) across
  hot-prefix, partial-prefix, divergent-mid-block, chunked-prefill,
  recycled-slot, cancellation, and fleet-failover geometries;
- the allocator is refcount-aware and zero-leak: a shared block frees on
  its LAST deref, ``frees_by_cause`` gains the ``"shared"``/``"cow"``
  split, and identical FakeClock schedules replay identical block-table
  histories with sharing live;
- a shared page is never written through — the admit-time partial-block
  COW and the decode-step write guard both copy first (synthetic drill);
- unreferenced cached prefixes LRU-drop under pool pressure before an
  admission waits;
- compiles stay bounded (the paged bound + the one shared-prefill program
  + the page copy) and steady-state hot traffic retraces nothing;
- every ``kv_prefix_*`` family has a direct HELP entry and the
  ``serving.prefix_hit`` event carries the shared-span attribution.

All pure-CPU, tiny shapes, fast — tier-1 (marker ``prefix_cache``).
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
from perceiver_io_tpu.observability import LoadGenerator, WorkloadSpec
from perceiver_io_tpu.observability.exporters import HELP_TEXT, to_prometheus_text
from perceiver_io_tpu.reliability import ChaosRegistry, FakeClock
from perceiver_io_tpu.serving import (
    BucketTable,
    FleetRouter,
    KVPagePool,
    PrefixBlockIndex,
    SlotServingEngine,
)
from perceiver_io_tpu.serving.kv_pool import PoolExhausted

pytestmark = [pytest.mark.prefix_cache, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape another test module uses (executor cache keys
# include the module fingerprint; an identically-configured model elsewhere
# would pre-populate the caches this file's engines build and count).
TINY = dict(
    vocab_size=71, max_seq_len=32, max_latents=8, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)
GREEDY = SamplingConfig(temperature=0.0)
GEN = None  # set per test via _gcfg


def _gcfg(max_new=6, num_latents=2):
    return GenerationConfig(
        max_new_tokens=max_new, num_latents=num_latents, sampling=GREEDY
    )


TABLE = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    return model, params


def _engine(tiny_model, pc="on", *, slots=2, bs=4, table=TABLE, cfg=None, **kw):
    model, params = tiny_model
    return SlotServingEngine(
        model, params, cfg or _gcfg(), table, slots=slots, kv_layout="paged",
        kv_block_size=bs, prefix_cache=pc, **kw,
    )


def _ref(tiny_model, prompt, cfg):
    model, params = tiny_model
    return np.asarray(generate(model, params, jnp.asarray(prompt[None, :]), cfg))[0]


def _hot_prompts(rng, *, prefix_len=12, tails=(3, 3, 4, 2), vocab=71):
    prefix = rng.integers(1, vocab, size=prefix_len, dtype=np.int32)
    return [
        np.concatenate([prefix, rng.integers(1, vocab, size=int(t), dtype=np.int32)])
        for t in tails
    ]


# -- the paged read path under aliased tables -------------------------------
def test_paged_attention_shared_table_parity():
    """ops/paged_attention read-path parity with ALIASED tables: two rows
    whose tables reference the same physical blocks gather bitwise-equal
    k/v and produce bitwise-equal attention outputs — sharing is invisible
    to the read path (the property the whole prefix cache rests on)."""
    from perceiver_io_tpu.ops import paged_attention as paged

    rng = np.random.default_rng(0)
    bs, pages, h, d, n = 4, 4, 2, 8, 16
    pool_tokens = (pages * 2 + 1) * bs
    pool_k = jnp.asarray(rng.normal(size=(pool_tokens, h, d)).astype(np.float32))
    pool_v = jnp.asarray(rng.normal(size=(pool_tokens, h, d)).astype(np.float32))
    # row 0 and row 1 share blocks 1,2 (the "prefix"); tails diverge
    table = jnp.asarray([[1, 2, 3, 0], [1, 2, 5, 0]], jnp.int32)
    flat = paged.flat_position_indices(table, bs, n)
    np.testing.assert_array_equal(flat[0][:8], flat[1][:8])  # aliased span
    k = paged.gather_kv(pool_k, flat)
    np.testing.assert_array_equal(np.asarray(k[0, :, :8]), np.asarray(k[1, :, :8]))
    q = jnp.asarray(rng.normal(size=(2, h, 1, d)).astype(np.float32))
    q = jnp.concatenate([q[:1], q[:1]], axis=0)  # same query both rows

    def attend(q, k, v, *, pad_mask, deterministic):
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        logits = jnp.where(pad_mask[:, None, None, :], -1e30, logits)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits), v)

    # mask the divergent tail: only the shared span is live for both rows
    pad_mask = jnp.arange(n)[None, :] >= 8
    pad_mask = jnp.broadcast_to(pad_mask, (2, n))
    out = paged.paged_decode_attention(
        attend, q, pool_k, pool_v, table, block_size=bs, n=n,
        pad_mask=pad_mask,
    )
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))


# -- the allocator as a unit ------------------------------------------------
def test_pool_refcounts_shared_maps_cow_and_leak_accounting():
    """map_shared excludes referenced blocks from the reservation, release
    becomes a deref (free on LAST reference only), cow swaps a private
    block in and tags its source's final free "cow", and leaked() stays 0
    with retained-but-unmapped blocks resident."""
    pool = KVPagePool(num_blocks=8, block_size=4, slots=3, max_len=32)
    # donor: 3 private blocks
    pool.reserve(0, 10)  # 3 blocks
    pool.ensure(0, 10)
    donor_blocks = list(pool.slot_blocks(0))
    assert donor_blocks == [1, 2, 3]
    # "index" retains the first two (published prefix blocks)
    pool.retain(1)
    pool.retain(2)
    assert pool.refcount(1) == 2 and pool.refcount(3) == 1
    # sharer: maps blocks 1,2 by reference + 1 private block
    pool.reserve(1, 10, shared_blocks=2)
    assert pool._reserved[1] == 1
    pool.map_shared(1, [1, 2])
    assert pool.page_shared(1, 0) and pool.page_shared(1, 1)
    pool.ensure(1, 10)
    assert list(pool.slot_blocks(1)) == [1, 2, 4]
    assert pool.refcount(1) == 3
    # COW on the sharer's page 1: needs a block but reservation is spent —
    # free blocks exist, so the swap allocates past it
    old, new = pool.cow(1, 1)
    assert (old, new) == (2, 5)
    assert list(pool.slot_blocks(1)) == [1, 5, 4]
    assert pool.refcount(2) == 2  # donor + index ref survive
    assert pool.cow_swaps_total == 1
    # donor retires: blocks 1,2 stay (index refs), 3 frees
    assert pool.release(0, cause="retire") == 1
    assert pool.frees_by_cause == {"retire": 1}
    assert pool.shared_derefs_total > 0
    assert pool.leaked() == 0  # retained blocks are referenced, not leaked
    # sharer cancels: 5, 4 free; 1 drops to index-only
    assert pool.release(1, cause="cancelled") == 2
    assert pool.frees_by_cause["cancelled"] == 2
    # index evicts its two blocks: the "shared" cause split
    assert pool.deref(1, cause="shared") == 1
    assert pool.deref(2, cause="shared") == 1
    assert pool.frees_by_cause["shared"] == 2
    assert pool.in_use == 0 and pool.leaked() == 0
    assert pool.allocs_total == pool.frees_total == 5
    stats = pool.stats()
    assert stats["shared_maps_total"] == 2
    assert stats["cow_swaps_total"] == 1
    assert stats["refs_total"] == 0 and stats["shared_blocks"] == 0
    # retain/deref on a free block is an engine bug, not load
    with pytest.raises(ValueError, match="not allocated"):
        pool.retain(7)
    with pytest.raises(ValueError, match="not allocated"):
        pool.deref(7)


def test_pool_cow_respects_free_list_invariant():
    """A reservation-less COW must not steal blocks other slots reserved:
    with every free block spoken for it raises PoolExhausted."""
    pool = KVPagePool(num_blocks=3, block_size=4, slots=2, max_len=16)
    pool.reserve(0, 4)
    pool.ensure(0, 4)
    pool.retain(1)  # page 0 now shared (slot + fake index)
    pool.reserve(1, 8)  # slot 1 reserves the remaining 2 blocks
    with pytest.raises(PoolExhausted, match="copy-on-write"):
        pool.cow(0, 0)
    pool.release(1)
    old, new = pool.cow(0, 0)  # now fine: free blocks exceed reservations
    assert old == 1 and new == 2
    pool.release(0)
    pool.deref(1, cause="shared")
    assert pool.leaked() == 0


def test_prefix_index_match_insert_best_partial_and_lru_eviction():
    """Radix semantics: full-block chain matching, first-donor-wins
    insert, longest-LCP divergent-block candidate, and deterministic
    LRU-leaf eviction (deepest leaves before parents, ties by use order)."""
    pool = KVPagePool(num_blocks=8, block_size=4, slots=2, max_len=32)
    index = PrefixBlockIndex(block_size=4)
    tokens = np.arange(1, 13, dtype=np.int32)  # blocks [1..4],[5..8],[9..12]
    pool.reserve(0, 12)
    pool.ensure(0, 12)
    assert index.insert(tokens, pool.slot_blocks(0), pool) == 3
    assert index.cached_blocks == 3
    # re-publish of the same path is a no-op (first donor wins)
    assert index.insert(tokens, (7, 7, 7), pool) == 0
    match = index.match(tokens)
    assert [n.block for n in match] == [1, 2, 3]
    assert index.match(np.arange(2, 9, dtype=np.int32)) == []
    # divergent mid-block: first block matches, second diverges at token 2
    div = tokens.copy()
    div[6] = 63
    m = index.match(div)
    assert [n.block for n in m] == [1]
    cand, lcp = index.best_partial(m, div)
    assert cand is not None and cand.block == 2 and lcp == 2
    # eviction: only leaves drop; the chain unwinds deepest-first; blocks
    # retained only by the index physically free with cause="shared"
    pool.release(0)  # donor gone: index holds the only refs
    assert pool.in_use == 3 and pool.leaked() == 0
    assert index.evict_one(pool) == 1  # LRU leaf = deepest block 3
    assert index.cached_blocks == 2
    assert pool.frees_by_cause["shared"] == 1
    assert index.flush(pool) == 2
    assert index.cached_blocks == 0 and pool.in_use == 0
    assert index.evict_one(pool) is None


def test_allocator_schedule_determinism_with_sharing(tiny_model):
    """The refcount-determinism drill: two engines driven through an
    identical FakeClock schedule with sharing live — hot admits, a
    mid-generation cancellation returning shared refs, refills — produce
    IDENTICAL block-table histories and identical refcount snapshots, and
    drain leak-free."""
    model, params = tiny_model
    cfg = _gcfg(max_new=5)

    def run():
        clock = FakeClock()
        engine = _engine(tiny_model, "on", clock=clock, cfg=cfg)
        rng = np.random.default_rng(11)
        prompts = _hot_prompts(rng, tails=(3, 4, 3, 2))
        handles = [engine.submit(p) for p in prompts]
        history, refs = [], []
        engine.step()
        engine.cancel(handles[1].request_id)
        while engine.pending():
            engine.step()
            history.append(engine._pool.table().copy())
            refs.append(sorted(engine._pool._refcount.items()))
        return engine, history, refs

    e1, h1, r1 = run()
    e2, h2, r2 = run()
    assert len(h1) == len(h2)
    for a, b in zip(h1, h2):
        np.testing.assert_array_equal(a, b)
    assert r1 == r2
    assert e1._pool.leaked() == 0
    # at idle everything still resident is exactly the cached prefix
    assert e1._pool.in_use == e1._prefix_index.cached_blocks > 0
    assert e1._prefix_index.flush(e1._pool) == e1._pool.frees_by_cause["shared"]
    assert e1._pool.in_use == 0 and e1._pool.leaked() == 0


# -- greedy token parity ----------------------------------------------------
def test_parity_hot_partial_divergent_recycled(tiny_model):
    """Hot full-prefix hits, a shorter prompt sharing part of the cached
    chain, a divergent-mid-block prompt (LCP partial + COW), and recycled
    slots — every output token-identical to the unshared paged engine AND
    per-request generate(), zero pool leak, COW counted."""
    cfg = _gcfg()
    rng = np.random.default_rng(0)
    prompts = _hot_prompts(rng, prefix_len=12, tails=(3, 3, 4, 2))
    div = prompts[0].copy()
    div[6] = int(div[6]) % 69 + 1 if int(div[6]) != int(div[6]) % 69 + 1 else 68
    prompts.append(div)
    prompts.append(prompts[0][:11])  # shorter: partial share of the chain
    news = [6, 4, 6, 5, 6, 4]

    def serve(pc):
        engine = _engine(tiny_model, pc, cfg=cfg)
        handles = [
            engine.submit(p, config=dataclasses.replace(cfg, max_new_tokens=k))
            for p, k in zip(prompts, news)
        ]
        engine.run_until_idle()
        return engine, [h.result for h in handles]

    eon, on = serve("on")
    eoff, off = serve("off")
    for p, k, a, b in zip(prompts, news, on, off):
        ref = _ref(tiny_model, p, dataclasses.replace(cfg, max_new_tokens=k))
        np.testing.assert_array_equal(a, ref)
        np.testing.assert_array_equal(a, b)
    st = eon.stats()["prefix_cache"]
    assert st["enabled"] and st["hits"] >= 4 and st["cow_copies"] >= 1
    assert st["shared_tokens"] > 0 and st["published"] > 0
    assert eon._pool.leaked() == 0
    assert eoff.stats()["prefix_cache"] == {"enabled": False}
    # the off arm must have zero prefix accounting
    assert eoff.registry.counter("kv_prefix_hits_total") == 0


def test_parity_chunked_prefill_shared_spread(tiny_model):
    """Shared admissions under chunked prefill: the staged span is the
    un-shared suffix only, spread one chunk per step when it exceeds the
    chunk size, straight into the pool — token-identical across hot and
    cold admissions, with staging chunks counted."""
    model, params = tiny_model
    cfg = _gcfg(max_new=4)
    table = BucketTable(prompt_lens=(8, 24), batch_sizes=(1,))
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, 71, size=8, dtype=np.int32)
    prompts = [
        np.concatenate([prefix, rng.integers(1, 71, size=k, dtype=np.int32)])
        for k in (14, 12, 10)
    ] + [rng.integers(1, 71, size=20, dtype=np.int32)]
    engine = _engine(
        tiny_model, "on", bs=4, table=table, cfg=cfg, prefill_chunk=4
    )
    outs = engine.serve(prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(tiny_model, p, cfg))
    st = engine.stats()
    assert st["prefill_chunks"] > 0
    assert st["prefix_cache"]["hits"] >= 2
    assert engine._pool.leaked() == 0


def test_cancellation_returns_refcounts_at_cancel_instant(tiny_model):
    """Cancel a sharer mid-generation AND mid-(shared)-admission: its
    private pages free tagged "cancelled" within the cancel instant, the
    shared prefix survives in the index for the next admission, and the
    surviving sharer's stream is untouched (token-identical)."""
    cfg = _gcfg()
    engine = _engine(tiny_model, "on", cfg=cfg, prefill_chunk=2)
    rng = np.random.default_rng(4)
    prompts = _hot_prompts(rng, prefix_len=12, tails=(3, 4, 3))
    h0 = engine.submit(prompts[0])
    engine.run_until_idle()  # donor publishes
    cached_before = engine._prefix_index.cached_blocks
    assert cached_before > 0
    h1 = engine.submit(prompts[1])
    h2 = engine.submit(prompts[2])
    engine.step()  # both resident (hot suffix fits one step)
    in_use_before = engine._pool.in_use
    assert engine.cancel(h1.request_id)
    # reclaim is immediate: mapped private pages freed before the next step
    assert engine._pool.in_use < in_use_before
    assert engine._pool.frees_by_cause.get("cancelled", 0) > 0
    assert engine._prefix_index.cached_blocks == cached_before
    engine.run_until_idle()
    np.testing.assert_array_equal(h2.result, _ref(tiny_model, prompts[2], cfg))
    assert h1.status == "cancelled"
    # cancel mid chunked shared admission: suffix long enough to spread
    long_tail = np.concatenate(
        [prompts[0][:12], rng.integers(1, 71, size=4, dtype=np.int32)]
    )
    h3 = engine.submit(long_tail)
    h4 = engine.submit(prompts[1])
    engine.step()
    if engine._admitting is not None:
        assert engine.cancel(engine._admitting.req.request_id)
    else:
        engine.cancel(h3.request_id)
    engine.run_until_idle()
    assert engine._pool.leaked() == 0
    assert engine._pool.in_use == engine._prefix_index.cached_blocks


def test_lru_eviction_under_pool_pressure_before_waiting(tiny_model):
    """A small pool fills with cached prefixes; a cold admission that
    cannot reserve LRU-drops unreferenced cached blocks instead of
    waiting, completes token-identically, and the eviction is counted."""
    cfg = _gcfg()
    engine = _engine(tiny_model, "on", cfg=cfg, kv_blocks=6)
    rng = np.random.default_rng(2)
    hot = _hot_prompts(rng, prefix_len=12, tails=(3,))[0]
    out = engine.serve([hot])[0]
    np.testing.assert_array_equal(out, _ref(tiny_model, hot, cfg))
    assert engine._prefix_index.cached_blocks == 3  # prefix_len 13 -> 3 full
    cold = rng.integers(1, 71, size=14, dtype=np.int32)
    out2 = engine.serve([cold])[0]
    np.testing.assert_array_equal(out2, _ref(tiny_model, cold, cfg))
    st = engine.stats()["prefix_cache"]
    assert st["evicted"] > 0
    assert engine._pool.leaked() == 0


def test_cow_write_guard_never_writes_through_a_shared_page(tiny_model):
    """The synthetic write-guard drill: force a resident's TAIL pages to
    read as shared (an extra retain, as if the index held them), then keep
    decoding — the guard COWs each page before the append/migration write
    lands, output stays token-identical, and the retained source pages
    keep their refs (never written, never freed out from under the
    'index')."""
    cfg = _gcfg(max_new=8)
    engine = _engine(tiny_model, "on", cfg=cfg)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 71, size=9, dtype=np.int32)
    h = engine.submit(prompt)
    engine.step()  # admitted + first token
    slot = next(s for s in engine._slots if s is not None).slot
    pinned = list(engine._pool.slot_blocks(slot))
    for b in pinned:
        engine._pool.retain(b)  # every mapped page now reads shared
    cows_before = engine.registry.counter("kv_prefix_cow_copies_total")
    engine.run_until_idle()
    assert engine.registry.counter("kv_prefix_cow_copies_total") > cows_before
    np.testing.assert_array_equal(h.result, _ref(tiny_model, prompt, cfg))
    # the pinned source pages still carry our refs — deref to drain
    for b in pinned:
        engine._pool.deref(b, cause="shared")
    assert engine._pool.in_use == engine._prefix_index.cached_blocks
    assert engine._pool.leaked() == 0


def test_fleet_failover_replay_rehits_survivor_cache(tiny_model):
    """Two paged+prefix replicas, one killed mid-decode: every request
    completes exactly once, recovered outputs are token-identical to the
    no-fault fleet, and the survivor's independent cache records hits
    (replays re-prefill through it). The fleet stats() rollup sums
    per-replica hit accounting."""
    model, params = tiny_model
    cfg = _gcfg(max_new=4)
    rng = np.random.default_rng(8)
    prompts = _hot_prompts(rng, prefix_len=12, tails=(3, 4, 2, 3, 4, 2))

    def factory_clock(clock):
        def factory():
            return SlotServingEngine(
                model, params, cfg, TABLE, slots=2, clock=clock,
                kv_layout="paged", kv_block_size=4, prefix_cache="on",
                rng=jax.random.PRNGKey(1),
            )
        return factory

    def run(chaos=None):
        clock = FakeClock()
        fleet = FleetRouter(
            [factory_clock(clock)] * 2, clock=clock, chaos=chaos,
        )
        handles = [fleet.submit(p) for p in prompts]
        fleet.run_until_idle()
        return fleet, handles

    baseline_fleet, base = run()
    assert all(h.status == "ok" for h in base)
    chaos = ChaosRegistry()
    chaos.crash_replica(0, 3)
    fleet, handles = run(chaos)
    assert [h.status for h in handles] == ["ok"] * len(handles)
    for got, want in zip(handles, base):
        np.testing.assert_array_equal(got.result, want.result)
    s = fleet.stats()
    assert s["failovers"] == 1
    assert s["prefix_cache"] is not None
    assert s["prefix_cache"]["hits"] > 0
    assert s["prefix_cache"]["hits"] + s["prefix_cache"]["misses"] >= len(prompts)
    for r in fleet.replicas:
        assert r.engine._pool.leaked() == 0


def test_shared_admit_pushes_device_table_without_page_crossings(tiny_model):
    """Regression: a straddle-partial hit whose shared+COW'd pages already
    cover EVERY page the request ever touches (no later ensure() maps a
    block, no decode page crossing) must still push the block table to
    device at admit — or decode gathers through a stale all-zero row and
    greedy output silently diverges."""
    model, params = tiny_model
    cfg = _gcfg(max_new=4, num_latents=6)
    table = BucketTable(prompt_lens=(24,), batch_sizes=(1,))
    engine = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged",
        kv_block_size=16, prefix_cache="on",
    )
    rng = np.random.default_rng(21)
    donor = rng.integers(1, 71, size=23, dtype=np.int32)
    np.testing.assert_array_equal(
        engine.serve([donor])[0], _ref(tiny_model, donor, cfg)
    )
    assert engine._prefix_index.cached_blocks == 1  # prefix_len 17 -> 1 block
    # re-hit with a 12-token prefix of the donor: one COW'd page covers the
    # whole 16-position worst case, so ensure() never maps a fresh block
    sharer = donor[:12]
    out = engine.serve([sharer])[0]
    np.testing.assert_array_equal(out, _ref(tiny_model, sharer, cfg))
    st = engine.stats()["prefix_cache"]
    assert st["cow_copies"] == 1 and st["hits"] == 1
    assert engine._pool.leaked() == 0


def test_inline_shared_admit_fault_clears_admission(tiny_model):
    """A fault in the FIRST executor call of an inline shared admission
    must clear the admission record before the prefill-fault handler
    rebuilds state: the request fails exactly once, the next step() does
    not advance a dead admission, and the engine keeps serving."""
    cfg = _gcfg()
    engine = _engine(tiny_model, "on", cfg=cfg)
    rng = np.random.default_rng(13)
    prompts = _hot_prompts(rng, prefix_len=12, tails=(3, 4))
    engine.serve([prompts[0]])  # donor warms the cache

    def boom():
        def raiser(*a, **k):
            raise RuntimeError("injected shared-prefill fault")
        return raiser

    real = engine._shared_prefill_executor
    engine._shared_prefill_executor = boom
    h = engine.submit(prompts[1])  # hot: takes the inline shared path
    engine.step()
    assert h.status == "failed" and "injected" in h.error
    assert engine._admitting is None
    assert engine._pool.leaked() == 0
    engine._shared_prefill_executor = real
    # the engine survives: the rebuilt state serves fresh traffic, and the
    # request above carries exactly one terminal disposition
    out = engine.serve([prompts[1]])[0]
    np.testing.assert_array_equal(out, _ref(tiny_model, prompts[1], cfg))
    assert engine.registry.counter("serving_requests_failed_total") == 1


def test_spread_shared_chunk_fault_fails_residents(tiny_model):
    """A fault on a LATER stage call of a spread shared admission must
    fail residents like a first-call fault: shared staging writes pool
    pages through the live state, so the weaker unshared-CPU handling
    (release the slot, keep decoding) would serve corrupt state."""
    model, params = tiny_model
    cfg = _gcfg(max_new=6)
    table = BucketTable(prompt_lens=(8, 24), batch_sizes=(1,))
    engine = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged",
        kv_block_size=4, prefix_cache="on", prefill_chunk=2,
    )
    rng = np.random.default_rng(17)
    prefix = rng.integers(1, 71, size=8, dtype=np.int32)
    donor = np.concatenate([prefix, rng.integers(1, 71, size=4, dtype=np.int32)])
    engine.serve([donor])  # publishes the prefix
    resident = engine.submit(donor)  # hot, short suffix: admits quickly
    engine.step()
    assert any(s is not None for s in engine._slots)
    # hot long-suffix admission spreads its chunks; blow up the SECOND call
    real = engine._shared_prefill_executor()
    calls = {"n": 0}

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected mid-admission fault")
        return real(*a, **k)

    engine._shared_prefill_executor = lambda: flaky
    victim = engine.submit(
        np.concatenate([prefix, rng.integers(1, 71, size=14, dtype=np.int32)])
    )
    while victim.status == "queued" or engine._admitting is not None:
        engine.step()
        if victim.status not in ("queued", "running") and engine._admitting is None:
            break
    assert victim.status == "failed"
    # the resident was failed too (state rebuilt), not left decoding
    # against the poisoned pool
    assert resident.status == "failed"
    assert engine._admitting is None
    assert engine._pool.leaked() == 0 and engine._pool.in_use == 0
    engine._shared_prefill_executor = lambda: real
    out = engine.serve([donor])[0]
    np.testing.assert_array_equal(out, _ref(tiny_model, donor, cfg))


def test_small_hit_long_suffix_falls_back_to_one_shot(tiny_model, monkeypatch):
    """Without an operator chunk discipline, a tiny hit in front of a long
    un-shared suffix is treated as a MISS (the one-shot bucket prefill
    beats an unbounded inline chunk drain) — output unchanged, miss
    counted."""
    cfg = _gcfg()
    engine = _engine(tiny_model, "on", cfg=cfg)  # prefill_chunk=None
    rng = np.random.default_rng(19)
    donor = _hot_prompts(rng, prefix_len=12, tails=(3,))[0]
    engine.serve([donor])
    monkeypatch.setattr(engine, "_shared_chunk_size", lambda: 2)  # bound=8
    hot_small = np.concatenate(
        [donor[:4], rng.integers(1, 71, size=11, dtype=np.int32)]
    )  # 1 shared block, suffix 9 > 8: falls back
    hits_before = engine.registry.counter("kv_prefix_hits_total")
    out = engine.serve([hot_small])[0]
    np.testing.assert_array_equal(out, _ref(tiny_model, hot_small, cfg))
    assert engine.registry.counter("kv_prefix_hits_total") == hits_before
    assert engine.registry.counter("kv_prefix_misses_total") >= 1
    assert engine._pool.leaked() == 0


# -- compile-count guarantee ------------------------------------------------
def test_compile_bound_and_zero_retrace_with_sharing(tiny_model):
    """Prefix-cache warmup compiles the paged bound plus exactly two more
    programs (the shared suffix-only prefill and the COW page copy); hot
    mixed traffic afterwards retraces NOTHING — shared spans, start
    positions, and block tables are all traced arguments."""
    model, params = tiny_model
    cfg = _gcfg(max_new=4)
    reset_executor_caches()
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, kv_layout="paged",
        kv_block_size=8, prefix_cache="on",
    )
    assert engine.warmup() == len(TABLE.prompt_lens) + 2 + 2
    before = executor_cache_stats()["misses"]
    rng = np.random.default_rng(5)
    prompts = _hot_prompts(rng, prefix_len=10, tails=(3, 4, 5, 2, 6))
    engine.serve(prompts)
    assert executor_cache_stats()["misses"] == before
    assert engine.stats()["prefix_cache"]["hits"] > 0


# -- resolution / persistence ----------------------------------------------
def test_prefix_cache_resolution_env_registry_and_ctor_errors(
        tiny_model, tmp_path, monkeypatch):
    """Resolution precedence (explicit > env > recorded > off), registry
    persistence beside the boundary/kv entries, and the ctor pairing rule:
    prefix_cache='on' without the paged layout rejects loudly."""
    model, params = tiny_model
    strategy_mod.reset_registry()
    try:
        assert strategy_mod.resolve_prefix_cache(None, model) == "off"
        monkeypatch.setenv(strategy_mod.ENV_PREFIX_CACHE, "on")
        assert strategy_mod.resolve_prefix_cache(None, model) == "on"
        assert strategy_mod.resolve_prefix_cache("off", model) == "off"
        monkeypatch.delenv(strategy_mod.ENV_PREFIX_CACHE)
        with pytest.raises(ValueError, match="prefix cache"):
            strategy_mod.resolve_prefix_cache("maybe", model)
        strategy_mod.record_prefix_cache(model, "on", note="recorded")
        assert strategy_mod.resolve_prefix_cache(None, model) == "on"
        path = str(tmp_path / "strategy.json")
        strategy_mod.save_registry(path)
        strategy_mod.reset_registry()
        assert strategy_mod.load_registry(path) == 1
        assert strategy_mod.lookup_prefix_cache(model) == "on"
        # engine obeys the recorded verdict under the paged layout...
        engine = SlotServingEngine(
            model, params, _gcfg(), TABLE, slots=2, kv_layout="paged",
            kv_block_size=4,
        )
        assert engine.prefix_cache == "on" and engine._prefix_index is not None
        # ...but a dense engine silently stays off (sharing needs tables)
        dense = SlotServingEngine(model, params, _gcfg(), TABLE, slots=2)
        assert dense.prefix_cache == "off" and dense._prefix_index is None
    finally:
        strategy_mod.reset_registry()
    # explicit on + kv_layout='auto' is allowed at ctor: the warmup
    # autotuner may still pick paged. The preference survives the dense
    # init, and a layout rebuild onto paged activates sharing (the
    # warmup-switch path); a dense verdict raises there instead of
    # dropping the explicit request silently.
    auto = SlotServingEngine(
        model, params, _gcfg(), TABLE, slots=2, kv_layout="auto",
        prefix_cache="on",
    )
    assert auto.prefix_cache == "off" and auto._prefix_pref == "on"
    auto._init_kv_state("paged")
    assert auto.prefix_cache == "on" and auto._prefix_index is not None
    with pytest.raises(ValueError, match="requires kv_layout='paged'"):
        SlotServingEngine(
            model, params, _gcfg(), TABLE, slots=2, prefix_cache="on"
        )
    with pytest.raises(ValueError, match="prefix_cache must be one of"):
        SlotServingEngine(
            model, params, _gcfg(), TABLE, slots=2, kv_layout="paged",
            prefix_cache="yes",
        )


# -- feasibility / concurrent packing ---------------------------------------
def test_admission_gate_accounts_for_shareable_blocks(tiny_model):
    """Where feasibility meets sharing: the single-request pool bound is
    PHYSICAL (a request's pages are distinct blocks, shared or not — it
    still rejects past the pool), but the admission gate excludes
    referenced blocks from each reservation, so two hot-prefix requests
    whose raw worst cases overflow the pool run CONCURRENTLY shared where
    the unshared engine serializes them at the queue head."""
    cfg = _gcfg(max_new=4)
    rng = np.random.default_rng(9)
    prompts = _hot_prompts(rng, prefix_len=8, tails=(4, 4))  # 12 tokens each
    # raw worst case: 16 positions -> 4 blocks each, 8 raw for the pair;
    # pool of 6: unshared serializes, shared packs (2 shared + 2x2 private)
    def serve(pc):
        engine = _engine(tiny_model, pc, cfg=cfg, kv_blocks=6)
        seed = engine.serve([prompts[0]])  # donor warms the cache (hit arm)
        handles = [engine.submit(p) for p in prompts]
        max_residents = 0
        while engine.pending():
            engine.step()
            max_residents = max(
                max_residents, sum(1 for s in engine._slots if s is not None)
            )
        assert engine._pool.leaked() == 0
        return engine, seed + [h.result for h in handles], max_residents

    eon, on, res_on = serve("on")
    eoff, off, res_off = serve("off")
    for a, b, p in zip(on, off, [prompts[0]] + prompts):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, _ref(tiny_model, p, cfg))
    assert res_on == 2  # shared pair resident together
    assert res_off == 1  # unshared pair serialized on the pool
    assert eoff.stats()["kv_pool"]["admit_waits"] > 0
    # the physical bound is cache-blind: 28 positions -> 7 blocks can never
    # fit the 6-block pool, however hot the prefix
    with pytest.raises(ValueError, match="can never be admitted"):
        eon.submit(
            np.concatenate([prompts[0], prompts[1][:4]]),
            config=dataclasses.replace(cfg, max_new_tokens=12),
        )


# -- observability ----------------------------------------------------------
def test_prefix_metrics_events_help_and_health(tiny_model):
    """Every kv_prefix_* family a traffic-bearing shared engine publishes
    has a direct HELP entry, the cached-blocks gauge tracks the index, the
    serving.prefix_hit event carries the shared-span attribution, and
    stats()/health() expose the prefix_cache section."""
    from perceiver_io_tpu.observability import Tracer

    cfg = _gcfg()
    tracer = Tracer()
    engine = _engine(tiny_model, "on", cfg=cfg, tracer=tracer)
    rng = np.random.default_rng(3)
    prompts = _hot_prompts(rng, prefix_len=12, tails=(3, 4))
    engine.serve(prompts)
    reg = engine.registry
    assert reg.gauge("kv_prefix_cached_blocks") == engine._prefix_index.cached_blocks
    assert reg.counter("kv_prefix_hits_total") == 1
    assert reg.counter("kv_prefix_misses_total") == 1
    assert reg.counter("kv_prefix_shared_tokens_total") > 0
    snap = reg.snapshot()
    published = (
        set(snap["counters"]) | set(snap["gauges"]) | set(snap["histograms"])
    )
    missing = sorted(
        n for n in published if n.startswith("kv_prefix_") and n not in HELP_TEXT
    )
    assert not missing, f"families without a direct HELP entry: {missing}"
    text = to_prometheus_text(reg)
    for name in published:
        if name.startswith("kv_prefix_"):
            assert f"# HELP {name} " in text, name
    hits = tracer.spans("serving.prefix_hit")
    assert len(hits) == 1
    attrs = hits[0].attrs
    assert attrs["shared_tokens"] > 0 and attrs["shared_blocks"] >= 1
    assert attrs["trace_id"] if "trace_id" in attrs else hits[0].trace_id
    assert engine.health()["prefix_cache"] == "on"
    assert engine.stats()["prefix_cache"]["hit_ratio"] == 0.5


def test_workload_shared_prefix_zipf_deterministic_end_to_end(tiny_model):
    """The loadgen satellite: WorkloadSpec's shared-prefix distribution is
    deterministic under a seed, Zipf-skews toward the hot prefix, and an
    offered-load drill through the shared paged engine records real hits
    (sharing exercised end to end, FakeClock-replayable)."""
    spec = WorkloadSpec(
        prompt_len=(3, 5), max_new_tokens=(2, 3), vocab=(1, 71),
        shared_prefix_pool=2, shared_prefix_len=(8, 8),
        shared_prefix_zipf=2.0,
    )
    a_spec = WorkloadSpec(**dataclasses.asdict(spec))
    rng_a = np.random.default_rng(5)
    a = [a_spec.sample_prompt(rng_a) for _ in range(6)]
    b_spec = WorkloadSpec(**dataclasses.asdict(spec))
    rng_b = np.random.default_rng(5)
    b = [b_spec.sample_prompt(rng_b) for _ in range(6)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    # prompts share one of two 8-token prefixes
    heads = {tuple(p[:8]) for p in a}
    assert len(heads) <= 2
    with pytest.raises(ValueError, match="shared_prefix_zipf"):
        WorkloadSpec(
            shared_prefix_pool=2, shared_prefix_zipf=1.0
        ).sample_prompt(np.random.default_rng(0))

    clock = FakeClock()
    engine = _engine(tiny_model, "on", cfg=_gcfg(max_new=3), clock=clock)
    gen = LoadGenerator(
        engine, workload=b_spec, mode="open", arrival="uniform",
        rate_rps=50.0, max_requests=6, rng=7, clock=clock,
    )
    report = gen.run()
    assert report["completed"] == 6
    assert engine.registry.counter("kv_prefix_hits_total") > 0
    assert engine._pool.leaked() == 0

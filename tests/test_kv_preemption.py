"""Optimistic KV admission with preemption (docs/serving.md "Preemption &
priorities"; ``serving/kv_pool.py`` ``reserve_lazy``, ``serving/slots.py``
preemption section).

The load-bearing assertions:

- **token identity through preempt/resume**: a preempted request is
  requeued and replayed from its original prompt, and the greedy token
  stream it finally delivers is identical to an unpressured engine's —
  across paged, paged_int8, prefix-shared, and chunked-prefill
  geometries, and identical to the DENSE layout / per-request
  ``generate()`` where the layout is exact;
- **lazy allocation as a unit**: ``reserve_lazy`` hard-commits only
  prompt pages + headroom, records the worst case as a soft watermark,
  and ``ensure`` on a lazy slot allocates decode pages at boundary
  crossings from the free heap — raising ``PoolExhausted`` (never
  partially mapping) when every free block is spoken for;
- **victim policy**: lowest priority tier first (never a higher tier),
  then most-tenant-pages / most-pages-held / fewest-tokens-generated;
  admission-time preemption crosses tiers only; the LAST resident is
  never preempted (forward progress);
- **zero leak under scripted exhaustion**: the ``kv.exhaust`` chaos site
  forces the PoolExhausted path deterministically — a preemption storm
  drains leak-free with every request still completing token-identical;
- **frees_by_cause completeness**: eos/max_new/deadline retire as
  ``retire``, plus ``cancelled`` / ``failover`` / ``scale_down`` /
  ``preempted`` — every retirement route lands in exactly one bucket and
  the pool balances to zero.

All pure-CPU, tiny shapes, fast — tier-1 (marker ``preemption``).
"""
import dataclasses

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
from perceiver_io_tpu.reliability import ChaosRegistry, FakeClock
from perceiver_io_tpu.serving import BucketTable, KVPagePool, SlotServingEngine
from perceiver_io_tpu.serving.kv_pool import PoolExhausted
from perceiver_io_tpu.serving.slots import PREEMPTION_MODES

pytestmark = [pytest.mark.preemption, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use (executor cache keys
# include the module fingerprint; an identically-configured model in
# another file would pre-populate the cache this file counts).
TINY = dict(
    vocab_size=71, max_seq_len=32, max_latents=8, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    return model, params


def _prompts(rng, lengths, vocab=71):
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32)
            for n in lengths]


def _ref(model, params, prompt, cfg):
    return np.asarray(
        generate(model, params, jnp.asarray(prompt[None, :]), cfg)
    )[0]


# -- the lazy allocator as a unit -------------------------------------------
def test_reserve_lazy_commits_prompt_plus_headroom():
    """Hard commitment = min(prompt - shared + headroom, worst case);
    the worst case becomes a soft watermark, not a reservation."""
    pool = KVPagePool(num_blocks=12, block_size=4, slots=3, max_len=32)
    committed = pool.reserve_lazy(0, 5, 24, headroom=1)  # 2 prompt + 1
    assert committed == 3
    assert pool.reserved == 3
    assert pool.is_lazy(0) and not pool.is_lazy(1)
    # headroom can never over-reserve past the worst case
    assert pool.reserve_lazy(1, 4, 6, headroom=5) == 2  # clamped to total
    # strict path untouched, and the two ledgers co-exist
    pool.reserve(2, 8)
    assert not pool.is_lazy(2)
    assert pool.reserved == 3 + 2 + 2
    assert pool.headroom_blocks == 12 - 7
    pool.release(0)
    pool.release(1)
    pool.release(2)
    assert pool.leaked() == 0 and not pool.is_lazy(0)


def test_reserve_lazy_raise_semantics():
    """Admit-time raises mirror reserve(): ValueError for structural
    bugs (double booking, bad ranges), PoolExhausted for doesn't-fit-now."""
    pool = KVPagePool(num_blocks=6, block_size=4, slots=2, max_len=32)
    pool.reserve_lazy(0, 4, 8)
    with pytest.raises(ValueError):
        pool.reserve_lazy(0, 4, 8)  # double booking
    with pytest.raises(ValueError):
        pool.reserve_lazy(1, 12, 8)  # prompt past total
    with pytest.raises(ValueError):
        pool.reserve_lazy(1, 4, 99)  # past one slot's page budget
    with pytest.raises(ValueError):
        pool.reserve_lazy(1, 4, 8, headroom=-1)
    # slot 0 hard-committed 1 block; 6 prompt blocks no longer fit
    with pytest.raises(PoolExhausted):
        pool.reserve_lazy(1, 24, 24)
    pool.release(0)
    assert pool.leaked() == 0 and pool.reserved == 0


def test_lazy_ensure_boundary_crossing_and_exhaustion():
    """Decode pages past the commitment come from the free heap — but
    never from blocks other slots' hard reservations have spoken for;
    a dry crossing raises with the table unchanged (no partial map)."""
    pool = KVPagePool(num_blocks=6, block_size=4, slots=3, max_len=32)
    pool.reserve_lazy(0, 4, 24)  # commit 1, soft watermark 6
    assert pool.ensure(0, 4)  # within the commitment
    assert pool.ensure(0, 12)  # 2 decode pages from the free heap
    # outstanding reservation fully consumed: reserved == mapped blocks
    assert pool.mapped_blocks(0) == 3 and pool.reserved == pool.in_use == 3
    pool.reserve(1, 9)  # 3 blocks hard: exactly the 3 free blocks left
    before = list(pool.table_row(0))
    with pytest.raises(PoolExhausted):
        pool.ensure(0, 16)  # the next crossing would eat a reservation
    assert list(pool.table_row(0)) == before  # unchanged on raise
    # a strict slot's ensure past ITS reservation stays a loud bug
    pool.ensure(1, 9)
    with pytest.raises(ValueError):
        pool.ensure(1, 13)
    # past the soft watermark = admission accounting bug, not pressure
    pool.release(1)
    with pytest.raises(ValueError):
        pool.ensure(0, 25)
    pool.release(0)
    assert pool.leaked() == 0
    assert pool.stats()["lazy_slots"] == 0


def test_ensure_many_multi_page_burst_and_determinism():
    """A speculative round's accepted burst maps every page it needs in
    ONE call, and the block-id sequence is identical to n single ensure()
    calls (same min-heap order) — the paged schedule stays deterministic
    whether tokens arrive one per step or k+1 per round."""
    pool = KVPagePool(num_blocks=12, block_size=4, slots=2, max_len=48)
    pool.reserve(0, 20)  # 5 blocks
    assert pool.ensure_many(0, 4) is True  # first page
    assert pool.ensure_many(0, 4) is False  # already mapped: no-op
    assert pool.ensure_many(0, 17) is True  # +9 tokens in one burst
    assert pool.mapped_blocks(0) == 5
    burst_row = list(pool.table_row(0))
    pool.release(0)
    pool.reserve(1, 20)
    for tokens in (4, 8, 12, 16, 17):
        pool.ensure(1, tokens)
    assert list(pool.table_row(1)) == burst_row
    pool.release(1)
    assert pool.leaked() == 0


def test_ensure_many_lazy_guard_mid_burst():
    """A lazy slot's burst spends headroom only for pages past its hard
    commitment: reservation-consuming pages never trip the guard, and a
    burst needing more unreserved blocks than remain raises PoolExhausted
    BEFORE mapping anything."""
    pool = KVPagePool(num_blocks=6, block_size=4, slots=3, max_len=32)
    pool.reserve_lazy(0, 4, 24)  # commit 1, soft watermark 6
    assert pool.ensure_many(0, 4)  # consumes the commitment
    pool.reserve(1, 12)  # 3 blocks hard -> headroom = 2
    assert pool.headroom_blocks == 2
    assert pool.ensure_many(0, 12)  # 2 lazy pages: exactly the headroom
    before = list(pool.table_row(0))
    with pytest.raises(PoolExhausted):
        pool.ensure_many(0, 16)  # one more lazy page than remains
    assert list(pool.table_row(0)) == before  # untouched on raise
    assert pool.mapped_blocks(0) == 3
    # past the soft watermark stays a loud structural bug, not pressure
    with pytest.raises(ValueError):
        pool.ensure_many(0, 25)
    pool.release(0)
    pool.release(1)
    assert pool.leaked() == 0 and pool.allocs_total == pool.frees_total


def test_ensure_many_exhaustion_leaves_table_untouched():
    """The atomicity bar ensure() can't give a burst: exhaustion MID-SPAN
    must not leave leading pages mapped. ensure_many pre-checks the whole
    span, so the retry-after-preempt loop never double-counts pages."""
    pool = KVPagePool(num_blocks=4, block_size=4, slots=2, max_len=32)
    pool.reserve_lazy(0, 4, 20, headroom=0)  # commit 1 of worst-case 5
    pool.ensure_many(0, 4)
    pool.reserve(1, 8)  # 2 blocks hard -> headroom = 1
    before = list(pool.table_row(0))
    in_use = pool.in_use
    with pytest.raises(PoolExhausted):
        pool.ensure_many(0, 16)  # needs 3 lazy pages, 1 unreserved free
    assert list(pool.table_row(0)) == before
    assert pool.in_use == in_use  # nothing mapped, nothing leaked
    # after the victim frees (release), the same burst succeeds
    pool.release(1)
    assert pool.ensure_many(0, 16)
    assert pool.mapped_blocks(0) == 4
    pool.release(0)
    assert pool.leaked() == 0


# -- ctor validation ---------------------------------------------------------
def test_preemption_requires_paged_layout(tiny_model):
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8,), batch_sizes=(1,))
    with pytest.raises(ValueError, match="preemption"):
        SlotServingEngine(model, params, cfg, table, slots=2,
                          preemption="bogus")
    with pytest.raises(ValueError, match="paged"):
        SlotServingEngine(model, params, cfg, table, slots=2,
                          kv_layout="dense", preemption="recompute")
    with pytest.raises(ValueError, match="admit_headroom_blocks"):
        SlotServingEngine(model, params, cfg, table, slots=2,
                          kv_layout="paged", preemption="recompute",
                          admit_headroom_blocks=-1)
    assert PREEMPTION_MODES == ("off", "recompute", "swap", "auto")


# -- token identity through preempt -> requeue -> readmit -> complete -------
def _pressured_engine(model, params, cfg, *, kv_layout="paged", slots=4,
                      kv_blocks=10, **kw):
    table = BucketTable(prompt_lens=(8,), batch_sizes=(1,))
    return SlotServingEngine(
        model, params, cfg, table, slots=slots, kv_layout=kv_layout,
        kv_block_size=4, kv_blocks=kv_blocks, preemption="recompute",
        clock=FakeClock(), **kw
    )


def _longtail(rng, n=6):
    """Mixed declared max_new: shorts + near-context longs — the strict
    arm's worst case would head-of-line block; lazy admission overcommits
    and preempts under pressure."""
    base = GenerationConfig(max_new_tokens=3, num_latents=2, sampling=GREEDY)
    long_cfg = dataclasses.replace(base, max_new_tokens=14)
    prompts = _prompts(rng, [5, 7, 6, 4, 7, 5][:n])
    cfgs = [long_cfg if i % 2 else base for i in range(n)]
    return prompts, cfgs


@pytest.mark.slow  # 2026-08 audit: ~18s; plain-paged preemption identity +
# zero-leak stay tier-1 via the kv.exhaust storm drill here and the
# speculative storm drill (tests/test_speculative.py)
def test_paged_preemption_token_identity_and_zero_leak(tiny_model):
    """Genuine exhaustion (no chaos): lazy admission packs more residents
    than the pool can grow, boundary crossings preempt victims, preempted
    requests requeue + readmit — every final output token-identical to
    per-request generate(), pool drained to zero."""
    model, params = tiny_model
    prompts, cfgs = _longtail(np.random.default_rng(3))
    engine = _pressured_engine(
        model, params, cfgs[0], kv_blocks=8, admit_headroom_blocks=0
    )
    handles = [engine.submit(p, config=c) for p, c in zip(prompts, cfgs)]
    engine.run_until_idle()
    pre = engine.stats()["preemption"]
    assert pre["mode"] == "recompute"
    assert pre["preemptions"] > 0
    assert pre["readmissions"] > 0
    assert pre["by_tier"].get(0, 0) == pre["preemptions"]
    for h, p, c in zip(handles, prompts, cfgs):
        assert h.status == "ok"
        np.testing.assert_array_equal(h.result, _ref(model, params, p, c))
    pool = engine._pool
    assert pool.in_use == 0 and pool.leaked() == 0
    assert pool.allocs_total == pool.frees_total > 0
    assert pool.frees_by_cause.get("preempted", 0) > 0
    assert engine.registry.counter("kv_preemptions_total") == \
        pre["preemptions"]
    assert engine.registry.counter("kv_preemptions_tier_0_total") == \
        pre["preemptions"]
    assert engine.health()["preemption"] == "recompute"


@pytest.mark.parametrize("geometry", ["chunked", "prefix", "int8"])
def test_preemption_token_identity_geometries(tiny_model, geometry):
    """Preempt/replay is invisible across the hard geometries: a
    chunked-prefill victim (preempted mid-admission restarts its chunks),
    a prefix-shared victim (derefs published blocks, never frees them out
    from under sharers), and the int8 pool (quantized decode replays
    bit-identically vs an UNPRESSURED int8 engine — the approximate
    layout is compared against itself, not the exact reference)."""
    model, params = tiny_model
    rng = np.random.default_rng(11)
    prompts, cfgs = _longtail(rng)
    kw = {}
    layout = "paged"
    if geometry == "chunked":
        kw["prefill_chunk"] = 4
    elif geometry == "prefix":
        kw["prefix_cache"] = "on"
        shared = prompts[0][:4]
        prompts = [np.concatenate([shared, p]).astype(np.int32)[:8]
                   for p in prompts]
    else:
        layout = "paged_int8"

    def run(kv_blocks, preemption):
        table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
        engine = SlotServingEngine(
            model, params, cfgs[0], table, slots=4, kv_layout=layout,
            kv_block_size=4, kv_blocks=kv_blocks, preemption=preemption,
            clock=FakeClock(), **kw
        )
        handles = [engine.submit(p, config=c) for p, c in zip(prompts, cfgs)]
        engine.run_until_idle()
        return engine, handles

    pressured, tight = run(8, "recompute")
    relaxed, ample = run(32, None)
    assert pressured.stats()["preemption"]["preemptions"] > 0
    for h_tight, h_ample in zip(tight, ample):
        assert h_tight.status == "ok" and h_ample.status == "ok"
        np.testing.assert_array_equal(h_tight.result, h_ample.result)
    assert pressured._pool.leaked() == 0
    if geometry != "prefix":
        # prefix geometry legitimately retains published cache blocks at
        # idle (referenced by the index, not leaked — test_prefix_cache's
        # retention convention); the others must drain to empty
        assert pressured._pool.in_use == 0
    assert pressured._pool.frees_by_cause.get("preempted", 0) > 0


# -- victim policy -----------------------------------------------------------
def test_priority_tiers_never_preempt_higher(tiny_model):
    """Batch-tier (priority 0) residents yield to an interactive
    (priority 1) submission; the interactive request is NEVER the victim,
    and per-tenant fairness picks the most-pages tenant first."""
    model, params = tiny_model
    base = GenerationConfig(max_new_tokens=12, num_latents=2, sampling=GREEDY)
    engine = _pressured_engine(model, params, base, kv_blocks=8)
    prompts = _prompts(np.random.default_rng(5), [6, 6, 6, 6])
    batch = [
        engine.submit(prompts[0], priority=0, tenant="batch-a"),
        engine.submit(prompts[1], priority=0, tenant="batch-a"),
        engine.submit(prompts[2], priority=0, tenant="batch-b"),
    ]
    interactive = engine.submit(prompts[3], priority=1, tenant="live")
    engine.run_until_idle()
    assert interactive.status == "ok" and interactive.preemptions == 0
    assert engine.stats()["preemption"]["preemptions"] > 0
    assert sum(r.preemptions for r in batch) == \
        engine.stats()["preemption"]["preemptions"]
    for h, p in zip(batch + [interactive], prompts):
        np.testing.assert_array_equal(
            h.result, _ref(model, params, p, base)
        )
    assert engine._pool.leaked() == 0
    by_tier = engine.stats()["preemption"]["by_tier"]
    assert set(by_tier) == {0}


def test_priority_orders_queue_admission(tiny_model):
    """The queue admits by tier (FIFO within a tier): a later high-tier
    submission starts before earlier low-tier ones."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=2, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8,), batch_sizes=(1,))
    engine = SlotServingEngine(
        model, params, cfg, table, slots=1, kv_layout="paged",
        kv_block_size=4, preemption="recompute", clock=FakeClock(),
    )
    prompts = _prompts(np.random.default_rng(9), [5, 5, 5])
    low1 = engine.submit(prompts[0], priority=0)
    low2 = engine.submit(prompts[1], priority=0)
    high = engine.submit(prompts[2], priority=5)
    order = []
    while engine.pending():
        engine.step()
        for h in (low1, low2, high):
            if h.done and h.request_id not in order:
                order.append(h.request_id)
    # the queue sorts by tier before the first admission, FIFO within it
    assert order == [high.request_id, low1.request_id, low2.request_id]


def test_last_resident_never_preempted(tiny_model):
    """Forward progress: with a single live request there is no victim,
    no self-yield, and the reclaim path reports the (structurally
    unreachable) stuck outcome instead of preempting the sole resident."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    engine = _pressured_engine(model, params, cfg, kv_blocks=10)
    h = engine.submit(_prompts(np.random.default_rng(2), [6])[0])
    engine.step()  # resident now
    entry = next(s for s in engine._slots if s is not None)
    assert engine._pick_victim(
        entry.req.priority, strict=False, exclude_slot=entry.slot
    ) is None
    assert engine._reclaim_decode_page(entry) == "stuck"
    assert engine._slots[entry.slot] is entry  # untouched
    engine.run_until_idle()
    assert h.status == "ok" and h.preemptions == 0
    assert engine.stats()["preemption"]["preemptions"] == 0


# -- scripted exhaustion (chaos kv.exhaust) ----------------------------------
def test_kv_exhaust_chaos_storm_zero_leak(tiny_model):
    """A scripted preemption storm (kv.exhaust on consecutive decode
    steps) forces the PoolExhausted path without real pressure: every
    request still completes token-identically and the pool drains to
    zero — the new chaos site's zero-leak bar."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)
    chaos = ChaosRegistry()
    chaos.exhaust_kv(2, count=4)  # steps 2-5 each force one exhaustion
    engine = _pressured_engine(
        model, params, cfg, kv_blocks=24, chaos=chaos
    )
    prompts = _prompts(np.random.default_rng(13), [5, 7, 6, 4])
    handles = [engine.submit(p) for p in prompts]
    engine.run_until_idle()
    pre = engine.stats()["preemption"]
    assert pre["preemptions"] >= 4
    assert pre["readmissions"] >= 1
    for h, p in zip(handles, prompts):
        assert h.status == "ok"
        np.testing.assert_array_equal(h.result, _ref(model, params, p, cfg))
    pool = engine._pool
    assert pool.in_use == 0 and pool.leaked() == 0
    assert pool.allocs_total == pool.frees_total
    assert pool.frees_by_cause.get("preempted", 0) >= 4
    assert chaos.fired_count("kv.exhaust") == 4


def test_kv_exhaust_off_engine_unaffected(tiny_model):
    """The chaos site is only consulted when preemption is enabled — a
    strict-reservation engine with the same schedule never trips it."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    chaos = ChaosRegistry()
    chaos.exhaust_kv(1, count=3)
    table = BucketTable(prompt_lens=(8,), batch_sizes=(1,))
    engine = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged",
        kv_block_size=4, chaos=chaos, clock=FakeClock(),
    )
    h = engine.submit(_prompts(np.random.default_rng(1), [6])[0])
    engine.run_until_idle()
    assert h.status == "ok"
    assert chaos.log == []


# -- frees_by_cause completeness ---------------------------------------------
def test_frees_by_cause_every_retirement_route(tiny_model):
    """Each retirement route frees its pages into exactly one bucket:
    eos, max_new and deadline land in ``retire``; cancel, executor
    failure, scale-down evacuation and preemption each tag their own
    cause — and the pool balances to zero after all of them."""
    model, params = tiny_model
    base = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8,), batch_sizes=(1,))
    clock = FakeClock()
    chaos = ChaosRegistry()
    engine = SlotServingEngine(
        model, params, base, table, slots=2, kv_layout="paged",
        kv_block_size=4, preemption="recompute", clock=clock, chaos=chaos,
    )
    pool = engine._pool
    rng = np.random.default_rng(17)
    prompt = _prompts(rng, [6])[0]

    def delta(action):
        before = dict(pool.frees_by_cause)
        action()
        while engine.pending():
            engine.step()
        after = pool.frees_by_cause
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in set(after) | set(before)
                if after.get(k, 0) != before.get(k, 0)}

    # max_new: ordinary completion
    d = delta(lambda: engine.submit(prompt))
    assert set(d) == {"retire"}
    # eos: the first greedily-emitted token doubles as the stop token.
    # The slot engine pins one sampling/eos plan per engine (only
    # max_new_tokens varies per request), so the eos route gets its own
    # engine built around that stop token.
    first = int(_ref(model, params, prompt, base)[0])
    eos_engine = SlotServingEngine(
        model, params, dataclasses.replace(base, eos_token_id=first),
        table, slots=2, kv_layout="paged", kv_block_size=4,
        preemption="recompute", clock=FakeClock(),
    )
    h = eos_engine.submit(prompt)
    while eos_engine.pending():
        eos_engine.step()
    # fixed-length result row: the stop token lands, the tail stays pad —
    # the request retired on eos, not max_new
    assert h.status == "ok" and int(h.result[0]) == first
    assert np.all(h.result[1:] == base.pad_token_id)
    assert set(eos_engine._pool.frees_by_cause) == {"retire"}
    assert eos_engine._pool.in_use == 0 and eos_engine._pool.leaked() == 0
    # deadline: resident expires mid-generation
    def deadline():
        engine.submit(prompt, deadline_s=1.0)
        engine.step()
        clock.advance(5.0)
    d = delta(deadline)
    assert set(d) == {"retire"}
    # cancelled: client disconnect on a resident
    def cancel():
        h = engine.submit(prompt)
        engine.step()
        engine.cancel(h.request_id)
    d = delta(cancel)
    assert set(d) == {"cancelled"}
    # failover: executor fault fails the resident (the next consulted
    # serving.batch dispatch — the site counter is engine-lifetime 1-based)
    def fail():
        chaos.fail_batch(chaos._counters.get("serving.batch", 0) + 1)
        engine.submit(prompt)
    d = delta(fail)
    assert set(d) == {"failover"}
    # scale_down: fleet evacuation
    def scale_down():
        engine.submit(prompt)
        engine.step()
        engine.evacuate("scale_down")
    d = delta(scale_down)
    assert set(d) == {"scale_down"}
    # preempted: a storm step forces a victim out (kv.exhaust keeps its
    # own 1-based consult counter)
    def preempt():
        chaos.exhaust_kv(chaos._counters.get("kv.exhaust", 0) + 1)
        for p in _prompts(rng, [5, 6]):
            engine.submit(p)
    d = delta(preempt)
    assert d.get("preempted", 0) > 0 and set(d) <= {"retire", "preempted"}
    assert pool.in_use == 0 and pool.leaked() == 0
    assert pool.allocs_total == pool.frees_total
    assert set(pool.frees_by_cause) == {
        "retire", "cancelled", "failover", "scale_down", "preempted"
    }


# -- observability surfaces --------------------------------------------------
def test_preemption_stats_gauges_and_report(tiny_model):
    """The stats()/gauge/report surfaces agree: headroom gauge tracks the
    pool, the report's kv section gains the preemption rollup, and
    HELP_TEXT documents the new families."""
    model, params = tiny_model
    prompts, cfgs = _longtail(np.random.default_rng(23))
    engine = _pressured_engine(model, params, cfgs[0], kv_blocks=8)
    for p, c in zip(prompts, cfgs):
        engine.submit(p, config=c)
    engine.run_until_idle()
    snap = engine.registry.snapshot()
    assert snap["gauges"]["kv_pool_headroom_blocks"] == \
        engine._pool.headroom_blocks
    pre = engine.stats()["preemption"]
    assert pre["headroom_blocks"] == engine._pool.headroom_blocks
    assert pre["admit_headroom_blocks"] == 0

    from perceiver_io_tpu.observability.exporters import HELP_TEXT
    from perceiver_io_tpu.observability.report import _kv_pool_section
    for name in ("kv_preemptions_total", "kv_readmissions_total",
                 "kv_pool_headroom_blocks"):
        assert name in HELP_TEXT
    section = _kv_pool_section(snap)
    assert section["preemption"]["preemptions"] == pre["preemptions"]
    assert section["preemption"]["readmissions"] == pre["readmissions"]

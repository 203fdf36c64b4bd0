"""Slot-engine tests: token-granular continuous batching over the
persistent multi-slot decode state (docs/serving.md, ``serving/slots.py``).

The load-bearing assertions:

- greedy decoding is **token-identical** to unbucketed per-request
  ``generate()`` — including requests admitted into recycled slots
  mid-generation and rows crossing the latent boundary at different times;
- EOS retires a slot immediately and the freed slot is refilled from the
  queue mid-generation;
- deadline expiry mid-generation ends the request in exactly one terminal
  ``serving.request`` span and frees the slot;
- compiles are bounded: one prefill executor per prompt bucket plus one
  decode executor plus its boundary variant, and mixed traffic after
  warmup retraces NOTHING.

All pure-CPU, tiny shapes, fast — tier-1.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    executor_cache_stats,
    generate,
    reset_executor_caches,
)
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.observability import Tracer
from perceiver_io_tpu.reliability import FakeClock
from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

pytestmark = pytest.mark.timeout(300)

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use: executor cache keys
# include the module fingerprint, and an identically-configured model in
# another file would pre-populate the cache this file counts.
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


def _ragged_prompts(rng, lengths, vocab=71):
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lengths]


def _ref(model, params, prompt, cfg):
    """Unbucketed per-request generate(): the parity oracle."""
    return np.asarray(generate(model, params, jnp.asarray(prompt[None, :]), cfg))[0]


# -- greedy token parity ---------------------------------------------------
def test_parity_mid_flight_admit_and_boundary_crossing(tiny_model):
    """5 ragged requests through 2 slots: requests 3-5 are admitted into
    recycled slots mid-generation, so their latent counts trail the resident
    row's — rows cross the latent boundary (m == max_latents) at different
    steps, exercising the per-row select in the boundary-variant executor.
    Every output must be token-identical to per-request generate()."""
    model, params = tiny_model
    # num_latents=2, max_latents=8, max_new=10: every request crosses the
    # boundary after 6 latent-growth steps (at a different absolute step per
    # admit time)
    cfg = GenerationConfig(max_new_tokens=10, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8, 16), batch_sizes=(1,)),
        slots=2,
    )
    # repeated lengths keep the per-request reference-executor compiles at 3
    # while still admitting 5 requests through 2 slots across both buckets
    prompts = _ragged_prompts(np.random.default_rng(0), [3, 11, 8, 3, 11])
    outs = engine.serve(prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))
    stats = engine.stats()
    assert stats["completed"] == 5 and stats["queued"] == 0
    assert stats["prefills"] == 5
    # 5 x 10 = 50 useful tokens over 2 slots: continuous refill keeps the
    # decode-call count well under the 5 generations a serial loop would run
    assert stats["decode_steps"] < 5 * 10
    assert 0.0 < stats["slot_occupancy"] <= 1.0


def test_parity_per_request_max_new_tokens_override(tiny_model):
    """Heterogeneous max_new_tokens share one decode executor (retirement is
    host-side), and each result still matches per-request generate()."""
    model, params = tiny_model
    # same slots/table/replaced-config as the mid-flight test: every slot
    # executor is already cached, so this test compiles references only
    base = GenerationConfig(max_new_tokens=9, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, base, BucketTable(prompt_lens=(8, 16), batch_sizes=(1,)),
        slots=2,
    )
    rng = np.random.default_rng(1)
    lens = [4, 7, 10]
    news = [3, 9, 2]
    prompts = _ragged_prompts(rng, lens)
    reqs = [
        engine.submit(p, config=dataclasses.replace(base, max_new_tokens=k))
        for p, k in zip(prompts, news)
    ]
    engine.run_until_idle()
    for req, p, k in zip(reqs, prompts, news):
        assert req.status == "ok" and req.result.shape == (k,)
        np.testing.assert_array_equal(
            req.result,
            _ref(model, params, p, dataclasses.replace(base, max_new_tokens=k)),
        )


def test_eos_retirement_frees_slot_for_queued_request(tiny_model):
    """When a row hits EOS its slot is retired immediately and refilled from
    the queue: with ONE slot, the second request's slot_assigned event comes
    after the first's slot_retired, both on slot 0, and both outputs still
    match per-request generate() (pad after EOS)."""
    model, params = tiny_model
    rng = np.random.default_rng(2)
    prompts = _ragged_prompts(rng, [6, 9])
    probe = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    # pick the token request 0 greedily emits at step 2 as EOS, so it
    # retires after 3 of 8 tokens — deterministically, with random weights
    eos = int(_ref(model, params, prompts[0], probe)[2])
    cfg = dataclasses.replace(probe, eos_token_id=eos)

    tracer = Tracer()
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(16,), batch_sizes=(1,)),
        slots=1, tracer=tracer,
    )
    outs = engine.serve(prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))
    assert eos in outs[0][:3]  # retired at or before step 3 of 8

    assigned = tracer.spans("serving.slot_assigned")
    retired = tracer.spans("serving.slot_retired")
    assert [s.attrs["slot"] for s in assigned] == [0, 0]
    assert [s.attrs["slot"] for s in retired] == [0, 0]
    assert retired[0].attrs["decode_steps"] <= 3  # EOS retired it early
    # request 1 entered the slot only after request 0 left it
    r0, a1 = retired[0], assigned[1]
    assert r0.trace_id != a1.trace_id
    assert a1.start_s >= r0.start_s
    # early retirement actually saved decode steps vs two full generations
    assert engine.stats()["decode_steps"] < 2 * cfg.max_new_tokens


def test_deadline_mid_generation_single_terminal_span(tiny_model):
    """A request whose deadline expires mid-generation ends in EXACTLY one
    terminal serving.request span (status timed_out), frees its slot, and
    the next queued request is admitted into it."""
    model, params = tiny_model
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(16,), batch_sizes=(1,)),
        slots=1, clock=clock, tracer=tracer,
    )
    rng = np.random.default_rng(3)
    doomed = engine.submit(_ragged_prompts(rng, [5])[0], deadline_s=5.0)
    survivor = engine.submit(_ragged_prompts(rng, [7])[0])
    engine.step()  # admits doomed, decodes token 1
    engine.step()  # token 2
    assert doomed.status == "queued" and len(engine._slots[0].emitted) == 2
    clock.advance(10.0)  # past the deadline, mid-generation
    engine.run_until_idle()

    assert doomed.status == "timed_out" and doomed.result is None
    assert "deadline exceeded after 2 of 6 tokens" in doomed.error
    assert survivor.status == "ok"
    np.testing.assert_array_equal(
        survivor.result, _ref(model, params, survivor.prompt, cfg)
    )
    terminal = tracer.spans("serving.request", trace_id=doomed.trace_id)
    assert len(terminal) == 1 and terminal[0].status == "timed_out"
    assert engine.stats()["timed_out"] == 1 and engine.stats()["completed"] == 1
    # the freed slot was recycled: two assignments, both slot 0
    assert [s.attrs["slot"] for s in tracer.spans("serving.slot_assigned")] == [0, 0]


# -- compile-count guarantee ----------------------------------------------
def test_compile_count_bounded_and_zero_retrace_after_warmup(tiny_model):
    """warmup() compiles exactly len(prompt_buckets) prefill executors + the
    decode executor + its boundary variant; mixed-length traffic with
    mid-flight admits and per-request max_new overrides then retraces
    NOTHING."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    reset_executor_caches()
    engine = SlotServingEngine(model, params, cfg, table, slots=2)
    compiled = engine.warmup()
    assert compiled == len(table.prompt_lens) + 2

    before = executor_cache_stats()["misses"]
    rng = np.random.default_rng(4)
    prompts = _ragged_prompts(rng, [3, 4, 5, 6, 7, 8, 9, 12, 16, 11])
    for i, p in enumerate(prompts):
        engine.submit(
            p, config=dataclasses.replace(cfg, max_new_tokens=2 + (i % 4))
        )
    engine.run_until_idle()
    assert executor_cache_stats()["misses"] == before  # zero retraces
    assert engine.stats()["completed"] == len(prompts)


# -- feasibility and rejection ---------------------------------------------
def test_submit_scope_rejections(tiny_model):
    """The slot engine's two scope restrictions reject with precise errors
    at submit (counted + terminal-spanned as 'rejected'); the bucket-grid
    and empty-prompt checks are inherited."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=30, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(16,), batch_sizes=(1,)),
        slots=1,
    )
    with pytest.raises(ValueError, match="sliding-window phase has no"):
        engine.submit(np.arange(1, 8, dtype=np.int32))  # 7 + 30 > 32
    short = GenerationConfig(max_new_tokens=4, num_latents=8, sampling=GREEDY)
    engine2 = SlotServingEngine(
        model, params, short, BucketTable(prompt_lens=(16,), batch_sizes=(1,)),
        slots=1,
    )
    with pytest.raises(ValueError, match="left pads would occupy latent"):
        engine2.submit(np.arange(1, 4, dtype=np.int32))  # 3 < num_latents 8
    with pytest.raises(ValueError, match="empty prompt"):
        engine2.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        engine2.submit(
            np.arange(1, 12, dtype=np.int32),
            config=dataclasses.replace(short, max_new_tokens=0),
        )
    assert engine.stats()["rejected"] == 1
    assert engine2.stats()["rejected"] == 3


def test_submit_rejects_incompatible_config(tiny_model):
    """Per-request configs may only override max_new_tokens — anything that
    would need a different compiled decode plan is rejected loudly."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(16,), batch_sizes=(1,)),
        slots=1,
    )
    other = dataclasses.replace(cfg, eos_token_id=7)
    with pytest.raises(ValueError, match="share the engine GenerationConfig"):
        engine.submit(np.arange(1, 6, dtype=np.int32), config=other)


# -- observability ---------------------------------------------------------
def test_slot_gauges_histograms_and_stats(tiny_model):
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=3, num_latents=2, sampling=GREEDY)
    engine = SlotServingEngine(
        model, params, cfg, BucketTable(prompt_lens=(8,), batch_sizes=(1,)),
        slots=4,
    )
    assert engine.registry.gauge("serving_slots_active") == 0
    assert engine.registry.gauge("serving_slots_idle") == 4
    engine.serve(_ragged_prompts(np.random.default_rng(5), [4, 5, 6]))
    assert engine.registry.gauge("serving_slots_active") == 0  # drained
    stats = engine.stats()
    assert stats["engine"] == "slots" and stats["slots"] == 4
    assert stats["decode_step_ms"]["p50"] is not None
    assert stats["decode_steps"] == 3  # 3 requests x 3 tokens, in lockstep
    assert stats["prefills"] == 3
    assert stats["slot_occupancy"] == 0.75  # 3 of 4 slots busy every step
    assert stats["decode_rows_padding_waste"] == 0.25
    assert engine.registry.histogram("serving_prefill_ms").count == 3
    health = engine.health()
    assert health["ready"] and health["slots"] == 4 and health["slots_active"] == 0


@pytest.mark.slow
def test_serve_cli_slots_engine(tmp_path):
    """`clm serve --serve.engine=slots` end to end, and parity with the
    bucket engine's output on the same prompts/checkpoint."""
    from perceiver_io_tpu.scripts.text import clm as clm_script
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=8, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    save_pretrained(str(tmp_path / "ckpt"), params, cfg)
    (tmp_path / "prompts.txt").write_text("hello\nhi\nwhat is up\n")

    common = [
        "serve", "--ckpt", str(tmp_path / "ckpt"),
        f"--serve.prompts={tmp_path}/prompts.txt",
        "--serve.max_new_tokens=3", "--serve.num_latents=2",
        "--serve.prompt_buckets=16", "--serve.warmup=false",
    ]
    slots = clm_script.main(common + ["--serve.engine=slots", "--serve.slots=2"])
    bucket = clm_script.main(common + ["--serve.engine=bucket"])
    assert [r["prompt"] for r in slots] == ["hello", "hi", "what is up"]
    assert all(r["status"] == "ok" for r in slots)
    assert [r["completion"] for r in slots] == [r["completion"] for r in bucket]
    with pytest.raises(SystemExit, match="bucket.*or.*slots"):
        clm_script.main(common + ["--serve.engine=nope"])

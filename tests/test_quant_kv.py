"""Quantized int8 KV pool (docs/serving.md "Quantized KV";
``ops/paged_attention.py`` quantize/scatter/gather, ``serving/slots.py``,
``inference/decode_strategy.py`` quality gate + autotune arm).

The load-bearing assertions:

- ``quantize_kv`` is a per-(position, head) symmetric int8 grid: the
  roundtrip error is bounded by half a grid step, and an all-zero row
  quantizes to ``(q=0, scale=0)`` whose dequant is exactly 0.0 — never a
  0/0 NaN (the null-block contract);
- the int8 engine is internally deterministic: chunked prefill and
  prefix sharing (COW copies bits + scales verbatim, never requantizes)
  are token-identical to the plain int8 engine on the same prompts;
- byte accounting follows the RESOLVED layout's dtype: int8 blocks are
  ``4d/(d+4)``x smaller than f32 plus an explicit per-block scale term
  (``kv_pool_block_scale_bytes``), in capacity, residency,
  ``check_feasible``'s never-fits reason, stats, and ``obs report``;
- ``paged_int8`` only wins ``kv_layout="auto"`` through the quality
  gate: ``quant_quality_probe`` measures the greedy logit delta against
  exact paged, ``autotune_kv_layout`` demotes a failed gate to exact
  layouts, serving warmup surfaces the demotion on
  ``kv_quant_fallback_total``, and the verdict round-trips the registry
  artifact (corrupt files degrade to re-measurement).

All pure-CPU, tiny shapes — tier-1 (marker ``quant_kv``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.inference import decode_strategy as strategy_mod
from perceiver_io_tpu.inference.generate import GenerationConfig
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.ops import paged_attention as paged_ops
from perceiver_io_tpu.serving import BucketTable, SlotServingEngine

pytestmark = [pytest.mark.quant_kv, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use (executor cache keys
# include the module fingerprint; an identically-configured model in
# another file would pre-populate the cache this file counts).
TINY = dict(
    vocab_size=61, max_seq_len=32, max_latents=8, num_channels=32,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    return model, params


def _ragged_prompts(rng, lengths, vocab=61):
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lengths]


# -- the quantizer as a unit ------------------------------------------------
def test_quantize_roundtrip_bound_and_zero_row():
    """Symmetric per-(position, head) int8: dequant error <= half a grid
    step everywhere; an all-zero row yields (q=0, scale=0) and dequants to
    exactly 0.0 (finite — the eps guard keeps the quantizing divide from
    ever producing NaN)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 2, 16)) * 3.0
    q, s = paged_ops.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (5, 2, 1)
    deq = np.asarray(q, np.float32) * np.asarray(s)
    err = np.abs(np.asarray(x, np.float32) - deq)
    assert np.all(err <= 0.5 * np.asarray(s) + 1e-6)
    # absmax element of every row hits the grid exactly (|q| = 127)
    assert np.all(np.max(np.abs(np.asarray(q)), axis=-1) == 127)

    qz, sz = paged_ops.quantize_kv(jnp.zeros((3, 2, 16)))
    assert np.all(np.asarray(qz) == 0) and np.all(np.asarray(sz) == 0.0)
    assert np.all(np.asarray(qz, np.float32) * np.asarray(sz) == 0.0)


def test_gather_kv_null_block_semantics():
    """Block 0 is the null/trash block in EVERY layout. Exact layout: a
    zero-initialized null block gathers to 0.0. Int8 layout: the null
    block's scale rows are zero, so even GARBAGE int8 bytes parked there
    dequantize to exactly 0.0 — finite, never a 0/0 NaN — while mapped
    blocks round-trip through scatter_kv/gather_kv within the grid
    bound."""
    bs, h, d = 4, 2, 16
    pool_tokens = 3 * bs  # null block + 2 real blocks
    rng = np.random.default_rng(5)
    vals = jnp.asarray(rng.normal(size=(bs, h, d)).astype(np.float32))

    # exact: scatter into block 1, gather block 0 (null) + block 1
    pool = jnp.zeros((pool_tokens, h, d), jnp.float32)
    flat = jnp.arange(bs, 2 * bs, dtype=jnp.int32)
    pool, none = paged_ops.scatter_kv(pool, None, flat, vals)
    assert none is None
    idx = jnp.concatenate([jnp.arange(bs), flat])[None, :]  # (1, 2*bs)
    g = np.asarray(paged_ops.gather_kv(pool, idx))  # (1, h, 2*bs, d)
    assert np.all(g[:, :, :bs] == 0.0)  # null block
    np.testing.assert_allclose(
        g[0, :, bs:], np.asarray(vals).transpose(1, 0, 2), rtol=0, atol=0
    )

    # int8: garbage bytes in the null block, zero scales kill them
    qpool = jnp.full((pool_tokens, h, d), 119, jnp.int8)  # garbage everywhere
    scale = jnp.zeros((pool_tokens, h, 1), jnp.float32)
    qpool, scale = paged_ops.scatter_kv(qpool, scale, flat, vals)
    gq = np.asarray(paged_ops.gather_kv(qpool, idx, scale, jnp.float32))
    assert np.all(np.isfinite(gq))
    assert np.all(gq[:, :, :bs] == 0.0)  # garbage * zero scale == exactly 0
    q, s = paged_ops.quantize_kv(vals)
    np.testing.assert_array_equal(
        gq[0, :, bs:],
        (np.asarray(q, np.float32) * np.asarray(s)).transpose(1, 0, 2),
    )


# -- engine determinism -----------------------------------------------------
@pytest.mark.slow  # 2026-08 audit: ~9s; int8 engine parity stays tier-1 via the
# preemption [int8] geometry and the speculative paged_int8 geometry drills
def test_int8_engine_internal_determinism(tiny_model):
    """Quantization happens ONCE at append, so every admission path must
    agree bit-for-bit: chunked prefill (staged rows quantized per chunk)
    and prefix sharing (COW copies int8 bits + scales verbatim) are
    token-identical to the plain int8 engine on the same prompts, through
    mid-flight admits and recycled slots."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    prompts = _ragged_prompts(np.random.default_rng(0), [3, 11, 8, 5])
    news = [6, 4, 6, 5]

    def serve(**extra):
        engine = SlotServingEngine(
            model, params, cfg, table, slots=2, kv_layout="paged_int8",
            kv_block_size=8, **extra,
        )
        reqs = [
            engine.submit(p, config=dataclasses.replace(cfg, max_new_tokens=k))
            for p, k in zip(prompts, news)
        ]
        engine.run_until_idle()
        return engine, [r.result for r in reqs]

    engine, plain = serve()
    assert engine.stats()["kv_layout"] == "paged_int8"
    assert engine.stats()["kv_pool"]["dtype"] == "int8"
    assert engine._pool.in_use == 0 and engine._pool.leaked() == 0
    _, chunked = serve(prefill_chunk=4)
    for a, b in zip(plain, chunked):
        np.testing.assert_array_equal(a, b)

    # prefix sharing: common 8-token prefix, ragged tails
    rng = np.random.default_rng(2)
    prefix = rng.integers(1, 61, size=8).astype(np.int32)
    shared_prompts = [
        np.concatenate([prefix, t])
        for t in _ragged_prompts(rng, [3, 5, 7])
    ]

    def serve_shared(pc):
        engine = SlotServingEngine(
            model, params, cfg, table, slots=2, kv_layout="paged_int8",
            kv_block_size=4, prefill_chunk=8, prefix_cache=pc,
        )
        return engine, engine.serve(shared_prompts)

    shared_engine, shared = serve_shared("on")
    assert shared_engine.registry.counter("kv_prefix_hits_total") > 0
    _, unshared = serve_shared("off")
    for a, b in zip(shared, unshared):
        np.testing.assert_array_equal(a, b)
    # published prefix blocks stay resident by design (the radix cache
    # holds a ref); nothing may leak beyond them
    assert shared_engine._pool.leaked() == 0


# -- byte accounting --------------------------------------------------------
def test_int8_byte_accounting_feasibility_and_report(tiny_model):
    """Capacity/residency follow the RESOLVED dtype: the int8 pool's block
    is 4d/(d+4)x smaller than f32 plus an explicit per-block scale term,
    check_feasible prices the never-fits reason in int8 bytes, stats and
    ``obs report`` name the layout, and the new metric families are
    HELP-documented on the Prometheus surface."""
    from perceiver_io_tpu.observability import report as report_mod
    from perceiver_io_tpu.observability.exporters import HELP_TEXT, to_prometheus_text

    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(16,), batch_sizes=(1,))

    def make(layout, **kw):
        return SlotServingEngine(
            model, params, cfg, table, slots=2, kv_layout=layout,
            kv_block_size=8, **kw,
        )

    int8 = make("paged_int8")
    exact = make("paged")
    h, d = 2, 16  # num_channels=32 over 2 heads
    assert int8._kv_token_bytes == 2 * h * d          # int8 k + v entries
    assert int8._kv_scale_token_bytes == 2 * h * 4    # f32 k + v scales
    assert exact._kv_token_bytes == 2 * h * d * 4 and \
        exact._kv_scale_token_bytes == 0
    reg = int8.registry
    assert reg.gauge("kv_pool_block_bytes") == 8 * int8._kv_token_bytes
    assert reg.gauge("kv_pool_block_scale_bytes") == 8 * int8._kv_scale_token_bytes
    assert exact.registry.gauge("kv_pool_block_scale_bytes") == 0
    # capacity = pool blocks at the resolved per-position cost + stack floor
    floor = reg.gauge("kv_cache_resident_bytes")
    assert reg.gauge("kv_cache_capacity_bytes") == floor + \
        int8._pool.num_blocks * 8 * (int8._kv_token_bytes + int8._kv_scale_token_bytes)
    # same geometry, ~4x cheaper blocks: strictly below the exact capacity
    assert reg.gauge("kv_cache_capacity_bytes") < \
        exact.registry.gauge("kv_cache_capacity_bytes")

    # residency prices live pages in int8+scale bytes
    req = int8.submit(np.arange(1, 10, dtype=np.int32))
    int8.step()
    live = int8._pool.in_use
    assert live > 0
    assert reg.gauge("kv_cache_resident_bytes") == floor + \
        live * 8 * (int8._kv_token_bytes + int8._kv_scale_token_bytes)
    int8.run_until_idle()
    assert req.status == "ok"

    # never-fits reason is priced at the int8 layout's bytes
    small = SlotServingEngine(
        model, params, cfg, table, slots=4, kv_layout="paged_int8",
        kv_block_size=8, kv_blocks=2,
    )
    with pytest.raises(ValueError, match="can never be admitted") as ei:
        small.submit(np.arange(1, 14, dtype=np.int32))
    msg = str(ei.value)
    per_block = 8 * (small._kv_token_bytes + small._kv_scale_token_bytes)
    assert "paged_int8" in msg and f"{2 * per_block} bytes" in msg

    # stats + obs report + Prometheus surface
    pool_stats = int8.stats()["kv_pool"]
    assert pool_stats["layout"] == "paged_int8"
    assert pool_stats["dtype"] == "int8"
    assert pool_stats["block_scale_bytes"] == 8 * int8._kv_scale_token_bytes
    analysis = report_mod.analyze([], reg.snapshot())
    kv = analysis["kv_pool"]
    assert kv["block_scale_bytes"] == 8 * int8._kv_scale_token_bytes
    rendered = report_mod.format_report(analysis)
    assert "layout: paged_int8" in rendered and "scale" in rendered
    text = to_prometheus_text(reg)
    for name in (
        "kv_pool_block_scale_bytes",
        "kv_quant_fallback_total",
        "kv_ragged_kernel_steps_total",
        "kv_ragged_kernel_enabled",
    ):
        assert name in HELP_TEXT, name
        assert f"# HELP {name}" in text, name
    # the CompileLedger attributes the two paged layouts distinctly
    assert int8._ledger_components()["kv_layout"].startswith("paged_int8:")
    assert exact._ledger_components()["kv_layout"].startswith("paged:")


# -- quality gate + autotune ------------------------------------------------
def test_quality_gate_autotune_and_persistence(tiny_model, tmp_path, monkeypatch):
    """The int8 arm only wins ``auto`` through the quality gate: the probe
    measures the greedy logit delta against exact paged, a scripted clock
    that ranks int8 fastest yields a ``paged_int8`` verdict carrying the
    gate verdict, a zero budget demotes it to exact ``paged`` at the SAME
    timings, and the verdict round-trips the registry artifact (corrupt
    files degrade to 0 entries loaded)."""
    model, params = tiny_model
    strategy_mod.reset_registry()
    try:
        assert strategy_mod.kv_quant_budget() == strategy_mod.DEFAULT_KV_QUANT_BUDGET
        monkeypatch.setenv(strategy_mod.ENV_KV_QUANT_BUDGET, "0.25")
        assert strategy_mod.kv_quant_budget() == 0.25
        monkeypatch.delenv(strategy_mod.ENV_KV_QUANT_BUDGET)

        probe = strategy_mod.quant_quality_probe(model, params, new_tokens=4)
        assert set(probe) == {"max_logit_delta", "token_match_rate", "budget", "passed"}
        assert probe["budget"] == strategy_mod.DEFAULT_KV_QUANT_BUDGET
        assert 0.0 < probe["max_logit_delta"] <= probe["budget"]
        assert probe["passed"] is True
        assert 0.0 < probe["token_match_rate"] <= 1.0
        # an impossible budget fails the same measurement
        assert strategy_mod.quant_quality_probe(
            model, params, new_tokens=4, budget=0.0
        )["passed"] is False

        # scripted clock: dense 10ms, paged 5ms, int8 1ms per pass -> the
        # gate (passing, above) lets the fastest arm win
        ticks = iter([0.0, 10.0, 0.0, 5.0, 0.0, 1.0])
        verdict = strategy_mod.autotune_kv_layout(
            model, params, clock=lambda: next(ticks), new_tokens=4,
        )
        assert verdict == "paged_int8"
        entry = strategy_mod.kv_entry(model)
        assert entry["kv_layout"] == "paged_int8"
        assert entry["quant_gate"]["passed"] is True
        assert entry["paged_int8_ms_per_token"] < entry["paged_ms_per_token"]
        assert strategy_mod.resolve_kv_layout(None, model) == "paged_int8"
        # memoized: no clock ticks left, yet the verdict returns
        assert strategy_mod.autotune_kv_layout(model, params) == "paged_int8"

        # persistence: the int8 verdict + gate round-trip the artifact
        path = str(tmp_path / "strategy.json")
        strategy_mod.save_registry(path)
        strategy_mod.reset_registry()
        assert strategy_mod.load_registry(path) == 1
        assert strategy_mod.lookup_kv_layout(model) == "paged_int8"
        assert strategy_mod.kv_entry(model)["quant_gate"]["passed"] is True
        corrupt = tmp_path / "corrupt.json"
        corrupt.write_text("{not json")
        assert strategy_mod.load_registry(str(corrupt)) == 0

        # zero budget: same scripted timings, failed gate -> exact paged
        strategy_mod.reset_registry()
        monkeypatch.setenv(strategy_mod.ENV_KV_QUANT_BUDGET, "0")
        ticks = iter([0.0, 10.0, 0.0, 5.0, 0.0, 1.0])
        verdict = strategy_mod.autotune_kv_layout(
            model, params, clock=lambda: next(ticks), new_tokens=4,
        )
        assert verdict == "paged"
        gate = strategy_mod.kv_entry(model)["quant_gate"]
        assert gate["passed"] is False and gate["budget"] == 0.0

        # env/explicit resolution accepts the new layout name
        monkeypatch.setenv(strategy_mod.ENV_KV_LAYOUT, "paged_int8")
        assert strategy_mod.resolve_kv_layout(None, model) == "paged_int8"
        monkeypatch.delenv(strategy_mod.ENV_KV_LAYOUT)
        assert strategy_mod.resolve_kv_layout("paged_int8", model) == "paged_int8"
    finally:
        strategy_mod.reset_registry()


@pytest.mark.slow  # 2026-08 audit: ~11s; the gate logic itself is pinned by
# the quality-gate autotune test, still in the `-m quant_kv` lane
def test_engine_warmup_quant_fallback_counter(tiny_model, monkeypatch):
    """Serving warmup under ``kv_layout="auto"`` with an impossible
    quality budget: the autotuner's gate fails, the engine does NOT land
    on paged_int8, and the demotion is surfaced on
    ``kv_quant_fallback_total`` (stats mirror) for fleet rollouts to
    alarm on."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=4, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(16,), batch_sizes=(1,))
    strategy_mod.reset_registry()
    monkeypatch.setenv(strategy_mod.ENV_KV_QUANT_BUDGET, "0")
    try:
        engine = SlotServingEngine(
            model, params, cfg, table, slots=2, kv_layout="auto",
        )
        engine.warmup()
        assert engine.kv_layout in ("dense", "paged")
        assert engine.registry.counter("kv_quant_fallback_total") == 1
        assert engine.stats()["kv_layout"] != "paged_int8"
        gate = strategy_mod.kv_entry(model)["quant_gate"]
        assert gate["passed"] is False
        if engine._pool is not None:
            assert engine.stats()["kv_pool"]["quant_fallbacks"] == 1
    finally:
        strategy_mod.reset_registry()

"""One ragged paged-attention kernel for mixed prefill/decode rows
(``ops/ragged_attention.py``; docs/serving.md "Ragged kernel"; opt-in via
``PERCEIVER_RAGGED_KERNEL=1``, interpreter-mode Pallas on CPU so the
tier-1 suite executes the real kernel body).

The load-bearing assertions:

- ONE launch handles ragged rows — multi-page spans, single-page spans
  and idle (length 0) rows together — for BOTH row shapes (``q_len = 1``
  decode, ``q_len = max_latents`` window) and BOTH pool layouts (f32,
  int8 + scales), matching a dense softmax reference over each row's
  live span while garbage beyond the span (and in the null block)
  contributes nothing;
- the serving engine under the flag is greedy token-identical to the
  gather reference (and therefore to dense and per-request generate())
  across mid-flight admits, boundary crossings, chunked prefill, prefix
  sharing, recycled slots, and the 2x2 data x model mesh;
- the compile bound is UNCHANGED (``len(prompt_buckets) + 2``) — no
  per-phase kernel variants — and steady-state traffic neither retraces
  executors nor re-traces the kernel (``TRACE_COUNT``);
- the flag folds into ``ragged_attention.trace_env`` (a mid-process toggle
  rebuilds, never silently reuses) and dispatch is observable
  (``kv_ragged_kernel_steps_total`` / ``kv_ragged_kernel_enabled``).

All pure-CPU, tiny shapes — tier-1 (marker ``quant_kv``).
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
from perceiver_io_tpu.ops import paged_attention as paged_ops
from perceiver_io_tpu.ops import ragged_attention as ragged_mod
from perceiver_io_tpu.serving import BucketTable, ServingMeshSpec, SlotServingEngine

pytestmark = [pytest.mark.quant_kv, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape other test modules use (executor cache keys
# include the module fingerprint; an identically-configured model in
# another file would pre-populate the cache this file counts). The env
# flag is itself part of the fingerprint, so this module's kernel-on
# executors never collide with any flag-off module regardless.
TINY = dict(
    vocab_size=71, max_seq_len=32, max_latents=8, num_channels=32,
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
    return np.asarray(generate(model, params, jnp.asarray(prompt[None, :]), cfg))[0]


def _dense_reference(q, k_dense, v_dense, lengths):
    """Direct masked softmax over each row's live span with the
    Perceiver-AR right-aligned causal bound (query ``i`` sits at position
    ``L - q_len + i`` and sees only positions up to its own — the dense
    attend's ``j <= i + (j_len - i_len)`` mask) — the oracle the
    online-softmax kernel must match. Idle rows (length 0) -> zeros;
    fully-masked queries (bound < 1, only possible for the pad rows the
    engine discards) -> zeros, matching the kernel's ``l == 0`` epilogue."""
    b, h, q_len, d = q.shape
    out = np.zeros((b, h, q_len, d), np.float32)
    for r in range(b):
        L = int(lengths[r])
        if L <= 0:
            continue
        for i in range(q_len):
            hi = min(L, L - q_len + i + 1)
            if hi <= 0:
                continue
            s = np.einsum("hd,hkd->hk", q[r, :, i], k_dense[r][:, :hi])
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            p /= p.sum(axis=-1, keepdims=True)
            out[r, :, i] = np.einsum("hk,hkd->hd", p, v_dense[r][:, :hi])
    return out


# -- the kernel as a unit ---------------------------------------------------
@pytest.mark.parametrize("q_len", [1, 4], ids=["decode_row", "window_row"])
def test_kernel_ragged_rows_one_launch(q_len):
    """One launch over rows with lengths (6, 16, 0) — a partial span whose
    tail pages are unmapped (null block), a full multi-page span, and an
    idle row — matches the dense softmax oracle per row; garbage parked in
    the null block and beyond each span contributes nothing; the idle row
    emits finite zeros. Same pin for the int8 pool (dequant inside the
    kernel, zero scales killing the null block's garbage bytes)."""
    h, d, bs, pages = 2, 8, 4, 4
    pool_tokens = 7 * bs  # null block + 6 mappable blocks
    rng = np.random.default_rng(9)
    pool_k = rng.normal(size=(pool_tokens, h, d)).astype(np.float32)
    pool_v = rng.normal(size=(pool_tokens, h, d)).astype(np.float32)
    pool_k[:bs] = 1e3  # garbage in the null block: must never surface
    pool_v[:bs] = -1e3
    table = np.array([[1, 2, 0, 0], [3, 4, 5, 6], [0, 0, 0, 0]], np.int32)
    lengths = np.array([6, 16, 0], np.int32)
    q = rng.normal(size=(3, h, q_len, d)).astype(np.float32)

    # dense per-row views via the gather reference (the bitwise oracle)
    flat = paged_ops.flat_position_indices(jnp.asarray(table), bs, pages * bs)
    k_dense = np.asarray(paged_ops.gather_kv(jnp.asarray(pool_k), flat))
    v_dense = np.asarray(paged_ops.gather_kv(jnp.asarray(pool_v), flat))
    want = _dense_reference(q, k_dense, v_dense, lengths)

    before = ragged_mod.TRACE_COUNT
    got = np.asarray(ragged_mod.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(table), jnp.asarray(lengths), block_size=bs,
    ))
    assert ragged_mod.TRACE_COUNT == before + 1  # one launch, traced once
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.all(got[2] == 0.0)  # idle row

    # int8 pool: quantize per position, garbage bytes + zero scales in the
    # null block; the kernel dequantizes on the one page it processes
    qk, sk = paged_ops.quantize_kv(jnp.asarray(pool_k))
    qv, sv = paged_ops.quantize_kv(jnp.asarray(pool_v))
    qk = qk.at[:bs].set(119)   # garbage int8 bytes ...
    qv = qv.at[:bs].set(-77)
    sk = sk.at[:bs].set(0.0)   # ... killed by the null block's zero scale
    sv = sv.at[:bs].set(0.0)
    k8 = np.asarray(paged_ops.gather_kv(qk, flat, sk, jnp.float32))
    v8 = np.asarray(paged_ops.gather_kv(qv, flat, sv, jnp.float32))
    want8 = _dense_reference(q, k8, v8, lengths)
    got8 = np.asarray(ragged_mod.ragged_paged_attention(
        jnp.asarray(q), qk, qv, jnp.asarray(table), jnp.asarray(lengths),
        block_size=bs, scale_k=sk, scale_v=sv,
    ))
    assert np.all(np.isfinite(got8))
    np.testing.assert_allclose(got8, want8, rtol=1e-5, atol=1e-5)
    assert np.all(got8[2] == 0.0)

    with pytest.raises(ValueError, match="multiple of block_size"):
        ragged_mod.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(pool_k[:-1]), jnp.asarray(pool_v[:-1]),
            jnp.asarray(table), jnp.asarray(lengths), block_size=bs,
        )


def test_flag_normalization_and_fingerprint(monkeypatch):
    """The opt-in flag is trace-time state: it folds into
    ``ragged_attention.trace_env`` so executor caches rebuild on a
    mid-process toggle instead of silently serving the other program."""
    monkeypatch.delenv(ragged_mod.ENV_KERNEL, raising=False)
    assert not ragged_mod.kernel_requested()
    off = ragged_mod.trace_env()
    monkeypatch.setenv(ragged_mod.ENV_KERNEL, "1")
    assert ragged_mod.kernel_requested()
    on = ragged_mod.trace_env()
    assert on == (True,) and off == (False,)
    monkeypatch.setenv(ragged_mod.ENV_KERNEL, "0")  # explicit off == unset
    assert not ragged_mod.kernel_requested()
    assert ragged_mod.trace_env() == off


def test_generation_executor_cache_keys_on_the_flag(tiny_model, monkeypatch):
    """A mid-process flip of the flag rebuilds the generation executor (the
    flag is part of its cache key) and flipping back HITS the first one:
    never a program traced under the other setting, never a third build."""
    model, params = tiny_model
    ids = jnp.asarray(np.random.default_rng(2).integers(1, 71, (1, 6)), jnp.int32)
    cfg = GenerationConfig(max_new_tokens=3, num_latents=2, sampling=GREEDY)

    monkeypatch.delenv(ragged_mod.ENV_KERNEL, raising=False)
    out0 = np.asarray(generate(model, params, ids, cfg))
    before = executor_cache_stats()
    monkeypatch.setenv(ragged_mod.ENV_KERNEL, "1")
    out1 = np.asarray(generate(model, params, ids, cfg))
    mid = executor_cache_stats()
    assert mid["misses"] - before["misses"] == 1  # fresh executor, not reuse
    monkeypatch.setenv(ragged_mod.ENV_KERNEL, "0")
    out2 = np.asarray(generate(model, params, ids, cfg))
    after = executor_cache_stats()
    assert after["misses"] == mid["misses"] and after["hits"] - mid["hits"] == 1
    # generate() holds a dense cache, which the kernel never reads
    np.testing.assert_array_equal(out0, out1)
    np.testing.assert_array_equal(out0, out2)


# -- engine parity under the flag -------------------------------------------
# 2026-08 runtime audit: the two engine-level parity drills below are
# slow depth (~31s combined, four engine builds each) — they re-prove at
# generate() level what the one-launch kernel-vs-dense-oracle tests above
# pin directly, and the kernel is opt-in (gather stays the bitwise
# oracle on every default path).
@pytest.mark.slow
def test_engine_parity_kernel_vs_gather_and_dense(tiny_model, monkeypatch):
    """4 ragged requests through 2 paged slots under the flag — mid-flight
    admits into recycled slots, boundary crossings at different steps,
    heterogeneous max_new — greedy token-identical to the flag-off gather
    engine AND to per-request generate(); dispatch lands on the
    ``kv_ragged_kernel_*`` observability surface. The same pin for the
    int8 pool (dequant inside the kernel)."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    prompts = _ragged_prompts(np.random.default_rng(0), [3, 11, 8, 5])
    news = [6, 4, 6, 5]

    def serve(layout, kernel):
        monkeypatch.setenv(ragged_mod.ENV_KERNEL, "1" if kernel else "0")
        engine = SlotServingEngine(
            model, params, cfg, table, slots=2, kv_layout=layout,
            kv_block_size=8,
        )
        reqs = [
            engine.submit(p, config=dataclasses.replace(cfg, max_new_tokens=k))
            for p, k in zip(prompts, news)
        ]
        engine.run_until_idle()
        return engine, [r.result for r in reqs]

    engine, kernel_outs = serve("paged", kernel=True)
    assert engine.registry.gauge("kv_ragged_kernel_enabled") == 1
    assert engine.registry.counter("kv_ragged_kernel_steps_total") > 0
    assert engine._pool.in_use == 0 and engine._pool.leaked() == 0
    _, gather_outs = serve("paged", kernel=False)
    for p, k, a, b in zip(prompts, news, kernel_outs, gather_outs):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            a, _ref(model, params, p, dataclasses.replace(cfg, max_new_tokens=k))
        )

    _, int8_kernel = serve("paged_int8", kernel=True)
    _, int8_gather = serve("paged_int8", kernel=False)
    for a, b in zip(int8_kernel, int8_gather):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_engine_parity_chunked_and_prefix_shared(tiny_model, monkeypatch):
    """Chunked prefill and prefix sharing under the flag: the window-phase
    rows (q_len = max_latents over the staged span) run the SAME kernel as
    decode rows and stay token-identical to per-request generate() / the
    flag-off sharing engine."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=5, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 24), batch_sizes=(1,))
    monkeypatch.setenv(ragged_mod.ENV_KERNEL, "1")
    prompts = _ragged_prompts(np.random.default_rng(1), [22, 5])
    engine = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged",
        kv_block_size=4, prefill_chunk=4,
    )
    outs = engine.serve(prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(model, params, p, cfg))
    assert engine.stats()["prefill_chunks"] > 0
    assert engine.registry.counter("kv_ragged_kernel_steps_total") > 0

    rng = np.random.default_rng(2)
    prefix = rng.integers(1, 71, size=8).astype(np.int32)
    shared_prompts = [
        np.concatenate([prefix, t]) for t in _ragged_prompts(rng, [3, 7])
    ]

    def serve_shared(kernel):
        monkeypatch.setenv(ragged_mod.ENV_KERNEL, "1" if kernel else "0")
        engine = SlotServingEngine(
            model, params, cfg, table, slots=2, kv_layout="paged",
            kv_block_size=4, prefill_chunk=8, prefix_cache="on",
        )
        return engine, engine.serve(shared_prompts)

    shared_engine, kernel_outs = serve_shared(True)
    assert shared_engine.registry.counter("kv_prefix_hits_total") > 0
    _, gather_outs = serve_shared(False)
    for a, b in zip(kernel_outs, gather_outs):
        np.testing.assert_array_equal(a, b)


def test_engine_parity_sharded_mesh(tiny_model, monkeypatch):
    """The kernel on the 2x2 data x model mesh (rows sharded along data,
    heads along model via shard_map, pages replicated) is token-identical
    to the unsharded kernel engine — the sharded slot engine can flip the
    flag without touching its mesh plumbing."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=6, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    monkeypatch.setenv(ragged_mod.ENV_KERNEL, "1")
    prompts = _ragged_prompts(np.random.default_rng(3), [3, 11, 8, 5])

    ref = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged", kv_block_size=8,
    )
    outs_ref = ref.serve(prompts)
    eng = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged", kv_block_size=8,
        mesh=ServingMeshSpec(data=2, model=2),
    )
    outs = eng.serve(prompts)
    for a, b in zip(outs_ref, outs):
        np.testing.assert_array_equal(a, b)
    assert eng.registry.gauge("serving_mesh_devices") == 4
    assert eng.registry.counter("kv_ragged_kernel_steps_total") > 0
    assert eng._pool.in_use == 0 and eng._pool.leaked() == 0


# -- compile-count guarantee ------------------------------------------------
def test_kernel_compile_bound_and_zero_retrace(tiny_model, monkeypatch):
    """The one-kernel design keeps the dense compile bound:
    len(prompt_buckets) prefills + decode + boundary variant, nothing
    extra for the kernel. Steady-state mixed traffic afterwards retraces
    neither executors nor the kernel itself (TRACE_COUNT is a trace-time
    probe: block tables and lengths are traced ARGUMENTS, never cache
    keys)."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=8, num_latents=2, sampling=GREEDY)
    table = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))
    monkeypatch.setenv(ragged_mod.ENV_KERNEL, "1")
    reset_executor_caches()
    engine = SlotServingEngine(
        model, params, cfg, table, slots=2, kv_layout="paged", kv_block_size=8,
    )
    assert engine.warmup() == len(table.prompt_lens) + 2
    assert ragged_mod.TRACE_COUNT > 0  # warmup traced the kernel

    misses = executor_cache_stats()["misses"]
    traces = ragged_mod.TRACE_COUNT
    rng = np.random.default_rng(4)
    for i, p in enumerate(_ragged_prompts(rng, [3, 8, 12, 16, 5])):
        engine.submit(p, config=dataclasses.replace(cfg, max_new_tokens=2 + (i % 4)))
    engine.run_until_idle()
    assert executor_cache_stats()["misses"] == misses  # zero retraces
    assert ragged_mod.TRACE_COUNT == traces  # zero kernel re-traces
    assert engine.stats()["completed"] == 5
    assert engine.registry.counter("kv_ragged_kernel_steps_total") > 0

"""Token-granular continuous batching: the persistent-slot decode engine.

The bucket engine (``serving/engine.py``) schedules at *generation*
granularity: a micro-batch is packed, a whole compiled ``generate()`` runs
to completion, and only then can queued requests join — a newly arrived
prompt waits a full batch of decoding, and a row that hits EOS early burns
its slot until the slowest row finishes. Both "Ragged Paged Attention"
(TPU serving kernels over ragged in-flight batches) and the compiler-first
O(1)-caching paper (PAPERS.md) land on the same fix: keep a **fixed-shape
resident decode state** and make scheduling **per token**.

This module is that engine. Serving splits into two compiled phases:

- **Prefill** — one executor per *prompt bucket* ``L``: right-align the
  prompt into the full decode window, run
  :func:`~perceiver_io_tpu.inference.generate._decode_prefill` at batch 1,
  and ``dynamic_update_slice`` the resulting KV caches + row state into
  slot ``s`` of the persistent multi-slot state. ``s`` is a traced scalar,
  so admitting into any slot reuses one program.
- **Decode** — exactly ONE fixed-shape executor advances all ``S`` slots by
  one token per call, using the per-row ``length``/``m`` vectors
  (:func:`~perceiver_io_tpu.inference.generate._slot_decode_step`) for
  ragged masking. No bucket grid on the decode path, no retracing as
  traffic mixes. When any active slot has filled its latent segment
  (``m == max_latents``), the engine switches to the **boundary variant**:
  a second executor that computes both the latent-growth step and the
  boundary-migration step (:func:`..generate._decode_step_boundary`) and
  selects per row — correct for mixed phases at ~2x step cost, used only
  while a boundary-phase row is resident.

``step()`` is a token-level scheduler: it retires slots immediately on
EOS / ``max_new_tokens`` / deadline expiry, refills freed slots
mid-generation by prefilling the next queued request into them, and keeps
the per-request trace alive across the slot lifecycle
(``serving.slot_assigned`` / ``serving.slot_retired`` events on the
request's trace; docs/observability.md).

**Chunked prefill** (``prefill_chunk=C``): a long-prompt admission is the
one remaining head-of-line stall — the full-window prefill runs between
two decode steps, so every resident slot's inter-token latency spikes by
the whole prompt's cost. With chunking, the prefix cross-k/v cache is
built ``C`` token positions at a time in a batch-1 *staging* buffer by ONE
bucket-independent chunk executor (traced offset/slot/m; a final *pure
finalize* call — the other ``lax.cond`` branch of the same program — runs
the latent attend + stack and inserts the finished row), one call per
:meth:`SlotServingEngine.step` interleaved with the resident decode steps
— the "Ragged Paged Attention" admission pattern (PAPERS.md). The
persistent state never holds a half-built row, so decode steps between
chunks stay oblivious.

**Prefix sharing** (``prefix_cache="on"``, paged layout only;
docs/serving.md "Prefix sharing"): a radix index over published full
prompt-prefix blocks lets an admission whose leading token ids match map
those pool blocks BY REFERENCE (per-block refcounts), copy-on-write at
the first divergent or partially-usable block, and prefill only the
un-shared suffix through the ``start_position``-taking shared executor —
a fully-hot system prompt admits with zero staged chunks, so TTFT
collapses to block-table writes plus the latent finalize. A shared page
is never written through (write routing + the COW guard), frees are
refcount-aware (a block returns to the pool on its LAST deref), and
unreferenced cached prefixes LRU-drop under pool pressure before any
admission is made to wait. Greedy output stays token-identical to the
unshared path (pinned by ``tests/test_prefix_cache.py``).

**Decode strategy** (``decode_strategy=...`` /
``PERCEIVER_DECODE_STRATEGY``): the boundary decode variant's
implementation — cached migration step vs full windowed recompute — is a
measured platform/shape choice (``inference/decode_strategy.py``; the
cached step loses to recompute on a CPU; on the chip: not measured). Both
are exact, so greedy output stays token-identical either way; ``"auto"``
uses the autotuner's memoized verdict (``warmup()`` measures it once when
asked explicitly).

**Speculative decoding** (``speculation="k<K>d<D>"`` /
``PERCEIVER_SPECULATION``; docs/serving.md "Speculative decoding"): a
self-draft proposer (the model's own first ``D`` self-attention layers,
``inference/speculative.py``) drafts ``K`` tokens per round and ONE
fixed-shape lane-batched verify forward scores all ``K+1`` positions; the
longest matching drafted prefix — ``n_e ∈ [1, K+1]`` tokens — advances
the persistent state in a single step. Greedy output stays
token-identical by the lane construction (each lane IS the window the
plain step would have seen), so speculation composes with every KV axis:
the verify executors pass the dense/paged/int8 caches through untouched
(recompute lanes never read them past the prefill), the pool maps each
round's worst-case burst atomically (``kv_pool.ensure_many`` —
multi-block crossings, lazy admission, and preemption victims behave as
``n_e`` sequential steps would), and every accepted token gets its own
``on_token`` delivery, ITL sample, and timeline event in index order.
Whether a round PAYS is measured
(``decode_strategy.autotune_speculation``) and persisted beside the
boundary/KV-layout/prefix-cache verdicts; ``"off"`` is byte-identical to
the pre-speculation engine.

Compile-count guarantee: at most ``len(prompt_buckets)`` prefill executors
plus one decode executor plus its boundary variant, plus ONE chunked-
prefill executor when ``prefill_chunk`` is set (``+2 -> +3``), plus the
draft + verify executor pair when ``speculation`` is on (``+2``) —
mixed-length traffic causes **zero** additional retraces after
:meth:`SlotServingEngine.warmup` (pinned by ``tests/test_slots.py`` /
``tests/test_decode_strategy.py`` / ``tests/test_speculative.py``).

Exactness: for greedy decoding the slot engine is token-identical to
unbucketed per-request ``generate()``, including requests admitted into
recycled slots mid-generation — each row's dynamic phase schedule (latent
growth while ``m < max_latents``, then boundary migration) reproduces the
static per-request plan exactly. Two scope restrictions keep that true,
enforced with precise errors at ``submit``:

- ``prompt_len + max_new_tokens <= max_seq_len`` — the sliding-window
  phase (semantically forced recompute, ``generate`` module docstring) has
  no incremental slot form; route longer generations to the bucket engine.
- ``prompt_len >= min(bucket_len, num_latents)`` — left pads must never
  occupy latent slots (the boundary cache's validity precondition; the
  bucket engine serves such prompts via its windowed-recompute demotion).

Fault tolerance mirrors the bucket engine (docs/reliability.md): bounded
queue backpressure, per-request deadlines checked every token (expiry
mid-generation retires the slot and ends the request's one terminal span
``timed_out``), per-request chaos hooks at admit time, executor-level
faults failing only resident requests while the queue survives.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Deque, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.inference import decode_strategy as decode_strategy_mod
from perceiver_io_tpu.inference import speculative as speculative_mod
from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    _decode_forward,
    _decode_prefill,
    _decode_step_boundary,
    _decode_step_boundary_paged,
    _prefill_chunk_kv,
    _prefill_finalize,
    _prefill_finalize_paged,
    _slot_decode_step,
    _slot_decode_step_paged,
    cached_executor,
    executor_cache_stats,
    ledger_model_id,
    model_fingerprint,
    register_executor_cache,
)
from perceiver_io_tpu.inference.samplers import apply_min_new_tokens, sample_logits
from perceiver_io_tpu.observability.timeline import tenant_label, tier_label
from perceiver_io_tpu.ops import paged_attention as paged_ops
from perceiver_io_tpu.serving.engine import ServeRequest, ServingEngine, _round_ms
from perceiver_io_tpu.serving.kv_pool import (
    KVPagePool,
    PoolExhausted,
    PrefixBlockIndex,
    SwapBundle,
)
from perceiver_io_tpu.serving.sharding import as_serving_sharding

#: preemption policies (docs/serving.md "Preemption & priorities" and
#: "Host-swap preemption"): ``off`` keeps reserve-worst-case admission;
#: ``recompute`` admits on prompt pages and replays preempted victims
#: from their original prompt (token-identical under greedy — no KV
#: state is saved or restored); ``swap`` gathers a victim's pool pages to
#: host memory and restores them at readmission, skipping prompt replay
#: entirely (pay transfer instead of recompute); ``auto`` picks swap vs
#: recompute per victim from the live post-mortem cost model.
PREEMPTION_MODES = ("off", "recompute", "swap", "auto")

_EXECUTOR_CACHE: dict = register_executor_cache({})


def _jit(fn, donate: tuple, out_shardings=None):
    """jit an executor body, donating the persistent slot state (``donate``
    argnums: in-place cache update on device) and optionally pinning its
    output shardings to the serving mesh (docs/serving.md "Sharded
    serving"). Pinning matters for trace stability, not just placement: the
    persistent state round-trips through every executor, so an output GSPMD
    re-sharded differently from its input would change the next call's
    committed-input signature and retrace. On a serving mesh the body is
    traced with that mesh published, because the flash kernel shard_maps
    itself over the ambient mesh (``ops/attention.py``). ``None`` (unsharded
    engine) is a plain ``jax.jit`` call."""
    if out_shardings is None:
        return jax.jit(fn, donate_argnums=donate)
    mesh = jax.tree_util.tree_leaves(out_shardings)[0].mesh

    @functools.wraps(fn)
    def on_mesh(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)

    return jax.jit(on_mesh, donate_argnums=donate, out_shardings=out_shardings)


_STATE_SHAPES: dict = {}  # (model key, param dtypes) -> (logits, cache) shapes


def _prefill_shapes(model, params):
    """ShapeDtypeStructs of one row's prefill outputs, via an abstract eval
    (no compile, no FLOPs). Tracing the flax module still costs hundreds of
    ms, so the result is memoized per (architecture, param dtypes) — engine
    construction and post-fault state rebuilds stay cheap."""
    key = (
        type(model).__qualname__, model_fingerprint(model),
        tuple(sorted({str(l.dtype) for l in jax.tree_util.tree_leaves(params)})),
    )
    hit = _STATE_SHAPES.get(key)
    if hit is not None:
        return hit
    n = model.max_seq_len

    def fn(p):
        window = jnp.zeros((1, n), jnp.int32)
        pad = jnp.zeros((1,), jnp.int32)
        return model.apply(
            {"params": p}, window, pad, jnp.asarray(1, jnp.int32),
            method=_decode_prefill,
        )

    logits_s, cache_s, _, _ = jax.eval_shape(fn, params)
    if len(_STATE_SHAPES) > 32:
        _STATE_SHAPES.clear()
    _STATE_SHAPES[key] = (logits_s, cache_s)
    return logits_s, cache_s


def _blank_state(model, params, slots: int, pad_token_id: int,
                 pool_tokens: Optional[int] = None,
                 quantized: bool = False) -> dict:
    """Zero-initialized persistent multi-slot decode state; KV-cache and
    logits shapes/dtypes track the model's computation dtype.

    ``pool_tokens`` selects the block-paged cross-KV layout
    (docs/serving.md): instead of per-slot dense ``cross_k/cross_v`` rows
    sized at the full context, the state holds ONE flat token-major pool
    ``pool_k/pool_v`` of that many positions, addressed through the
    engine's :class:`~perceiver_io_tpu.serving.kv_pool.KVPagePool` block
    tables. ``quantized`` (the ``paged_int8`` layout) stores the pool
    int8 and adds per-(position, head) f32 dequant scales ``scale_k/
    scale_v`` addressed by the same flat indices; a zero scale (every
    never-written position) dequantizes to exactly 0.0, so the blank
    pool reads as harmlessly as the exact layout's zeros. The
    latent-stack caches stay dense either way — they scale with
    ``max_latents`` (a model constant), not ``max_context``, so they are
    not part of the ``slots × max_context`` term the pool breaks."""
    n = model.max_seq_len
    logits_s, cache_s = _prefill_shapes(model, params)

    def z(sds):
        return jnp.zeros((slots,) + tuple(sds.shape[1:]), sds.dtype)

    state = {
        "window": jnp.full((slots, n), pad_token_id, jnp.int32),
        "pad": jnp.full((slots,), n, jnp.int32),
        "length": jnp.zeros((slots,), jnp.int32),
        "m": jnp.zeros((slots,), jnp.int32),
        "steps": jnp.zeros((slots,), jnp.int32),
        "logits": z(logits_s),
        "stack_k": tuple(z(s) for s in cache_s["stack_k"]),
        "stack_v": tuple(z(s) for s in cache_s["stack_v"]),
    }
    if pool_tokens is None:
        state["cross_k"] = z(cache_s["cross_k"])
        state["cross_v"] = z(cache_s["cross_v"])
    else:
        _, h, _, d = cache_s["cross_k"].shape
        pool_dtype = jnp.int8 if quantized else cache_s["cross_k"].dtype
        state["pool_k"] = jnp.zeros((pool_tokens, h, d), pool_dtype)
        state["pool_v"] = jnp.zeros((pool_tokens, h, d), pool_dtype)
        if quantized:
            state["scale_k"] = jnp.zeros((pool_tokens, h, 1), jnp.float32)
            state["scale_v"] = jnp.zeros((pool_tokens, h, 1), jnp.float32)
    return state


def _insert_row(state: dict, slot, *, window, pad, logits, cache, length, m,
                table_row=None, block_size: Optional[int] = None):
    """Insert one prefilled row (batch-1 caches + row state) into slot
    ``slot`` of the persistent multi-slot state — shared by the per-bucket
    prefill executor and the chunked-prefill finalize so the two admission
    paths cannot drift. ``slot`` and ``m`` may be traced scalars.

    Under the paged layout (``table_row`` given) the row's dense batch-1
    ``cross_k/cross_v`` scatter into the shared pool through the slot's
    block table: live positions land on the slot's mapped blocks, positions
    past them route to the null block (trash the masked attends never
    read)."""
    def upd(dst, src):
        return jax.lax.dynamic_update_slice(
            dst, src.astype(dst.dtype), (slot,) + (0,) * (dst.ndim - 1)
        )

    new = dict(state)
    if "cross_k" not in cache:
        # prefix-sharing finalize: the cross k/v already live in the pool
        # (shared blocks + the admission's own staged chunks) — only the
        # row state and the latent-stack caches get inserted here
        pass
    elif table_row is None:
        new["cross_k"] = upd(state["cross_k"], cache["cross_k"])
        new["cross_v"] = upd(state["cross_v"], cache["cross_v"])
    else:
        n = cache["cross_k"].shape[2]
        flat = paged_ops.flat_position_indices(table_row, block_size, n)
        # scatter_kv quantizes when the state carries scales (paged_int8)
        new["pool_k"], scale_k = paged_ops.scatter_kv(
            state["pool_k"], state.get("scale_k"), flat,
            cache["cross_k"][0].transpose(1, 0, 2),
        )
        new["pool_v"], scale_v = paged_ops.scatter_kv(
            state["pool_v"], state.get("scale_v"), flat,
            cache["cross_v"][0].transpose(1, 0, 2),
        )
        if scale_k is not None:
            new["scale_k"], new["scale_v"] = scale_k, scale_v
    new["stack_k"] = tuple(
        upd(d, s) for d, s in zip(state["stack_k"], cache["stack_k"])
    )
    new["stack_v"] = tuple(
        upd(d, s) for d, s in zip(state["stack_v"], cache["stack_v"])
    )
    new["window"] = upd(state["window"], window)
    new["pad"] = upd(state["pad"], pad)
    new["length"] = upd(state["length"], length.astype(jnp.int32))
    new["m"] = upd(state["m"], jnp.reshape(m, (1,)).astype(jnp.int32))
    new["steps"] = upd(state["steps"], jnp.zeros((1,), jnp.int32))
    new["logits"] = upd(state["logits"], logits)
    return new


def _build_prefill_executor(model, config: GenerationConfig, bucket_len: int,
                            block_size: Optional[int] = None,
                            out_shardings=None):
    """Prefill one request at prompt bucket ``bucket_len`` and insert its
    caches + row state into slot ``slot`` of the persistent state.
    ``block_size`` selects the paged layout: the executor additionally
    takes the slot's block-table row and scatters the cross cache into the
    shared pool instead of the dense slot row."""
    n = model.max_seq_len
    m0 = min(bucket_len, config.num_latents)

    def prefill(params, ids, pad_count):
        window = jnp.full((1, n), config.pad_token_id, ids.dtype)
        window = window.at[:, n - bucket_len:].set(ids)
        pad = pad_count.astype(jnp.int32) + (n - bucket_len)
        logits, cache, length, _ = model.apply(
            {"params": params}, window, pad, jnp.asarray(m0, jnp.int32),
            method=_decode_prefill,
        )
        return window, pad, logits, cache, length

    if block_size is None:
        def run(params, ids, pad_count, slot, state):
            window, pad, logits, cache, length = prefill(params, ids, pad_count)
            return _insert_row(
                state, slot, window=window, pad=pad, logits=logits,
                cache=cache, length=length, m=jnp.asarray(m0, jnp.int32),
            )

        return _jit(run, (4,), out_shardings)

    def run_paged(params, ids, pad_count, slot, table_row, state):
        window, pad, logits, cache, length = prefill(params, ids, pad_count)
        return _insert_row(
            state, slot, window=window, pad=pad, logits=logits, cache=cache,
            length=length, m=jnp.asarray(m0, jnp.int32),
            table_row=table_row, block_size=block_size,
        )

    return _jit(run_paged, (5,), out_shardings)


def _build_chunked_prefill_executor(model, config: GenerationConfig, chunk: int,
                                    block_size: Optional[int] = None,
                                    out_shardings=None):
    """ONE bucket-independent executor for chunked admission, two
    ``lax.cond`` branches in one compiled program. Stage calls project the
    ``kv_norm``-side cross k/v of ``chunk`` prefix token positions into a
    batch-1 staging cache
    (:func:`~perceiver_io_tpu.inference.generate._prefill_chunk_kv`); the
    final call runs ONLY the finalize — latent-side k/v, gathered
    cross-attention, the self-attention stack
    (:func:`~..generate._prefill_finalize`) — and inserts caches + row
    state into slot ``slot``. Keeping the branches disjoint matters for the
    tail latency the feature exists to cut: the finalize call must not
    also pay a chunk's staging math, or the admission's worst per-step
    stall creeps back toward the one-shot prefill's.

    ``offset``, ``m``, ``slot`` and ``is_final`` are traced, so every
    chunk of every prompt bucket reuses this single program — the
    compile-count bound grows by exactly one
    (``len(prompt_buckets) + 2 -> + 3``, pinned by tests)."""

    def run(params, tokens, offset, is_final, window, pad_count, m, slot,
            table_row, stage_k, stage_v, state):
        def stage(ops):
            stage_k, stage_v, state = ops
            k_c, v_c = model.apply(
                {"params": params}, tokens, offset, method=_prefill_chunk_kv
            )
            stage_k = jax.lax.dynamic_update_slice(
                stage_k, k_c.astype(stage_k.dtype), (0, 0, offset, 0)
            )
            stage_v = jax.lax.dynamic_update_slice(
                stage_v, v_c.astype(stage_v.dtype), (0, 0, offset, 0)
            )
            return stage_k, stage_v, state

        def fin(ops):
            stage_k, stage_v, state = ops
            logits, cache, length, _ = model.apply(
                {"params": params}, window, pad_count, m, stage_k, stage_v,
                method=_prefill_finalize,
            )
            state = _insert_row(
                state, slot, window=window, pad=pad_count, logits=logits,
                cache=cache, length=length, m=m,
                # paged layout: the finalized row's dense cross cache
                # scatters into the pool through the slot's block table
                # (live positions -> mapped blocks, the rest -> null block)
                table_row=None if block_size is None else table_row,
                block_size=block_size,
            )
            return stage_k, stage_v, state

        return jax.lax.cond(is_final, fin, stage, (stage_k, stage_v, state))

    return _jit(run, (9, 10, 11), out_shardings)


def _build_shared_prefill_executor(model, config: GenerationConfig, chunk: int,
                                   block_size: int, out_shardings=None,
                                   gather_sharding=None):
    """The prefix-sharing admission executor (docs/serving.md "Prefix
    sharing"): ONE compiled program, two ``lax.cond`` branches, taking the
    admission's **start position** so shared prefix positions are never
    projected again.

    Stage calls project the ``kv_norm``-side cross k/v of ``chunk``
    prefix positions (:func:`~perceiver_io_tpu.inference.generate.
    _prefill_chunk_kv` — per-position math, identical values to the
    one-shot prefill) and scatter them STRAIGHT INTO THE POOL through the
    slot's block table; positions outside ``[lo, hi)`` — the un-shared
    prefix span — route to the null block, so a shared page is never
    written through (clamped chunk overruns land in trash, exactly the
    PR-8 write-routing discipline). The pool pages being written are the
    slot's own private/COW'd pages, invisible to every other slot's
    gathers, so interleaved decode steps never observe a half-built row.

    The final call runs :func:`~perceiver_io_tpu.inference.generate.
    _prefill_finalize_paged` — latent projections + pool gather + attend +
    stack — and inserts the finished row. ``offset``/``lo``/``hi``/``m``/
    ``slot`` are traced: one program serves every shared-span length of
    every prompt bucket, so the compile bound grows by exactly one."""

    def run(params, tokens, offset, is_final, window, pad_count, m, slot,
            table_row, lo, hi, state):
        table = table_row[None]

        def stage(state):
            k_c, v_c = model.apply(
                {"params": params}, tokens, offset, method=_prefill_chunk_kv
            )
            pos = offset + jnp.arange(chunk, dtype=jnp.int32)
            flat = paged_ops.flat_write_indices(table, pos[None, :], block_size)
            ok = (pos >= lo) & (pos < hi)
            flat = jnp.where(ok[None, :], flat, pos[None, :] % block_size)
            # scatter_kv quantizes when the state carries scales (paged_int8)
            pool_k, scale_k = paged_ops.scatter_kv(
                state["pool_k"], state.get("scale_k"), flat[0],
                k_c[0].transpose(1, 0, 2),
            )
            pool_v, scale_v = paged_ops.scatter_kv(
                state["pool_v"], state.get("scale_v"), flat[0],
                v_c[0].transpose(1, 0, 2),
            )
            out = {**state, "pool_k": pool_k, "pool_v": pool_v}
            if scale_k is not None:
                out["scale_k"], out["scale_v"] = scale_k, scale_v
            return out

        def fin(state):
            quant = "scale_k" in state
            scale_kwargs = (
                {"scale_k": state["scale_k"], "scale_v": state["scale_v"]}
                if quant else {}
            )
            outs = model.apply(
                {"params": params}, window, pad_count, m,
                state["pool_k"], state["pool_v"], table_row, block_size,
                method=_prefill_finalize_paged, **scale_kwargs,
            )
            if quant:
                (logits, pool_k, pool_v, scale_k, scale_v, cache, length,
                 m_out) = outs
                state = {**state, "pool_k": pool_k, "pool_v": pool_v,
                         "scale_k": scale_k, "scale_v": scale_v}
            else:
                logits, pool_k, pool_v, cache, length, m_out = outs
                state = {**state, "pool_k": pool_k, "pool_v": pool_v}
            return _insert_row(
                state, slot, window=window, pad=pad_count, logits=logits,
                cache=cache, length=length, m=m_out,
            )

        # trace-time: the finalize branch's pool gather stays head-sharded
        # on the serving mesh — lax.cond traces both branches inside the
        # context (docs/serving.md "Sharded serving")
        with paged_ops.gather_constraint(gather_sharding):
            return jax.lax.cond(is_final, fin, stage, state)

    return _jit(run, (11,), out_shardings)


def _build_page_copy_executor(block_size: int, out_shardings=None):
    """Copy one pool block's k/v content onto another — the device half of
    copy-on-write (``serving/kv_pool.py``): the host allocator swaps a
    fresh private block into the writing slot's table and this program
    makes its content identical to the shared source page before any
    write lands. ``src``/``dst`` are traced scalars: one compile covers
    every COW in the engine's lifetime."""

    def run(state, src, dst):
        idx_src = src * block_size + jnp.arange(block_size)
        idx_dst = dst * block_size + jnp.arange(block_size)
        pool_k = state["pool_k"].at[idx_dst].set(state["pool_k"][idx_src])
        pool_v = state["pool_v"].at[idx_dst].set(state["pool_v"][idx_src])
        out = {**state, "pool_k": pool_k, "pool_v": pool_v}
        if "scale_k" in state:
            # int8 layout: a COW'd page's dequant scales travel with its
            # content — already-quantized rows copy bit-exact, no requant
            out["scale_k"] = state["scale_k"].at[idx_dst].set(
                state["scale_k"][idx_src]
            )
            out["scale_v"] = state["scale_v"].at[idx_dst].set(
                state["scale_v"][idx_src]
            )
        return out

    return _jit(run, (0,), out_shardings)


def _build_swap_extract_executor(block_size: int):
    """Gather one victim's pool pages + per-slot row state for host swap
    (docs/serving.md "Host-swap preemption"). ``table_row`` is the slot's
    FULL padded block-table row and ``slot`` a traced scalar, so one
    compile covers every victim geometry: unmapped tail entries are 0 and
    gather null-block trash the restore routes right back to the null
    block. NOT donated — the resident state must survive the gather (the
    victim's neighbours keep decoding from it)."""

    def run(state, table_row, slot):
        flat = (
            table_row[:, None] * block_size + jnp.arange(block_size)[None, :]
        ).reshape(-1)
        out = {
            "pool_k": state["pool_k"][flat],
            "pool_v": state["pool_v"][flat],
        }
        if "scale_k" in state:
            out["scale_k"] = state["scale_k"][flat]
            out["scale_v"] = state["scale_v"][flat]
        row = {}
        for key in ("window", "pad", "length", "m", "steps", "logits"):
            row[key] = jax.lax.dynamic_index_in_dim(
                state[key], slot, axis=0, keepdims=False
            )
        row["stack_k"] = tuple(
            jax.lax.dynamic_index_in_dim(l, slot, axis=0, keepdims=False)
            for l in state["stack_k"]
        )
        row["stack_v"] = tuple(
            jax.lax.dynamic_index_in_dim(l, slot, axis=0, keepdims=False)
            for l in state["stack_v"]
        )
        out["row"] = row
        return out

    return jax.jit(run)


def _build_swap_restore_executor(block_size: int, out_shardings=None):
    """Scatter a :class:`~perceiver_io_tpu.serving.kv_pool.SwapBundle`'s
    payload back into the pool through the restored slot's NEW block-table
    row and re-insert its row state — the device half of swap-in. Pages
    below ``lo_blocks`` (the re-referenced prefix-shared run — their
    device content never left) and the unmapped tail route to the null
    block: a shared page is never written through, and the trash block
    absorbs the padding writes exactly as prefill scatter does. int8
    payloads restore bit-exact (no requant: content and scales travel
    together)."""

    def run(state, payload, table_row, slot, lo_blocks):
        pages = table_row.shape[0]
        pos = jnp.arange(pages * block_size)
        flat = (
            table_row[:, None] * block_size + jnp.arange(block_size)[None, :]
        ).reshape(-1)
        idx = jnp.where(pos >= lo_blocks * block_size, flat, pos % block_size)
        out = dict(state)
        out["pool_k"] = state["pool_k"].at[idx].set(
            payload["pool_k"].astype(state["pool_k"].dtype)
        )
        out["pool_v"] = state["pool_v"].at[idx].set(
            payload["pool_v"].astype(state["pool_v"].dtype)
        )
        if "scale_k" in state:
            out["scale_k"] = state["scale_k"].at[idx].set(
                payload["scale_k"].astype(state["scale_k"].dtype)
            )
            out["scale_v"] = state["scale_v"].at[idx].set(
                payload["scale_v"].astype(state["scale_v"].dtype)
            )

        def upd(dst, src):
            return jax.lax.dynamic_update_slice(
                dst,
                jnp.reshape(src, (1,) + dst.shape[1:]).astype(dst.dtype),
                (slot,) + (0,) * (dst.ndim - 1),
            )

        row = payload["row"]
        for key in ("window", "pad", "length", "m", "steps", "logits"):
            out[key] = upd(state[key], row[key])
        out["stack_k"] = tuple(
            upd(d, s) for d, s in zip(state["stack_k"], row["stack_k"])
        )
        out["stack_v"] = tuple(
            upd(d, s) for d, s in zip(state["stack_v"], row["stack_v"])
        )
        return out

    return _jit(run, (0,), out_shardings)


def _build_decode_executor(model, config: GenerationConfig, boundary: bool,
                           boundary_mode: str = "cached",
                           block_size: Optional[int] = None,
                           out_shardings=None, gather_sharding=None):
    """One fixed-shape token step over all slots: sample each row's next
    token from the resident logits, append it, advance every cache by one
    token. ``boundary=True`` additionally runs the boundary-phase step for
    rows whose latent segment is full and selects per row
    (``m == max_latents``) — the conservative mixed-phase variant, compiled
    once and used only while such a row is resident. ``boundary_mode``
    picks that step's implementation per the decode strategy
    (``inference/decode_strategy.py``): ``"cached"`` runs the cross-cache
    boundary-migration step, ``"recompute"`` the full windowed forward
    (exact either way; the winner is a measured platform/shape property).
    Under recompute the boundary rows' cross caches go
    stale, which is safe: a row never leaves the boundary phase (the
    sliding-window phase is out of the slot engine's scope)."""
    n = model.max_seq_len
    max_latents = model.max_latents
    min_new = config.min_new_tokens if config.eos_token_id is not None else 0

    if block_size is not None:
        # Paged layout: same per-token schedule, but the cross caches live
        # in the shared block pool and the executor takes the (slots,
        # pages) block table as a per-call traced argument — the host
        # re-pushes it only when the allocator changed it, and no table
        # content ever retraces this program. The dense executor's per-row
        # ``where`` select between the base and boundary steps becomes
        # write ROUTING (``write_ok``): each live pool position is written
        # by exactly the step whose value the dense select would keep, so
        # live rows' logits stay bitwise identical to the dense layout.
        def _paged_body(params, state, table, rng):
            logits = state["logits"].astype(jnp.float32)
            logits = apply_min_new_tokens(
                logits, state["steps"][:, None], min_new, config.eos_token_id or 0
            )
            pad_positions = jnp.arange(n)[None, :] < state["pad"][:, None]
            token = sample_logits(
                rng, logits, config.sampling, state["window"], pad_positions
            )
            window = jnp.concatenate(
                [state["window"][:, 1:], token[:, None].astype(state["window"].dtype)],
                axis=1,
            )
            pad = jnp.maximum(state["pad"] - 1, 0)
            length, m = state["length"], state["m"]
            stack_cache = {
                "stack_k": list(state["stack_k"]), "stack_v": list(state["stack_v"]),
            }
            is_b = m >= max_latents
            write_ok = None
            if boundary and boundary_mode == "cached":
                write_ok = ~is_b  # boundary rows' appends belong to the
                # boundary step below (dense select semantics)
            quant = "scale_k" in state  # paged_int8: scales ride along
            scale_kwargs = (
                {"scale_k": state["scale_k"], "scale_v": state["scale_v"]}
                if quant else {}
            )
            outs = model.apply(
                {"params": params}, token, state["pool_k"], state["pool_v"],
                table, stack_cache, length, m, block_size, write_ok,
                method=_slot_decode_step_paged, **scale_kwargs,
            )
            if quant:
                logits_a, pool_k, pool_v, scale_k, scale_v, stack_a, _, _ = outs
            else:
                logits_a, pool_k, pool_v, stack_a, _, _ = outs
                scale_k = scale_v = None
            new_logits = logits_a
            stack_k, stack_v = stack_a["stack_k"], stack_a["stack_v"]
            if boundary and boundary_mode == "recompute":
                logits_b = model.apply(
                    {"params": params}, window, pad,
                    jnp.asarray(max_latents, jnp.int32),
                    method=_decode_forward,
                )
                new_logits = jnp.where(is_b[:, None], logits_b, logits_a)
            elif boundary:
                b_scale_kwargs = (
                    {"scale_k": scale_k, "scale_v": scale_v} if quant else {}
                )
                outs_b = model.apply(
                    {"params": params}, window, pad, pool_k, pool_v, table,
                    length, block_size, is_b,
                    method=_decode_step_boundary_paged, **b_scale_kwargs,
                )
                if quant:
                    logits_b, pool_k, pool_v, scale_k, scale_v, _ = outs_b
                else:
                    logits_b, pool_k, pool_v, _ = outs_b
                r4 = is_b[:, None, None, None]
                new_logits = jnp.where(is_b[:, None], logits_b, logits_a)
                # boundary rows' stack caches are stale by construction
                # (the boundary step recomputes the whole stack); keep
                # their old entries so latent rows' appends survive
                stack_k = [jnp.where(r4, old, a) for old, a in zip(state["stack_k"], stack_k)]
                stack_v = [jnp.where(r4, old, a) for old, a in zip(state["stack_v"], stack_v)]
            new_state = {
                "window": window,
                "pad": pad,
                "length": jnp.minimum(length + 1, n),  # idle slots saturate
                "m": jnp.minimum(m + 1, max_latents),
                "steps": state["steps"] + 1,
                "logits": new_logits.astype(state["logits"].dtype),
                "pool_k": pool_k, "pool_v": pool_v,
                "stack_k": tuple(stack_k), "stack_v": tuple(stack_v),
            }
            if quant:
                new_state["scale_k"], new_state["scale_v"] = scale_k, scale_v
            return new_state, token

        def run_paged(params, state, table, rng):
            # trace-time: every pool gather in the body (base step AND the
            # boundary variant) keeps its dense view slot/head-sharded on
            # the serving mesh (docs/serving.md "Sharded serving")
            with paged_ops.gather_constraint(gather_sharding):
                return _paged_body(params, state, table, rng)

        return _jit(run_paged, (1,), out_shardings)

    def run(params, state, rng):
        logits = state["logits"].astype(jnp.float32)
        # EOS unreachable until min_new_tokens — per-row step counts (the
        # scan path passes a scalar step; broadcasting handles the vector)
        logits = apply_min_new_tokens(
            logits, state["steps"][:, None], min_new, config.eos_token_id or 0
        )
        pad_positions = jnp.arange(n)[None, :] < state["pad"][:, None]
        token = sample_logits(
            rng, logits, config.sampling, state["window"], pad_positions
        )
        window = jnp.concatenate(
            [state["window"][:, 1:], token[:, None].astype(state["window"].dtype)],
            axis=1,
        )
        pad = jnp.maximum(state["pad"] - 1, 0)
        length, m = state["length"], state["m"]
        cache = {
            "cross_k": state["cross_k"], "cross_v": state["cross_v"],
            "stack_k": list(state["stack_k"]), "stack_v": list(state["stack_v"]),
        }
        logits_a, cache_a, _, _ = model.apply(
            {"params": params}, token, cache, length, m, method=_slot_decode_step
        )
        new_logits = logits_a
        cross_k, cross_v = cache_a["cross_k"], cache_a["cross_v"]
        stack_k, stack_v = cache_a["stack_k"], cache_a["stack_v"]
        if boundary and boundary_mode == "recompute":
            # Strategy-selected full recompute for boundary rows: the
            # windowed forward at m = max_latents (garbage for latent rows,
            # selected away). No cache writes — boundary rows never read
            # their cross cache again under this mode.
            logits_b = model.apply(
                {"params": params}, window, pad,
                jnp.asarray(max_latents, jnp.int32),
                method=_decode_forward,
            )
            is_b = m >= max_latents
            new_logits = jnp.where(is_b[:, None], logits_b, logits_a)
        elif boundary:
            logits_b, ck_b, cv_b, _ = model.apply(
                {"params": params}, window, pad,
                state["cross_k"], state["cross_v"], length,
                method=_decode_step_boundary,
            )
            is_b = m >= max_latents
            r4 = is_b[:, None, None, None]
            new_logits = jnp.where(is_b[:, None], logits_b, logits_a)
            cross_k = jnp.where(r4, ck_b, cross_k)
            cross_v = jnp.where(r4, cv_b, cross_v)
            # boundary rows' stack caches are stale by construction (the
            # boundary step recomputes the whole stack); keep their old
            # entries untouched so latent rows' appends survive the select
            stack_k = [jnp.where(r4, old, a) for old, a in zip(state["stack_k"], stack_k)]
            stack_v = [jnp.where(r4, old, a) for old, a in zip(state["stack_v"], stack_v)]
        new_state = {
            "window": window,
            "pad": pad,
            "length": jnp.minimum(length + 1, n),  # idle slots saturate
            "m": jnp.minimum(m + 1, max_latents),
            "steps": state["steps"] + 1,
            "logits": new_logits.astype(state["logits"].dtype),
            "cross_k": cross_k, "cross_v": cross_v,
            "stack_k": tuple(stack_k), "stack_v": tuple(stack_v),
        }
        return new_state, token

    return _jit(run, (1,), out_shardings)


def _build_spec_draft_executor(model, config: GenerationConfig, spec,
                               out_shardings=None):
    """Draft phase of one speculative round (docs/serving.md "Speculative
    decoding"): ``spec.k`` truncated-stack forwards propose ``(slots, k+1)``
    candidate tokens from the resident window/logits state —
    ``cand[:, 0]`` is the exact greedy token of the already-verified
    logits, the rest come from the ``spec.draft_layers``-deep self-draft
    (:func:`~perceiver_io_tpu.inference.speculative.propose_tokens`).
    Read-only over the state (NO donation — the verify executor consumes
    the same buffers right after), so the pair costs no extra state copy."""
    min_new = config.min_new_tokens if config.eos_token_id is not None else 0

    def run(params, state):
        return model.apply(
            {"params": params}, state["window"], state["pad"], state["m"],
            state["steps"], state["logits"], spec.k, spec.draft_layers,
            min_new, config.eos_token_id or 0,
            method=speculative_mod.propose_tokens,
        )

    return _jit(run, (), out_shardings)


def _build_spec_verify_executor(model, config: GenerationConfig, spec,
                                out_shardings=None):
    """Verify + accept + advance phase of one speculative round: ONE
    lane-batched full-model forward scores all ``k+1`` candidate positions
    (:func:`~perceiver_io_tpu.inference.speculative.verify_lanes` — lane
    ``j`` is bitwise the window the plain step would have seen after
    emitting ``j+1`` tokens, per row, in every phase regime), the longest
    matching drafted prefix is accepted, and the fixed-shape state
    advances by ``n_e ∈ [1, k+1]`` tokens in one donated step.

    The KV caches (dense cross or paged pool + scales, latent stacks) pass
    through UNTOUCHED: speculation decodes by windowed recompute, so cache
    content past what the prefill wrote is never read again — the same
    deliberate-staleness contract as the recompute boundary strategy, and
    the reason speculation composes with paged/int8/prefix-shared layouts
    without a cache-append variant per layout."""
    n = model.max_seq_len
    max_latents = model.max_latents
    min_new = config.min_new_tokens if config.eos_token_id is not None else 0

    def run(params, state, cand):
        lane_logits = model.apply(
            {"params": params}, state["window"], state["pad"], state["m"],
            cand, method=speculative_mod.verify_lanes,
        )
        n_e, next_logits = speculative_mod.accept_prefix(
            lane_logits, cand, state["steps"], min_new,
            config.eos_token_id or 0,
        )
        window, pad, m = speculative_mod.advance_window(
            state["window"], state["pad"], state["m"], cand, n_e, max_latents
        )
        new_state = dict(state)
        new_state.update(
            window=window,
            pad=pad,
            length=jnp.minimum(state["length"] + n_e, n),  # idle slots saturate
            m=m,
            steps=state["steps"] + n_e,
            logits=next_logits.astype(state["logits"].dtype),
        )
        return new_state, n_e

    return _jit(run, (1,), out_shardings)


@dataclasses.dataclass
class _Slot:
    """Host-side record of one resident request: the emitted tokens plus the
    mirrored per-row counters the scheduler needs without device reads."""

    req: ServeRequest
    slot: int
    max_new: int
    m: int  # mirrors state["m"][slot] for decode-variant choice
    emitted: List[int] = dataclasses.field(default_factory=list)
    #: engine-clock time this row's latest token materialized — the
    #: inter-token latency anchor (docs/observability.md)
    last_token_at: float = 0.0


@dataclasses.dataclass
class _PrefixPlan:
    """Host-side record of one admission's prefix-cache match
    (docs/serving.md "Prefix sharing"): the cached FULL blocks it maps by
    reference, the optional divergent/partially-usable block it
    copy-on-writes, and the resulting shared span ``shared_tokens`` —
    the start position the suffix-only prefill skips to."""

    nodes: list  # fully-shared _PrefixNode chain (mapped by reference)
    partial: Optional[object]  # COW donor node (divergent / clamped block)
    shared_tokens: int  # S: prefill projects only [S, prefix_len)
    bucket_len: int
    m0: int
    prefix_len: int


@dataclasses.dataclass
class _ChunkedAdmit:
    """Host-side record of one in-flight chunked admission: the reserved
    slot, the prepared window/row state, the chunk schedule, and the
    device-side staging caches the chunk executor accumulates into. The
    persistent slot state is untouched until the finalize call inserts the
    finished row, so interleaved decode steps can never observe a
    half-built cache."""

    req: ServeRequest
    slot: int
    bucket_len: int
    m0: int
    window: np.ndarray  # (1, n) right-aligned ids
    pad: np.ndarray  # (1,) left-pad count
    by_index: np.ndarray  # (n,) ids in token-index space (prompt then pad)
    offsets: List[int]  # staging-chunk start indices; one more pure
    # finalize call follows the last chunk
    chunk: int = 0  # staging-chunk size C this admission was scheduled with
    next_chunk: int = 0
    stage_k: object = None
    stage_v: object = None
    device_ms: float = 0.0  # summed per-chunk executor time
    #: prefix-cache match (None = unshared admission). Shared admissions
    #: stage straight into the pool through the shared prefill executor;
    #: ``lo``/``hi`` bound the writable span (docs/serving.md "Prefix
    #: sharing")
    plan: Optional[_PrefixPlan] = None
    lo: int = 0
    hi: int = 0


class SlotServingEngine(ServingEngine):
    """Token-granular scheduler over the persistent-slot decode state.

    Shares the bucket engine's whole request surface — ``submit`` /
    ``serve`` / ``step`` / ``run_until_idle`` / ``drain`` / ``stats`` /
    ``health``, bounded queue, deadlines, chaos hooks, metrics registry,
    tracer — but ``step()`` advances ONE TOKEN across all ``S`` slots
    instead of one whole micro-batch, admitting and retiring in flight.

    :param slots: number of persistent decode slots ``S`` (the decode
        executor's fixed batch dimension). The bucket table's
        ``batch_sizes`` are ignored; ``prompt_lens`` are the prefill grid.
    :param prefill_chunk: chunked-prefill chunk size (token positions per
        chunk-executor call). A request whose prefix exceeds it is admitted
        incrementally — one chunk per ``step()``, interleaved with resident
        decode steps, so a long admission no longer stalls resident slots'
        token cadence. ``None`` (default) keeps every admission on the
        single-call per-bucket prefill path.
    :param decode_strategy: boundary-phase decode strategy for the mixed
        boundary decode variant — ``"auto" | "cached" | "recompute"``.
        ``None`` defers to ``PERCEIVER_DECODE_STRATEGY`` then the measured
        registry (cached when untuned). ``warmup()`` runs the autotuner
        first when set to ``"auto"`` explicitly, so one deployment measures
        once and every variant compiles against the winner.
    :param kv_layout: cross-KV cache layout — ``"auto" | "dense" |
        "paged"`` (docs/serving.md "Block-paged KV"). ``dense`` keeps
        per-slot worst-case caches (the original layout); ``paged`` holds
        ONE shared block pool + per-slot block tables, so HBM scales with
        the pool size instead of ``slots × max_context`` and a long-tail
        workload admits more residents at the same budget. Both layouts
        are greedy token-identical (pinned by ``tests/test_paged_kv.py``).
        ``None`` defers to ``PERCEIVER_KV_LAYOUT`` then the measured
        registry (dense when untuned); an explicit ``"auto"`` makes
        ``warmup()`` run the kv-layout autotuner and rebuild onto the
        winner.
    :param kv_block_size: token positions per pool block (paged layout;
        default ``min(16, max_seq_len)``).
    :param kv_blocks: usable pool capacity in blocks (the null block is
        extra). Default sizes the pool at dense capacity
        (``slots * ceil(max_seq_len / kv_block_size)``); size it BELOW
        that to spend less HBM than dense while long-tail traffic still
        fills every slot — requests whose worst case cannot currently fit
        wait at the queue head (``kv_pool_admit_waits_total``), and
        requests that could never fit reject at submit.
    :param prefix_cache: cross-request prefix sharing — ``"auto" | "on" |
        "off"`` (docs/serving.md "Prefix sharing"; ``kv_layout="paged"``
        only). ``on`` keeps a radix index over published full
        prompt-prefix blocks: an admission whose leading token ids match
        maps those blocks by reference (per-block refcounts), copy-on-
        writes at the first divergent or partially-usable block, and
        prefills ONLY the un-shared suffix — a fully-hot system prompt
        collapses TTFT to block-table writes plus the latent finalize.
        Greedy output stays token-identical to the unshared path (pinned
        by ``tests/test_prefix_cache.py``). Unreferenced cached prefixes
        are LRU-dropped under pool pressure before an admission is made
        to wait. ``None`` defers to ``PERCEIVER_PREFIX_CACHE`` then the
        measured registry (off when unrecorded).
    :param preemption: optimistic KV admission + eviction under memory
        pressure — ``"off" | "recompute" | "swap" | "auto"``
        (docs/serving.md "Preemption & priorities" and "Host-swap
        preemption"; paged layouts only). ``"recompute"`` drops the
        up-front worst-case reservation: a request admits when its PROMPT
        pages fit (plus ``admit_headroom_blocks``), decode pages allocate
        lazily at each block-boundary crossing, and when a crossing finds
        the pool genuinely dry the engine preempts a victim —
        lowest-priority-first, then most-pages-held, then fewest-tokens-
        generated, never a higher tier — returning every page
        (``frees_by_cause["preempted"]``) and requeueing it for a
        token-identical greedy replay from its original prompt.
        ``"swap"`` keeps the same admission and victim policy but gathers
        the victim's pool pages (+ int8 scales) to host memory first
        (``frees_by_cause["swapped"]``); readmission restores them into
        whatever free blocks exist and resumes decoding at the
        pre-preemption position — no prompt replay, transfer instead of
        recompute, still greedy token-identical. ``"auto"`` arbitrates
        per victim: swap when the post-mortem cost model (measured decode
        step × tokens to replay vs victim bytes ÷ the calibrated
        ``swap_link_gbps``) scores transfer cheaper, recompute otherwise.
        ``"off"`` (default) keeps reserve-worst-case admission unchanged.
    :param swap_link_gbps: host-link bandwidth (decimal GB/s) for the
        post-mortem swap cost model and the ``auto`` arbitration. Default
        ``None`` reads the calibrated per-platform registry entry
        (``swap_entries``; every real swap refines it from measured
        transfer time) and falls back to a 16 GB/s prior.
    :param admit_headroom_blocks: extra decode blocks hard-committed per
        lazy admission (``preemption="recompute"`` only) — a small buffer
        that absorbs the first boundary crossings without triggering
        preemption; 0 (default) admits on prompt pages alone.
    :param mesh: serving parallelism mesh (docs/serving.md "Sharded
        serving") — a :class:`~perceiver_io_tpu.serving.sharding.
        ServingMeshSpec` (or resolved ``ServingSharding`` / 4-axis training
        ``Mesh`` with fsdp/seq at 1). Slots/batch shard along ``data``
        (``slots`` must divide evenly), attention heads and KV caches —
        dense per-slot AND the paged pool's flat ``pool_k``/``pool_v`` —
        along ``model`` (heads must divide evenly); params get the
        Megatron TP placement. Every executor compiles over the mesh with
        pinned output shardings; mesh geometry folds into the executor
        cache keys and the compile ledger's ``mesh`` component, so a mesh
        change rebuilds and attributes instead of reusing a stale
        single-device trace. A 1-device mesh reproduces the unsharded
        engine's behavior exactly, and greedy output on a real mesh stays
        token-identical (pinned by ``tests/test_sharding.py``). ``None``
        (default) keeps today's single-device path untouched.
    """

    def __init__(self, model, params, config: Optional[GenerationConfig] = None,
                 table=None, *, slots: int = 8,
                 prefill_chunk: Optional[int] = None,
                 decode_strategy: Optional[str] = None,
                 kv_layout: Optional[str] = None,
                 kv_block_size: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 prefix_cache: Optional[str] = None,
                 preemption: Optional[str] = None,
                 admit_headroom_blocks: int = 0,
                 swap_link_gbps: Optional[float] = None,
                 speculation: Optional[str] = None,
                 mesh=None, **kwargs):
        super().__init__(
            model, params, config, table, decode_strategy=decode_strategy,
            **kwargs
        )
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if kv_layout is not None and kv_layout not in decode_strategy_mod.KV_LAYOUTS:
            raise ValueError(
                f"kv_layout must be one of {decode_strategy_mod.KV_LAYOUTS}, "
                f"got {kv_layout!r}"
            )
        if kv_block_size is not None and kv_block_size < 1:
            raise ValueError(f"kv_block_size must be >= 1, got {kv_block_size}")
        if kv_blocks is not None and kv_blocks < 1:
            raise ValueError(f"kv_blocks must be >= 1, got {kv_blocks}")
        self.slots = int(slots)
        self.prefill_chunk = (
            None if prefill_chunk is None
            else int(min(prefill_chunk, model.max_seq_len))
        )
        # -- serving mesh (docs/serving.md "Sharded serving") --------------
        # slots shard along `data`, heads/KV along `model`; params get the
        # TP placement once here (self.params stays the caller's unsharded
        # tree — the autotuners' probe engines compile their own unsharded
        # executors from it, keyed without the mesh component).
        self.sharding = as_serving_sharding(mesh)
        if self.sharding is not None and self.slots % self.sharding.data_size:
            raise ValueError(
                f"slots ({self.slots}) must divide evenly over the mesh "
                f"data axis ({self.sharding.data_size}): the decode "
                "executor's fixed batch dimension is slot-sharded"
            )
        self._exec_params = (
            self.sharding.put_params(self.params)
            if self.sharding is not None else self.params
        )
        self.registry.declare_counters(
            "serving_decode_steps_total",
            "serving_decode_rows_total",
            "serving_decode_rows_padded_total",
            "serving_prefills_total",
            "serving_prefill_chunks_total",
            "kv_pool_block_allocs_total",
            "kv_pool_block_frees_total",
            "kv_pool_admit_waits_total",
            "kv_prefix_hits_total",
            "kv_prefix_misses_total",
            "kv_prefix_shared_blocks_total",
            "kv_prefix_shared_tokens_total",
            "kv_prefix_cow_copies_total",
            "kv_prefix_evicted_blocks_total",
            "kv_prefix_published_blocks_total",
            "kv_quant_fallback_total",
            "kv_ragged_kernel_steps_total",
            "kv_preemptions_total",
            "kv_readmissions_total",
            "kv_swaps_total",
            "kv_swap_restores_total",
            "kv_swap_bytes_total",
            "spec_rounds_total",
            "spec_tokens_proposed_total",
            "spec_tokens_accepted_total",
            "spec_tokens_emitted_total",
        )
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self._admitting: Optional[_ChunkedAdmit] = None
        self._pinned_boundary_mode: Optional[str] = None
        # -- KV layout (docs/serving.md "Block-paged KV") ------------------
        # dense: per-slot worst-case cross caches (the original layout);
        # paged: one shared block pool + per-slot block tables. Resolution
        # mirrors the boundary strategy: explicit arg > PERCEIVER_KV_LAYOUT
        # > measured registry > dense. An explicit "auto" re-resolves at
        # warmup() after the kv-layout autotuner runs.
        self.kv_layout_requested = kv_layout
        #: True when the operator sized the pool explicitly — sizing IS a
        #: layout choice, so a dense resolution would silently discard the
        #: HBM budget the caller asked for; reject loudly instead, and skip
        #: the warmup auto-switch (a dense verdict must not drop the budget)
        self._kv_sized = kv_block_size is not None or kv_blocks is not None
        self.kv_block_size = int(
            min(kv_block_size or min(16, model.max_seq_len), model.max_seq_len)
        )
        #: usable pool capacity in blocks (null block excluded); default
        #: matches the dense layout's capacity so un-tuned paged serving
        #: admits exactly what dense would
        self.kv_blocks = int(kv_blocks or self.slots * self._pages_per_slot())
        resolved = decode_strategy_mod.resolve_kv_layout(kv_layout, model)
        if self._kv_sized and resolved not in decode_strategy_mod.PAGED_KV_LAYOUTS:
            raise ValueError(
                "kv_block_size/kv_blocks size the paged pool but the KV "
                f"layout resolved to {resolved!r} — the budget would be "
                "silently ignored; pass kv_layout='paged' or 'paged_int8' "
                "(sizing the pool is choosing the paged layout)"
            )
        # -- prefix cache (docs/serving.md "Prefix sharing") ---------------
        # cross-request copy-on-write sharing of hot prompt-prefix blocks;
        # only meaningful under the paged layout (sharing IS a block-table
        # property). Resolution mirrors the other axes: explicit arg >
        # PERCEIVER_PREFIX_CACHE > persisted registry > off.
        if prefix_cache is not None and \
                prefix_cache not in decode_strategy_mod.PREFIX_CACHE_MODES:
            raise ValueError(
                "prefix_cache must be one of "
                f"{decode_strategy_mod.PREFIX_CACHE_MODES}, got {prefix_cache!r}"
            )
        self.prefix_cache_requested = prefix_cache
        #: the resolved PREFERENCE (explicit > env > registry > off), kept
        #: apart from the ACTIVE state: kv_layout="auto" may only switch to
        #: paged at warmup, and the preference must survive that rebuild
        #: (the active self.prefix_cache is re-derived per _init_kv_state)
        self._prefix_pref = decode_strategy_mod.resolve_prefix_cache(
            prefix_cache, model
        )
        if prefix_cache == "on" and kv_layout != "auto" and \
                resolved not in decode_strategy_mod.PAGED_KV_LAYOUTS:
            raise ValueError(
                "prefix_cache='on' shares pool blocks between requests but "
                f"the KV layout resolved to {resolved!r} — prefix sharing "
                "requires kv_layout='paged' (or 'paged_int8'; dense slots "
                "have no block tables to share)"
            )
        # -- preemption (docs/serving.md "Preemption & priorities") --------
        # optimistic admission is a PAGED property: lazy pages need a block
        # pool to be lazy about. Same loud-reject discipline as prefix
        # sharing when the layout resolves dense.
        if preemption is not None and preemption not in PREEMPTION_MODES:
            raise ValueError(
                f"preemption must be one of {PREEMPTION_MODES}, "
                f"got {preemption!r}"
            )
        if admit_headroom_blocks < 0:
            raise ValueError(
                "admit_headroom_blocks must be >= 0, got "
                f"{admit_headroom_blocks}"
            )
        self.preemption = preemption or "off"
        self.admit_headroom_blocks = int(admit_headroom_blocks)
        if self.preemption != "off" and kv_layout != "auto" and \
                resolved not in decode_strategy_mod.PAGED_KV_LAYOUTS:
            raise ValueError(
                f"preemption={self.preemption!r} admits against the block "
                f"pool but the KV layout resolved to {resolved!r} — lazy "
                "pages need kv_layout='paged' (or 'paged_int8'; dense slots "
                "reserve their worst case by construction)"
            )
        # -- speculative decoding (docs/serving.md "Speculative decoding") -
        # draft/verify bursts are a DECODE property orthogonal to the KV
        # axes: exactness comes from the recompute lanes, so speculation
        # composes with dense, paged, int8, prefix sharing, and preemption
        # alike. Resolution mirrors the other measured axes: explicit arg >
        # PERCEIVER_SPECULATION > measured registry > off; the geometry is
        # validated HERE (greedy-only, draft a strict truncation) so a
        # misconfigured operator fails at construction, never mid-serving.
        if speculation is not None and \
                speculation not in decode_strategy_mod.SPECULATION_MODES:
            raise ValueError(
                "speculation must be one of "
                f"{decode_strategy_mod.SPECULATION_MODES}, got {speculation!r}"
            )
        self.speculation_requested = speculation
        self.speculation = decode_strategy_mod.resolve_speculation(
            speculation, model
        )
        self._spec = speculative_mod.parse_speculation(self.speculation)
        if self._spec is not None:
            speculative_mod.validate_spec(self._spec, model, self.config)
        if swap_link_gbps is not None and swap_link_gbps <= 0:
            raise ValueError(
                f"swap_link_gbps must be > 0, got {swap_link_gbps}"
            )
        #: modeled host-link bandwidth (decimal GB/s) for the preemption
        #: post-mortems' swap cost and the auto policy's per-victim
        #: arbitration. Resolution: explicit arg > the calibrated
        #: per-platform registry entry (``swap_entries`` in the strategy
        #: artifact — every real swap feeds a measured rate back through
        #: ``record_swap_gbps``) > a 16 GB/s prior. ROADMAP item 2's
        #: recompute-vs-swap crossover is measured against this rate.
        self.swap_link_gbps = float(
            swap_link_gbps
            if swap_link_gbps is not None
            else decode_strategy_mod.lookup_swap_gbps() or 16.0
        )
        #: preemption accounting: tier -> victims preempted at that tier
        #: (the kv_preemptions_total by-tier breakdown stats() reports)
        self._preempted_by_tier: Dict[int, int] = {}
        #: per-victim preemption post-mortems (docs/observability.md
        #: "Scheduler timeline & post-mortems"): actual recompute cost
        #: (tokens replayed x measured decode-step ms) vs the modeled
        #: host-swap cost (victim bytes / swap_link_gbps). Bounded ring;
        #: the running totals survive eviction.
        self._postmortems: Deque[dict] = deque(maxlen=256)
        self._postmortem_totals = {
            "count": 0, "swapped": 0, "tokens_discarded": 0,
            "pages_released": 0, "victim_bytes": 0,
            "recompute_est_ms": 0.0, "swap_est_ms": 0.0,
            "swap_measured_ms": 0.0,
        }
        #: per-tenant attribution (sanitized labels — observability.
        #: tenant_label): tokens generated and victims preempted; resident
        #: pool pages come live from _tenant_pages()
        self._tokens_by_tenant: Dict[str, int] = {}
        self._preempted_by_tenant: Dict[str, int] = {}
        self._tenant_gauge_keys: set = set()
        self._preempts_this_step = 0
        self._kv_counter_base = {"allocs": 0, "frees": 0}
        self._kv_waiting_id: Optional[int] = None  # last head counted waiting
        #: request_id -> host-side SwapBundle for swap-preempted victims
        #: awaiting readmission (docs/serving.md "Host-swap preemption");
        #: must exist before _init_kv_state (the rebuild path drops them)
        self._swap_bundles: Dict[int, SwapBundle] = {}
        self._init_kv_state(resolved)
        self._update_slot_gauges()

    def _pages_per_slot(self) -> int:
        """Block-table width: pages covering one slot's full context."""
        return -(-self.model.max_seq_len // self.kv_block_size)

    def _pool_tokens(self) -> int:
        """Device pool length in token positions: the usable blocks plus
        block 0, the null/trash block (``serving/kv_pool.py``)."""
        return (self.kv_blocks + 1) * self.kv_block_size

    # -- KV state/pool lifecycle --------------------------------------------
    def _init_kv_state(self, layout: str) -> None:
        """(Re)build the persistent device state and host allocator for
        ``layout`` ("dense" | "paged" | "paged_int8") and publish the
        capacity/resident gauges. Also the warmup-time layout-switch path
        (an explicit ``kv_layout="auto"`` re-resolving after the
        autotuner) — callers must guarantee no residents."""
        from perceiver_io_tpu.ops.ragged_attention import trace_env

        # swapped-out bundles reference the OUTGOING pool's shared blocks
        # and its device content — a rebuild invalidates both, so drop them
        # while the old pool can still absorb the derefs (the queued
        # requests replay from their prompts: still token-identical)
        if getattr(self, "_swap_bundles", None) and \
                getattr(self, "_pool", None) is not None:
            for bundle in self._swap_bundles.values():
                self._release_bundle(bundle, cause="swapped")
            self._swap_bundles.clear()
        model, params = self.model, self.params
        self.kv_layout = layout
        if self.sharding is not None and self.sharding.model_size > 1:
            _, cache_shapes = _prefill_shapes(model, params)
            heads = int(cache_shapes["cross_k"].shape[1])
            if heads % self.sharding.model_size:
                raise ValueError(
                    f"attention heads ({heads}) must divide evenly over the "
                    f"mesh model axis ({self.sharding.model_size}): the KV "
                    "caches and head projections are head-sharded — shrink "
                    "the model axis or pad the head count"
                )
        if layout in decode_strategy_mod.PAGED_KV_LAYOUTS:
            self._pool: Optional[KVPagePool] = KVPagePool(
                self.kv_blocks, self.kv_block_size, self.slots, model.max_seq_len
            )
            self._state = self._place_state(_blank_state(
                model, params, self.slots, self.config.pad_token_id,
                pool_tokens=self._pool_tokens(),
                quantized=(layout == "paged_int8"),
            ))
            self._table_dev = self._place_table(self._pool.table())
            # a state rebuild zeroes the device pool, so the prefix index
            # starts (over) empty — stale entries must not describe pages
            # that no longer hold their values. The ACTIVE state re-derives
            # from the resolved preference here, so a warmup-time
            # auto-layout switch onto paged turns sharing on rather than
            # inheriting a stale off from the dense __init__ resolution.
            self.prefix_cache = "on" if self._prefix_pref == "on" else "off"
            self._prefix_index: Optional[PrefixBlockIndex] = (
                PrefixBlockIndex(self.kv_block_size)
                if self.prefix_cache == "on" else None
            )
        else:
            self._pool = None
            self._prefix_index = None
            self.prefix_cache = "off"
            self._state = self._place_state(_blank_state(
                model, params, self.slots, self.config.pad_token_id
            ))
            self._table_dev = None
        #: trace environment the cached prefix blocks were written under:
        #: a mid-process flip rebuilds every executor (``_cache_key``), and
        #: the index flushes with them rather than carry blocks across
        self._prefix_env = trace_env()
        # analytic worst-case slot-KV footprint: per-position byte cost
        # computed from the RESOLVED layout's pool dtype (int8 pools store
        # 1-byte entries plus f32 per-(position, head) dequant scales —
        # pretending bf16/f32 here would overstate capacity 2-4x and admit
        # too little) + the dense latent-stack caches — exact on every
        # backend, device memory_stats() or not (docs/observability.md)
        _, cache_s = _prefill_shapes(model, params)
        _, h, n, d = cache_s["cross_k"].shape
        pool_dtype = (
            self._state["pool_k"].dtype if self._pool is not None
            else cache_s["cross_k"].dtype
        )
        itemsize = jnp.dtype(pool_dtype).itemsize
        self._kv_token_bytes = 2 * h * d * itemsize  # k + v, per position
        #: int8 layouts carry one f32 scale per (position, head) per tensor;
        #: zero for exact layouts so downstream sums stay layout-agnostic
        self._kv_scale_token_bytes = (
            2 * h * jnp.dtype(jnp.float32).itemsize
            if "scale_k" in self._state else 0
        )
        self._kv_stack_bytes = sum(
            int(leaf.nbytes)
            for name in ("stack_k", "stack_v")
            for leaf in self._state[name]
        )
        if self._pool is not None:
            # paged capacity is what the POOL can hold (operators size it
            # via kv_blocks), not the dense worst case
            self._kv_capacity_bytes = (
                self.kv_blocks * self.kv_block_size
                * (self._kv_token_bytes + self._kv_scale_token_bytes)
                + self._kv_stack_bytes
            )
        else:
            self._kv_capacity_bytes = (
                self.slots * n * self._kv_token_bytes + self._kv_stack_bytes
            )
        self.registry.set_gauge("kv_cache_capacity_bytes", self._kv_capacity_bytes)
        if self._pool is not None:
            self.registry.set_gauge("kv_pool_blocks", self._pool.num_blocks)
            self.registry.set_gauge(
                "kv_pool_block_bytes", self.kv_block_size * self._kv_token_bytes
            )
            self.registry.set_gauge(
                "kv_pool_block_scale_bytes",
                self.kv_block_size * self._kv_scale_token_bytes,
            )
        from perceiver_io_tpu.ops import ragged_attention as ragged_mod
        self.registry.set_gauge(
            "kv_ragged_kernel_enabled",
            1 if (self._pool is not None and ragged_mod.kernel_requested()) else 0,
        )
        if self.sharding is not None:
            # mesh geometry gauges (docs/observability.md): presence of
            # serving_mesh_devices is how `obs report` knows a mesh ran
            self.registry.set_gauge(
                "serving_mesh_devices", self.sharding.num_devices
            )
            self.registry.set_gauge("serving_mesh_data", self.sharding.data_size)
            self.registry.set_gauge("serving_mesh_model", self.sharding.model_size)
        self._update_kv_gauges()

    def _update_kv_gauges(self) -> None:
        """Publish the LIVE KV footprint: under the paged layout,
        ``kv_cache_resident_bytes`` counts allocated pages (+ the dense
        stack caches), updated on admit/retire/chunk progress; dense keeps
        resident == capacity (every slot row exists whether occupied or
        not). Pool gauges/counters ride along (docs/observability.md)."""
        from perceiver_io_tpu.observability import default_ledger

        pool = self._pool
        if pool is None:
            resident = self._kv_capacity_bytes
        else:
            resident = (
                pool.in_use * self.kv_block_size
                * (self._kv_token_bytes + self._kv_scale_token_bytes)
                + self._kv_stack_bytes
            )
            self.registry.set_gauge("kv_pool_blocks_in_use", pool.in_use)
            self.registry.set_gauge("kv_pool_blocks_reserved", pool.reserved)
            self.registry.set_gauge("kv_pool_blocks_high_water", pool.high_water)
            # distance to the next boundary-crossing PoolExhausted under
            # optimistic admission (docs/serving.md "Preemption &
            # priorities") — free blocks no hard reservation has claimed
            self.registry.set_gauge(
                "kv_pool_headroom_blocks", pool.headroom_blocks
            )
            if self._prefix_index is not None:
                self.registry.set_gauge(
                    "kv_prefix_cached_blocks", self._prefix_index.cached_blocks
                )
            # per-tenant attribution (docs/observability.md "Scheduler
            # timeline & post-mortems"): resident pool pages per tenant,
            # published as one gauge per (sanitized) tenant label. Gauges
            # for tenants that no longer hold pages drop to 0 rather than
            # lingering at their last value.
            live: Dict[str, int] = {}
            for tenant, held in self._tenant_pages().items():
                key = tenant_label(tenant)
                live[key] = live.get(key, 0) + held
            for key, held in live.items():
                self.registry.set_gauge(
                    f"kv_pool_tenant_blocks_in_use_{key}", held
                )
            for key in self._tenant_gauge_keys - set(live):
                self.registry.set_gauge(f"kv_pool_tenant_blocks_in_use_{key}", 0)
            self._tenant_gauge_keys |= set(live)
            base = self._kv_counter_base
            if pool.allocs_total > base["allocs"]:
                self.registry.inc(
                    "kv_pool_block_allocs_total", pool.allocs_total - base["allocs"]
                )
                base["allocs"] = pool.allocs_total
            if pool.frees_total > base["frees"]:
                self.registry.inc(
                    "kv_pool_block_frees_total", pool.frees_total - base["frees"]
                )
                base["frees"] = pool.frees_total
        self.registry.set_gauge("kv_cache_resident_bytes", resident)
        if self.sharding is not None:
            # the model-axis shard of the live KV bytes (heads are the
            # sharded dimension of both pool and dense caches); the data
            # axis divides the DENSE layout's slot rows further, but the
            # paged pool — the layout per-shard sizing matters for — is
            # shared across data shards (docs/observability.md)
            self.registry.set_gauge(
                "kv_cache_resident_bytes_per_shard",
                resident // self.sharding.model_size,
            )
        default_ledger().set_kv_cache_bytes(resident)

    def _place_state(self, state: dict) -> dict:
        """Place a freshly built slot state onto the serving mesh (identity
        when unsharded). Every state (re)build routes through here so the
        executors' committed-input signatures never drift."""
        return state if self.sharding is None else self.sharding.put_state(state)

    def _place_table(self, table) -> jnp.ndarray:
        if self.sharding is None:
            return jnp.asarray(table)
        return self.sharding.put_leaf("table", np.asarray(table))

    def _push_table(self) -> None:
        """Refresh the device copy of the block table after the allocator
        changed it (admit/chunk-progress/decode page crossing/retire). A
        (slots, pages) int32 transfer — tiny next to a decode step."""
        self._table_dev = self._place_table(self._pool.table())

    def _kv_release(self, slot: int, cause: str = "retire") -> None:
        """Return a retired/failed slot's pages to the pool and refresh
        gauges + device table. ``cause`` tags the pool's free accounting
        (``frees_by_cause`` in :meth:`KVPagePool.stats`): ordinary
        retirement vs a client-driven ``cancelled`` reclaim — the long-tail
        HBM-leak class the gateway's disconnect path exists to close."""
        if self._pool is not None:
            # push on UNMAP, not on physical free: a refcount-aware release
            # can free zero blocks (every page shared) yet still zero the
            # slot's table row, which the device copy must reflect
            had_pages = self._pool.mapped_blocks(slot) > 0
            self._pool.release(slot, cause=cause)
            if had_pages:
                self._push_table()
            self._update_kv_gauges()

    # -- executors -----------------------------------------------------------
    def _cache_key(self, kind: str, *extra):
        from perceiver_io_tpu.ops.ragged_attention import trace_env

        # max_new_tokens is scheduled host-side (per-request retirement), so
        # it must NOT key the executors — requests overriding it share one
        # compiled program
        cfg = dataclasses.replace(self.config, max_new_tokens=0)
        # the paged pool's device shape (blocks x block size) specializes
        # every executor, so it must key them; dense keys stay identical to
        # the pre-paged ones
        kv = (
            (self.kv_layout, self.kv_block_size, self.kv_blocks)
            if self.kv_layout in decode_strategy_mod.PAGED_KV_LAYOUTS else ()
        )
        # mesh geometry (axis sizes + concrete device ids) specializes every
        # executor — shardings are baked into the compiled program, so a
        # mesh flip must rebuild, never reuse the other geometry's trace
        mesh_fp = () if self.sharding is None else self.sharding.fingerprint()
        return (
            kind, type(self.model).__qualname__, model_fingerprint(self.model),
            cfg, self.slots, trace_env(), *kv, *mesh_fp, *extra,
        )

    def _ledger_components(self, **extra) -> dict:
        """Named cache-key components for the compile ledger — the same
        knobs :meth:`_cache_key` folds into the tuple key, under the names
        retrace attribution diffs (docs/observability.md reason names). Only
        called on a cache MISS (the executor getters pass it as a thunk):
        the model-id hash and config normalization stay off the per-token
        hit path."""
        from perceiver_io_tpu.ops.ragged_attention import trace_env

        cfg = dataclasses.replace(self.config, max_new_tokens=0)
        components = {
            "model": ledger_model_id(self.model),
            "config": cfg,
            "slots": self.slots,
            "trace_env": trace_env(),
            **extra,
        }
        if self.kv_layout in decode_strategy_mod.PAGED_KV_LAYOUTS:
            components["kv_layout"] = (
                f"{self.kv_layout}:{self.kv_blocks}x{self.kv_block_size}"
            )
        if self.sharding is not None:
            components["mesh"] = self.sharding.describe()
        return components

    def _kv_block_size_arg(self) -> Optional[int]:
        return (
            self.kv_block_size
            if self.kv_layout in decode_strategy_mod.PAGED_KV_LAYOUTS else None
        )

    # -- sharded-executor helpers (docs/serving.md "Sharded serving"). All
    # None on the unsharded engine; computed only inside cached_executor's
    # build thunks, so the per-dispatch hit path stays free of tree maps.
    def _state_out_shardings(self):
        if self.sharding is None:
            return None
        return self.sharding.state_shardings(self._state)

    def _decode_out_shardings(self):
        if self.sharding is None:
            return None
        return (
            self.sharding.state_shardings(self._state),
            self.sharding.tokens_sharding(self.slots),
        )

    def _chunk_out_shardings(self):
        if self.sharding is None:
            return None
        _, cache_s = _prefill_shapes(self.model, self.params)
        stage = self.sharding.leaf_sharding("stage_k", cache_s["cross_k"].shape)
        return (stage, stage, self.sharding.state_shardings(self._state))

    def _gather_sharding(self):
        """Constraint for the paged attend's transient dense gather."""
        if self.sharding is None or \
                self.kv_layout not in decode_strategy_mod.PAGED_KV_LAYOUTS:
            return None
        return self.sharding.named(self.sharding.gathered_kv_spec())

    def _prefill_executor(self, bucket_len: int):
        return cached_executor(
            _EXECUTOR_CACHE, self._cache_key("slot_prefill", bucket_len),
            lambda: _build_prefill_executor(
                self.model, self.config, bucket_len, self._kv_block_size_arg(),
                out_shardings=self._state_out_shardings(),
            ),
            ledger_site="slot_prefill",
            ledger_components=lambda: self._ledger_components(
                bucket_shape=f"1x{bucket_len}"
            ),
        )

    def _chunked_prefill_executor(self):
        return cached_executor(
            _EXECUTOR_CACHE,
            self._cache_key("slot_prefill_chunk", self.prefill_chunk),
            lambda: _build_chunked_prefill_executor(
                self.model, self.config, self.prefill_chunk,
                self._kv_block_size_arg(),
                out_shardings=self._chunk_out_shardings(),
            ),
            ledger_site="slot_prefill_chunk",
            ledger_components=lambda: self._ledger_components(
                chunk=self.prefill_chunk
            ),
        )

    def _shared_chunk_size(self) -> int:
        """Staging-chunk size for shared (prefix-cache hit) admissions:
        the configured ``prefill_chunk`` when set — so spread shared
        admissions share the schedule discipline — else a block-scaled
        default (the suffix past a hot prefix is short by construction)."""
        n = self.model.max_seq_len
        return int(self.prefill_chunk or min(n, max(self.kv_block_size, 16)))

    def _shared_prefill_executor(self):
        chunk = self._shared_chunk_size()
        return cached_executor(
            _EXECUTOR_CACHE,
            self._cache_key("slot_prefill_shared", chunk),
            lambda: _build_shared_prefill_executor(
                self.model, self.config, chunk, self.kv_block_size,
                out_shardings=self._state_out_shardings(),
                gather_sharding=self._gather_sharding(),
            ),
            ledger_site="slot_prefill_shared",
            ledger_components=lambda: self._ledger_components(chunk=chunk),
        )

    def _page_copy_executor(self):
        return cached_executor(
            _EXECUTOR_CACHE, self._cache_key("kv_page_copy"),
            lambda: _build_page_copy_executor(
                self.kv_block_size, out_shardings=self._state_out_shardings()
            ),
            ledger_site="kv_page_copy",
            ledger_components=lambda: self._ledger_components(),
        )

    def _swap_extract_executor(self):
        return cached_executor(
            _EXECUTOR_CACHE, self._cache_key("kv_swap_extract"),
            lambda: _build_swap_extract_executor(self.kv_block_size),
            ledger_site="kv_swap_extract",
            ledger_components=lambda: self._ledger_components(),
        )

    def _swap_restore_executor(self):
        return cached_executor(
            _EXECUTOR_CACHE, self._cache_key("kv_swap_restore"),
            lambda: _build_swap_restore_executor(
                self.kv_block_size, out_shardings=self._state_out_shardings()
            ),
            ledger_site="kv_swap_restore",
            ledger_components=lambda: self._ledger_components(),
        )

    def _boundary_mode(self) -> str:
        """Resolved boundary-phase strategy for the mixed decode variant
        (``decode_strategy`` ctor arg > env var > measured registry >
        cached), **pinned at first use**. Under recompute the resident
        boundary rows' cross caches are deliberately left stale, so a
        mid-serving registry change (a late autotune, a strategy file
        appearing) must not swap the executor under them — a fresh verdict
        applies from the next :meth:`warmup` (no residents there), not
        mid-flight. Pinning also keeps the per-token host path free of the
        env/file/fingerprint lookups ``resolve`` performs."""
        if self._pinned_boundary_mode is None:
            self._pinned_boundary_mode = decode_strategy_mod.resolve(
                self.decode_strategy, self.model
            ).boundary
        return self._pinned_boundary_mode

    def _decode_executor(self, boundary: bool):
        mode = self._boundary_mode() if boundary else "cached"
        return cached_executor(
            _EXECUTOR_CACHE, self._cache_key("slot_decode", boundary, mode),
            lambda: _build_decode_executor(
                self.model, self.config, boundary, mode,
                self._kv_block_size_arg(),
                out_shardings=self._decode_out_shardings(),
                gather_sharding=self._gather_sharding(),
            ),
            ledger_site="slot_decode",
            ledger_components=lambda: self._ledger_components(
                boundary=boundary, decode_strategy=mode
            ),
        )

    def _spec_cand_sharding(self):
        """Sharding for the draft executor's ``(slots, k+1)`` candidate
        block: slots along ``data`` like every per-row state leaf."""
        if self.sharding is None:
            return None
        return self.sharding.leaf_sharding(
            "window", (self.slots, self._spec.k + 1)
        )

    def _spec_draft_executor(self):
        spec = self._spec
        return cached_executor(
            _EXECUTOR_CACHE, self._cache_key("spec_draft", spec.mode),
            lambda: _build_spec_draft_executor(
                self.model, self.config, spec,
                out_shardings=self._spec_cand_sharding(),
            ),
            ledger_site="spec_draft",
            ledger_components=lambda: self._ledger_components(
                speculation=spec.mode
            ),
        )

    def _spec_verify_executor(self):
        spec = self._spec
        return cached_executor(
            _EXECUTOR_CACHE, self._cache_key("spec_verify", spec.mode),
            lambda: _build_spec_verify_executor(
                self.model, self.config, spec,
                out_shardings=self._decode_out_shardings(),
            ),
            ledger_site="spec_verify",
            ledger_components=lambda: self._ledger_components(
                speculation=spec.mode
            ),
        )

    # -- feasibility ---------------------------------------------------------
    def _pick_prompt_bucket(self, length: int, cfg: GenerationConfig) -> int:
        """Bucket choice plus the slot engine's scope checks (module
        docstring); called from ``submit`` so violations reject with a
        terminal span, never mid-schedule."""
        if dataclasses.replace(cfg, max_new_tokens=self.config.max_new_tokens) != self.config:
            raise ValueError(
                "slot engine requests must share the engine GenerationConfig "
                "(only max_new_tokens may differ per request): the decode "
                "executor is compiled once for one sampling/eos/latent plan"
            )
        if cfg.max_new_tokens < 1:
            # the decode loop always advances at least one token; a 0-token
            # request would retire with more emitted tokens than its result
            # can hold
            raise ValueError(
                f"max_new_tokens must be >= 1, got {cfg.max_new_tokens}"
            )
        cap = super()._pick_prompt_bucket(length, cfg)
        if length + cfg.max_new_tokens > self.model.max_seq_len:
            raise ValueError(
                f"prompt length {length} + max_new_tokens "
                f"{cfg.max_new_tokens} overruns the context "
                f"{self.model.max_seq_len}: the sliding-window phase has no "
                "slot form — use the bucket engine for this request"
            )
        if length < min(cap, cfg.num_latents):
            raise ValueError(
                f"prompt length {length} is shorter than the "
                f"{min(cap, cfg.num_latents)} latent positions its prompt "
                f"bucket ({cap}) assigns under num_latents="
                f"{cfg.num_latents}: left pads would occupy latent slots "
                "(boundary-cache precondition) — use the bucket engine for "
                "this request, or configure num_latents at or below the "
                "shortest served prompt"
            )
        return cap

    def check_feasible(self, prompt, config: Optional[GenerationConfig] = None
                       ) -> GenerationConfig:
        """Base feasibility plus KV-pool capacity (docs/serving.md): a
        request whose worst case ``prompt + max_new_tokens`` can NEVER fit
        the configured block pool rejects here — at submit, with its own
        precise reason — instead of camping at the queue head forever. A
        request that fits the pool but not its current free space is NOT
        rejected; it queues and admits when residents retire (counted
        ``kv_pool_admit_waits_total``)."""
        import numpy as np

        cfg = super().check_feasible(prompt, config)
        if self._pool is not None:
            tokens = int(np.asarray(prompt).size) + cfg.max_new_tokens
            need = self._pool.blocks_needed(tokens)
            # NOTE the never-fits bound is deliberately blind to the prefix
            # cache: a request's pages must all be DISTINCT resident blocks
            # simultaneously, shared or not, so sharing cannot relax the
            # single-request capacity. What sharing relaxes is the
            # CONCURRENT accounting — referenced blocks are excluded from
            # each admission's reservation in the scheduler's gate, so
            # hot-prefix residents pack where unshared ones would wait
            # (docs/serving.md "Prefix sharing"; the gate is where
            # feasibility accounts for shareable blocks).
            if need > self._pool.num_blocks:
                # byte figures from the RESOLVED layout's pool dtype (int8
                # positions cost 1 byte + f32 scales, not bf16/f32) so the
                # reason states the pool's TRUE capacity, not an assumed one
                per_block = self._pool.block_size * (
                    self._kv_token_bytes + self._kv_scale_token_bytes
                )
                raise ValueError(
                    f"request needs {need} KV blocks ({tokens} positions at "
                    f"block size {self._pool.block_size}, "
                    f"{need * per_block} bytes as {self.kv_layout!r}) but "
                    f"the pool holds {self._pool.num_blocks} blocks "
                    f"({self._pool.num_blocks * per_block} bytes): it can "
                    "never be admitted — raise kv_blocks "
                    "(--serve.kv_blocks) or route it to the dense layout / "
                    "bucket engine"
                )
        return cfg

    # -- prefix sharing (docs/serving.md "Prefix sharing") -------------------
    def _prefix_plan(self, prompt: np.ndarray,
                     cfg: GenerationConfig) -> Optional[_PrefixPlan]:
        """Match the prompt's leading token ids against the prefix index
        and clamp the usable span to this request's OWN prefix region
        ``[0, L - m0)`` — latent positions are boundary-normalized per
        request and migration rewrites from ``L - m0`` up, so only the
        kv_norm-side prefix is position/token-pure and safely shareable.
        Returns None on a miss (or when the cache is off/empty)."""
        index = self._prefix_index
        if index is None:
            return None
        from perceiver_io_tpu.ops.ragged_attention import trace_env

        env = trace_env()
        if env != self._prefix_env:
            # the executors that wrote these blocks are no longer the
            # ones that would read them
            index.flush(self._pool)
            self._prefix_env = env
            self._update_kv_gauges()
        prompt = np.asarray(prompt).reshape(-1)
        L = int(prompt.size)
        bucket_len = self._pick_prompt_bucket(L, cfg)
        m0 = min(bucket_len, cfg.num_latents)
        prefix_len = L - m0
        bs = self.kv_block_size
        if prefix_len < 1 or not index.cached_blocks:
            return None
        nodes = index.match(prompt)
        max_full = prefix_len // bs
        full = nodes[:max_full]
        shared = len(full) * bs
        partial = None
        room = prefix_len - shared
        if room > 0:
            if len(nodes) > len(full):
                # the next cached block matches fully but straddles this
                # request's latent boundary: COW it, use the leading
                # ``room`` positions, let the finalize rewrite the rest
                partial, extra = nodes[len(full)], room
            else:
                partial, extra = index.best_partial(full, prompt[:prefix_len])
                if extra < 1:
                    partial = None
            if partial is not None:
                shared += extra
        if shared < 1:
            return None
        if self.prefill_chunk is None and \
                prefix_len - shared > 4 * self._shared_chunk_size():
            # small hit, long un-shared suffix, no operator chunk
            # discipline: the shared path would drain the whole suffix
            # inline as many fenced stage calls in ONE step — slower than
            # the single bucket-prefill call a miss dispatches, and a
            # resident-stalling spike. Treat it as a miss; with
            # prefill_chunk set the suffix spreads one chunk per step and
            # any hit pays off.
            return None
        return _PrefixPlan(
            nodes=full, partial=partial, shared_tokens=shared,
            bucket_len=bucket_len, m0=m0, prefix_len=prefix_len,
        )

    def _map_shared_prefix(self, req: ServeRequest, slot: int,
                           plan: _PrefixPlan) -> None:
        """Reserve + map a hit admission's pool pages: the fully-matched
        blocks by reference (excluded from the reservation), the partial
        block shared-then-COW'd (the device page copy runs before any
        write could land), and the worst-case remainder reserved
        privately. Counters + the ``serving.prefix_hit`` span event ride
        here so hit accounting is identical for inline and spread
        admissions."""
        pool = self._pool
        L = int(req.prompt.size)
        self._reserve_admit(
            slot, L, req.config.max_new_tokens, shared_blocks=len(plan.nodes),
            pessimistic=bool(req.preemptions),
        )
        blocks = [node.block for node in plan.nodes]
        if plan.partial is not None:
            blocks.append(plan.partial.block)
        pool.map_shared(slot, blocks)
        if plan.partial is not None:
            old, new = pool.cow(slot, len(plan.nodes), use_reservation=True)
            self._state = self._page_copy_executor()(
                self._state, np.int32(old), np.int32(new)
            )
            self.registry.inc("kv_prefix_cow_copies_total")
        # the shared/COW'd pages may already cover EVERY page this request
        # will ever touch, in which case no later ensure() maps anything —
        # the device table must reflect the new mappings before the first
        # decode gather, so push unconditionally here
        self._push_table()
        self.registry.inc("kv_prefix_hits_total")
        self.registry.inc(
            "kv_prefix_shared_blocks_total",
            len(plan.nodes) + (1 if plan.partial is not None else 0),
        )
        self.registry.inc("kv_prefix_shared_tokens_total", plan.shared_tokens)
        if self.tracer is not None:
            self.tracer.event(
                "serving.prefix_hit", trace_id=req.trace_id, slot=slot,
                shared_tokens=plan.shared_tokens,
                shared_blocks=len(plan.nodes),
                cow=plan.partial is not None,
            )

    def _publish_prefix(self, req: ServeRequest, slot: int) -> None:
        """Publish the admitted row's full prefix blocks into the index
        (first donor wins; already-cached paths are skipped). Runs after
        the prefill finished, so every published page holds final
        kv_norm-side values that the donor's own decode never rewrites
        (migration starts at ``prefix_len``)."""
        index = self._prefix_index
        if index is None:
            return
        cfg = req.config
        L = int(req.prompt.size)
        prefix_len = L - min(self._pick_prompt_bucket(L, cfg), cfg.num_latents)
        count = prefix_len // self.kv_block_size
        if count < 1:
            return
        published = index.insert(
            np.asarray(req.prompt).reshape(-1),
            self._pool.slot_blocks(slot)[:count], self._pool,
        )
        if published:
            self.registry.inc("kv_prefix_published_blocks_total", published)
            self._update_kv_gauges()

    def _evict_for(self, need: int) -> bool:
        """LRU-drop unreferenced cached prefixes until ``need`` blocks are
        reservable — the pool-pressure policy: cached prefixes are a
        best-effort accelerator and must never starve admissions. Returns
        True when the need is now reservable."""
        index = self._prefix_index
        while not self._pool.can_reserve(need):
            if index is None:
                return False
            freed = index.evict_one(self._pool)
            if freed is None:
                return False
            self.registry.inc("kv_prefix_evicted_blocks_total")
            if freed:
                self._update_kv_gauges()
        return True

    def _cow_guard(self, entry: _Slot, next_len: int) -> bool:
        """Write-routing guard: a shared page is NEVER written through.
        Before a decode step, COW any page the step's append/migration
        writes would land on while it is still shared. Structurally
        unreachable under the publish policy (shared spans end before
        ``prefix_len``; writes start at it) — kept as the enforced
        invariant, pinned by a synthetic drill in
        ``tests/test_prefix_cache.py``."""
        if self._prefix_index is None:
            return False
        bs = self.kv_block_size
        pages = {(next_len - 1) // bs}
        if entry.m >= self.model.max_latents:
            mig = next_len - 1 - self.model.max_latents
            if mig >= 0:
                pages.add(mig // bs)
        changed = False
        for page in sorted(pages):
            if self._pool.page_shared(entry.slot, page):
                old, new = self._pool.cow(entry.slot, page)
                self._state = self._page_copy_executor()(
                    self._state, np.int32(old), np.int32(new)
                )
                self.registry.inc("kv_prefix_cow_copies_total")
                changed = True
        return changed

    # -- preemption (docs/serving.md "Preemption & priorities") --------------
    def _reserve_admit(self, slot: int, prompt_tokens: int, max_new: int,
                       *, shared_blocks: int = 0,
                       pessimistic: bool = False) -> None:
        """One admission's pool reservation, policy-routed: the worst case
        up front (``preemption="off"``, or ``pessimistic`` — a replayed
        victim's anti-thrash guarantee) or lazily — prompt pages plus
        ``admit_headroom_blocks``, with ``prompt + max_new`` recorded as a
        soft watermark (:meth:`KVPagePool.reserve_lazy`)."""
        total = prompt_tokens + max_new
        if self.preemption == "off" or pessimistic:
            self._pool.reserve(slot, total, shared_blocks=shared_blocks)
        else:
            self._pool.reserve_lazy(
                slot, prompt_tokens, total,
                headroom=self.admit_headroom_blocks,
                shared_blocks=shared_blocks,
            )

    def _admit_need(self, req: ServeRequest, plan: Optional[_PrefixPlan],
                    bundle: Optional[SwapBundle] = None) -> int:
        """Blocks the admission gate must see reservable before ``req``
        admits: its worst case (minus referenced prefix blocks) under
        up-front reservation, or just its private prompt pages + headroom
        under optimistic admission — the tentpole's capacity win: peak
        concurrency sized by what residents USE, not what they might.

        Forward-progress exception: a request that has ALREADY been
        preempted (``req.preemptions > 0``) re-admits under its full worst
        case. Optimistic readmission livelocks — N long tails each
        re-entering on a 2-block prompt commit evict each other forever,
        nobody keeping decode progress. Pessimistic readmission makes the
        cycle terminate: every preemption moves one request from the
        optimistic class to the guaranteed class, a guaranteed resident's
        ``ensure`` draws only on its own reservation (it can never trip
        exhaustion), and each preemption's beneficiary keeps its tokens —
        so memory preemptions are bounded by the request count.

        A swap-preempted head (``bundle``) re-admits through
        :meth:`_restore_admit`: full worst case (it was preempted, so the
        pessimistic rule applies) minus the bundle's still-referenced
        prefix-shared blocks, which re-map by reference."""
        if bundle is not None:
            shared = len(bundle.shared)
        else:
            shared = len(plan.nodes) if plan is not None else 0
        tokens = int(req.prompt.size) + req.config.max_new_tokens
        total = self._pool.blocks_needed(tokens) - shared
        if self.preemption == "off" or req.preemptions:
            return total
        prompt = self._pool.blocks_needed(int(req.prompt.size)) - shared
        return min(prompt + self.admit_headroom_blocks, total)

    def _tenant_pages(self) -> Dict[Optional[str], int]:
        """Resident pool pages held per tenant (the in-flight chunked
        admission included) — the fairness signal victim selection uses:
        at equal priority, the tenant holding the most pages yields first,
        so one tenant's long tail cannot starve the rest."""
        pages: Dict[Optional[str], int] = {}
        for entry in self._active():
            t = entry.req.tenant
            pages[t] = pages.get(t, 0) + self._pool.mapped_blocks(entry.slot)
        if self._admitting is not None:
            t = self._admitting.req.tenant
            pages[t] = pages.get(t, 0) + self._pool.mapped_blocks(
                self._admitting.slot
            )
        return pages

    def _pick_victim(self, priority_cap: int, *, strict: bool,
                     exclude_slot: int = -1
                     ) -> Optional[Union[_Slot, _ChunkedAdmit]]:
        """Deterministic victim policy over residents AND the in-flight
        chunked admission: never a tier above ``priority_cap`` (above OR AT
        it when ``strict`` — admission-time preemption crosses tiers only,
        "interactive preempts batch, never vice versa"), then
        most-tenant-pages (fairness), most-pages-held (biggest relief),
        fewest-tokens-generated (cheapest replay), newest request."""
        tenant_pages = self._tenant_pages()

        def key(req: ServeRequest, slot: int, generated: int):
            return (
                req.priority,
                -tenant_pages.get(req.tenant, 0),
                -self._pool.mapped_blocks(slot),
                generated,
                -req.request_id,
            )

        def eligible(req: ServeRequest) -> bool:
            if req.priority > priority_cap:
                return False
            return not (strict and req.priority == priority_cap)

        best = None
        best_key = None
        for entry in self._active():
            if entry.slot == exclude_slot or not eligible(entry.req):
                continue
            k = key(entry.req, entry.slot, len(entry.emitted))
            if best_key is None or k < best_key:
                best, best_key = entry, k
        admit = self._admitting
        if admit is not None and admit.slot != exclude_slot \
                and eligible(admit.req):
            k = key(admit.req, admit.slot, 0)
            if best_key is None or k < best_key:
                best = admit
        return best

    def _preempt_victim(self, victim: Union[_Slot, _ChunkedAdmit], *,
                        beneficiary: Optional[int] = None) -> None:
        """Preempt one victim: retire its slot with EVERY page returned
        (a prefix-sharing victim only derefs published blocks, never frees
        them out from under other sharers) and requeue the request as a
        VOLUNTARY replay — status stays ``queued``, no failover-budget
        analog is charged.

        The page disposition is policy-routed per victim. ``recompute``
        discards the pages (``frees_by_cause["preempted"]``) and the
        emitted tokens; greedy re-decoding from the original prompt is
        token-identical (the bar ``tests/test_kv_preemption.py`` pins),
        and stream consumers see ``on_token`` indices restart at 0 on
        replay and dedupe, exactly like a fleet failover. ``swap``
        gathers the pages to a host :class:`SwapBundle` first
        (``frees_by_cause["swapped"]``); readmission restores them and
        decoding RESUMES at the pre-preemption position — same greedy
        tokens, paid in transfer instead of recompute
        (``tests/test_kv_swap.py``). ``auto`` picks per victim from the
        post-mortem cost model — both arms are priced from the SAME
        numbers the post-mortem records, so the policy can never choose
        the arm its own record scores worse. A mid-admission
        (:class:`_ChunkedAdmit`) victim has no finished row to save and
        always recomputes."""
        req = victim.req
        if isinstance(victim, _ChunkedAdmit):
            generated = 0
            self._admitting = None
        else:
            generated = len(victim.emitted)
            self._slots[victim.slot] = None
        pages = self._pool.mapped_blocks(victim.slot)
        # post-mortem cost model (docs/observability.md "Scheduler
        # timeline & post-mortems"), priced BEFORE the disposition so the
        # auto arbitration and the record read identical numbers: the
        # recompute cost the victim would pay (discarded tokens x the
        # measured decode-step ms) against the host-swap cost (victim
        # bytes / the calibrated link rate, one direction) — ROADMAP
        # item 2's crossover curve, measured instead of assumed.
        step_ms = self.registry.percentile("serving_decode_step_ms", 50.0) or 0.0
        victim_bytes = pages * self.kv_block_size * (
            self._kv_token_bytes + self._kv_scale_token_bytes
        )
        recompute_ms = generated * step_ms
        swap_ms = victim_bytes / (self.swap_link_gbps * 1e9) * 1e3
        mode = "recompute"
        if not isinstance(victim, _ChunkedAdmit) and (
            self.preemption == "swap"
            or (self.preemption == "auto" and swap_ms < recompute_ms)
        ):
            mode = "swap"
        if mode == "swap":
            swap_out = self._swap_out(victim)
        else:
            swap_out = None
            self._kv_release(victim.slot, cause="preempted")
        req.preemptions += 1
        req.started_at = None
        self._queue.append(req)  # the priority sort re-orders next pass
        self._preempts_this_step += 1
        self.registry.inc("kv_preemptions_total")
        tier = int(req.priority)
        # per-tier family (ledger's retrace_reason_* naming convention);
        # negative tiers spell the sign out — metric names can't hold '-'
        self.registry.inc(f"kv_preemptions_tier_{tier_label(tier)}_total")
        self._preempted_by_tier[tier] = self._preempted_by_tier.get(tier, 0) + 1
        tkey = tenant_label(req.tenant)
        self._preempted_by_tenant[tkey] = \
            self._preempted_by_tenant.get(tkey, 0) + 1
        pm = {
            "request_id": req.request_id,
            "tenant": req.tenant,
            "priority": tier,
            "slot": victim.slot,
            "mode": mode,
            # under swap nothing is actually discarded — the field keeps
            # the cost-model input (tokens replay WOULD have re-decoded)
            "tokens_discarded": generated,
            "pages_released": pages,
            "victim_bytes": int(victim_bytes),
            "decode_step_ms": round(step_ms, 3),
            "recompute_est_ms": round(recompute_ms, 3),
            "swap_est_ms": round(swap_ms, 3),
            # positive = swapping out would have been cheaper than replay
            "swap_advantage_ms": round(recompute_ms - swap_ms, 3),
        }
        if swap_out is not None:
            pm["swap_measured_ms"] = round(swap_out["ms"], 3)
        self._postmortems.append(pm)
        totals = self._postmortem_totals
        totals["count"] += 1
        totals["swapped"] += 1 if mode == "swap" else 0
        totals["tokens_discarded"] += generated
        totals["pages_released"] += pages
        totals["victim_bytes"] += int(victim_bytes)
        totals["recompute_est_ms"] += recompute_ms
        totals["swap_est_ms"] += swap_ms
        if swap_out is not None:
            totals["swap_measured_ms"] += swap_out["ms"]
        self._tl_event(
            "preempted", request_id=req.request_id, slot=victim.slot,
            tenant=req.tenant, priority=tier, mode=mode,
            tokens_discarded=generated, pages_released=pages,
            beneficiary=beneficiary,
        )
        self._update_slot_gauges()
        if self.tracer is not None:
            self.tracer.event(
                "serving.preempted", trace_id=req.trace_id, slot=victim.slot,
                priority=tier, tenant=req.tenant, mode=mode,
                pages_released=pages, tokens_discarded=generated,
                beneficiary=beneficiary,
            )
        if self._preempts_this_step == 2 and self.flight_recorder is not None:
            # two victims in ONE scheduling instant = a preemption storm:
            # the pool is thrashing, not absorbing a single long tail —
            # incident-worthy once per step (the recorder's cooldown bounds
            # a sustained storm further)
            pool = self._pool.stats()
            self.flight_recorder.trigger(
                "preemption_storm",
                f"{self._preempts_this_step} residents preempted in one "
                f"step: pool {pool['in_use']}/{pool['blocks']} blocks "
                "in use — sustained memory pressure, not a long tail",
                trace_ids=[req.trace_id] if req.trace_id else [],
                blocks=pool["blocks"],
                blocks_in_use=pool["in_use"],
            )

    def _swap_out(self, victim: _Slot) -> dict:
        """Device half of swap preemption (docs/serving.md "Host-swap
        preemption"): gather the victim's pool pages + row state to host
        numpy, release its blocks (``frees_by_cause["swapped"]``; leading
        prefix-shared blocks are deref'd with ONE bundle retain each, so
        their content stays device-resident), and park the
        :class:`SwapBundle` keyed by request id for readmission. The
        gather runs BEFORE the release — once freed, the private ids may
        be re-allocated by the very next admission. Returns
        ``{"bytes", "ms"}`` (the measured transfer, fed to
        :meth:`_calibrate_swap`)."""
        req = victim.req
        slot = victim.slot
        pool = self._pool
        # copy: release() zeroes the live table row under us
        row = np.array(pool.table_row(slot))
        t0 = self._clock()
        out = self._swap_extract_executor()(
            self._state, jnp.asarray(row), np.int32(slot)
        )
        # tree-wide np.asarray both fences the gather and lands it in host
        # memory — the device->host leg of the transfer being measured
        host = jax.tree_util.tree_map(np.asarray, out)
        wall = self._clock() - t0
        shared, private = pool.extract(slot, cause="swapped")
        self._push_table()
        self._update_kv_gauges()
        bytes_moved = int(sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(host)
        ))
        self._swap_bundles[req.request_id] = SwapBundle(
            request_id=req.request_id,
            payload=host,
            shared=shared,
            n_private=len(private),
            tokens=int(req.prompt.size) + len(victim.emitted),
            emitted=list(victim.emitted),
            m=int(victim.m),
            last_token_at=victim.last_token_at,
            bytes_moved=bytes_moved,
        )
        ms = wall * 1e3
        self.registry.inc("kv_swaps_total")
        self.registry.inc("kv_swap_bytes_total", bytes_moved)
        self.registry.observe("kv_swap_ms", ms)
        self._calibrate_swap(bytes_moved, wall)
        self._tl_event(
            "swapped", request_id=req.request_id, slot=slot,
            tenant=req.tenant, pages=len(shared) + len(private),
            shared_blocks=len(shared), bytes=bytes_moved, ms=_round_ms(ms),
        )
        if self.tracer is not None:
            self.tracer.event(
                "serving.swapped", trace_id=req.trace_id, slot=slot,
                pages=len(shared) + len(private), bytes=bytes_moved,
                ms=_round_ms(ms),
            )
        return {"bytes": bytes_moved, "ms": ms}

    def _restore_admit(self, req: ServeRequest, slot: int,
                       bundle: SwapBundle) -> None:
        """Readmit a swapped-out victim WITHOUT prompt replay: re-map its
        bundle into whatever free blocks exist now (pessimistic full
        worst-case reservation — the anti-thrash rule), scatter the host
        payload back through the new block-table row, and resume the
        resident at its pre-preemption position — emitted tokens, latent
        count, and inter-token anchor all restored, so the next decode
        step samples from the exact logits the victim was preempted with
        (greedy token-identity by construction) and its ITL telescopes
        across the swap gap. No new ``admitted`` event and no new
        first-token mark: the request's timeline keeps its original
        admission arc, joined by the ``swapped``/``restored`` legs."""
        pool = self._pool
        t0 = self._clock()
        req.started_at = t0
        self.registry.observe(
            "serving_queue_wait_ms", (t0 - req.submitted_at) * 1e3
        )
        self._note_readmitted(req, slot)
        total = int(req.prompt.size) + req.config.max_new_tokens
        try:
            pool.restore(slot, bundle.shared, total, bundle.tokens)
        except BaseException:
            # reserve raises with the pool untouched (restore's ensure is
            # reservation-backed, infallible) — the caller fails the
            # request, so the bundle's parking retains must drop here or
            # the shared blocks strand allocated forever
            self._release_bundle(bundle, cause="failover")
            raise
        pool.set_owner(slot, tenant_label(req.tenant))
        # the slot now holds its own references on the shared run — drop
        # the bundle's parking retains (live derefs, nothing freed)
        for block in bundle.shared:
            pool.deref(block, cause="swapped")
        self._push_table()
        self._update_kv_gauges()
        t1 = self._clock()
        payload = jax.tree_util.tree_map(jnp.asarray, bundle.payload)
        self._state = self._swap_restore_executor()(
            self._state, payload, jnp.asarray(pool.table_row(slot)),
            np.int32(slot), np.int32(len(bundle.shared)),
        )
        # fence: the host->device leg must finish inside the measurement
        np.asarray(self._state["length"])
        wall = self._clock() - t1
        ms = wall * 1e3
        self.registry.inc("kv_swap_restores_total")
        self.registry.inc("kv_swap_bytes_total", bundle.bytes_moved)
        self.registry.observe("kv_swap_ms", ms)
        self._calibrate_swap(bundle.bytes_moved, wall)
        self._slots[slot] = _Slot(
            req=req, slot=slot, max_new=req.config.max_new_tokens,
            m=int(bundle.m), emitted=list(bundle.emitted),
            last_token_at=bundle.last_token_at,
        )
        self._tl_event(
            "restored", request_id=req.request_id, slot=slot,
            tenant=req.tenant, pages=pool.mapped_blocks(slot),
            shared_blocks=len(bundle.shared), tokens_resident=bundle.tokens,
            bytes=bundle.bytes_moved, ms=_round_ms(ms),
        )
        if self.tracer is not None:
            self.tracer.event(
                "serving.restored", trace_id=req.trace_id, slot=slot,
                pages=pool.mapped_blocks(slot), tokens=bundle.tokens,
                bytes=bundle.bytes_moved, ms=_round_ms(ms),
            )

    def _calibrate_swap(self, bytes_moved: int, seconds: float) -> None:
        """Fold one measured transfer into the live link-rate model and
        the per-platform autotune registry (``swap_entries``, persisted
        beside ``spec_entries``): an exponential half-life keeps the rate
        current without letting one outlier transfer swing the auto
        policy. Zero-duration measurements (FakeClock drills) are skipped
        — deterministic tests keep the configured rate."""
        if seconds <= 0 or bytes_moved <= 0:
            return
        measured = bytes_moved / (seconds * 1e9)
        self.swap_link_gbps = round(
            0.5 * self.swap_link_gbps + 0.5 * measured, 6
        )
        decode_strategy_mod.record_swap_gbps(
            self.swap_link_gbps, bytes_moved=int(bytes_moved),
            last_transfer_ms=round(seconds * 1e3, 3),
        )

    def _release_bundle(self, bundle: SwapBundle, cause: str) -> None:
        """Drop one parked bundle's shared-block retains (its host payload
        goes with it). ``cause`` tags any resulting physical frees — the
        bundle may be the LAST reference to a prefix block whose index
        entry was evicted while the victim waited."""
        if self._pool is None:
            return
        for block in bundle.shared:
            self._pool.deref(block, cause=cause)
        if bundle.shared:
            self._update_kv_gauges()

    def _drop_bundle(self, request_id: int, cause: str) -> None:
        """Invalidate a parked swap bundle when its request leaves the
        queue by any path other than restore (cancel / evacuate /
        failover / chaos) — the zero-leak bar counts bundle retains."""
        bundle = self._swap_bundles.pop(request_id, None)
        if bundle is not None:
            self._release_bundle(bundle, cause=cause)

    def postmortems(self) -> dict:
        """The preemption post-mortem rollup (docs/observability.md
        "Scheduler timeline & post-mortems"): lifetime recompute-vs-swap
        totals plus the last few per-victim records. Public so the flight
        recorder sources it into incident bundles and BENCH's preemption
        probe can diff it per arm; also embedded in
        ``stats()["preemption"]["postmortems"]``."""
        totals = self._postmortem_totals
        return {
            "count": totals["count"],
            "swapped": totals["swapped"],
            "tokens_discarded": totals["tokens_discarded"],
            "pages_released": totals["pages_released"],
            "victim_bytes": totals["victim_bytes"],
            "recompute_est_ms": round(totals["recompute_est_ms"], 3),
            "swap_est_ms": round(totals["swap_est_ms"], 3),
            "swap_measured_ms": round(totals["swap_measured_ms"], 3),
            "swap_advantage_ms": round(
                totals["recompute_est_ms"] - totals["swap_est_ms"], 3
            ),
            "swap_link_gbps": self.swap_link_gbps,
            "swapped_waiting": len(self._swap_bundles),
            "recent": list(self._postmortems)[-8:],
        }

    def _preempt_lower_tier(self, head: ServeRequest) -> bool:
        """Admission-time preemption: a strictly-higher-tier head may
        evict lower tiers to get in ("interactive preempts batch"). Never
        fires within a tier — equal-priority admission waits FIFO, so
        steady same-tier load cannot thrash residents."""
        victim = self._pick_victim(head.priority, strict=True)
        if victim is None:
            return False
        self._preempt_victim(victim, beneficiary=head.request_id)
        return True

    def _reclaim_decode_page(self, entry: _Slot) -> str:
        """A resident crossing a block boundary found the pool dry — make
        room, cheapest first: LRU-drop an unreferenced cached prefix
        block, else preempt a victim at or below the resident's own tier,
        else (every other live request outranks it) the resident YIELDS —
        preempts itself so higher tiers keep their pages. Returns
        ``"reclaimed"`` (caller retries the mapping), ``"yielded"`` (the
        entry is gone; caller skips it), or ``"stuck"`` — structurally
        unreachable while check_feasible bounds single-request need, kept
        loud rather than assumed."""
        index = self._prefix_index
        while index is not None:
            freed = index.evict_one(self._pool)
            if freed is None:
                break
            self.registry.inc("kv_prefix_evicted_blocks_total")
            if freed:
                self._update_kv_gauges()
                return "reclaimed"
        victim = self._pick_victim(
            entry.req.priority, strict=False, exclude_slot=entry.slot
        )
        if victim is not None:
            self._preempt_victim(victim, beneficiary=entry.req.request_id)
            return "reclaimed"
        if self._admitting is not None or len(self._active()) > 1:
            # every other live request is a higher tier: yield this slot
            self._preempt_victim(entry, beneficiary=None)
            return "yielded"
        # forward-progress guarantee: the LAST resident is never preempted
        return "stuck"

    def _note_readmitted(self, req: ServeRequest, slot: int) -> None:
        """Admission-side half of the preempt/replay cycle: count and mark
        the re-admission of a previously-preempted request so its trace
        shows the full preempt -> requeue -> readmit arc."""
        if not req.preemptions:
            return
        self.registry.inc("kv_readmissions_total")
        self._tl_event(
            "readmitted", request_id=req.request_id, slot=slot,
            tenant=req.tenant, preemptions=req.preemptions,
        )
        if self.tracer is not None:
            self.tracer.event(
                "serving.readmitted", trace_id=req.trace_id, slot=slot,
                preemptions=req.preemptions,
            )

    # -- slot lifecycle ------------------------------------------------------
    def _update_slot_gauges(self) -> None:
        active = sum(1 for s in self._slots if s is not None)
        self.registry.set_gauge("serving_slots_active", active)
        self.registry.set_gauge("serving_slots_idle", self.slots - active)

    def _active(self) -> List[_Slot]:
        return [s for s in self._slots if s is not None]

    def pending(self) -> bool:
        return (
            bool(self._queue)
            or self._admitting is not None
            or any(s is not None for s in self._slots)
        )

    def _free_slot(self) -> Optional[int]:
        """Lowest unoccupied slot index, excluding the one reserved by an
        in-flight chunked admission."""
        reserved = self._admitting.slot if self._admitting is not None else -1
        for i, s in enumerate(self._slots):
            if s is None and i != reserved:
                return i
        return None

    def _chunk_eligible(self, req: ServeRequest,
                        plan: Optional[_PrefixPlan] = None) -> bool:
        """True when this request should be admitted chunk-by-chunk: chunked
        prefill is configured and the prompt's prefix spans more than one
        chunk (shorter prefixes gain nothing over the single-call bucket
        prefill, which stays the fast path for them). A prefix-cache hit
        shrinks the staged span to the UN-shared suffix — a hot prefix
        with a short suffix admits in one step even under chunking."""
        if self.prefill_chunk is None:
            return False
        cfg = req.config
        bucket_len = self._pick_prompt_bucket(int(req.prompt.size), cfg)
        prefix_len = int(req.prompt.size) - min(bucket_len, cfg.num_latents)
        if plan is not None:
            prefix_len -= plan.shared_tokens
        return prefix_len > self.prefill_chunk

    def _admit(self, req: ServeRequest, slot: int,
               plan: Optional[_PrefixPlan] = None) -> None:
        if plan is not None:
            # prefix-cache hit whose suffix fits one step: run the whole
            # shared admission (mapping, staged suffix chunks, finalize)
            # inline through the chunked-admit machinery — one code path
            # for inline and spread shared admissions. _start_chunked_admit
            # runs the FIRST executor call itself, so it sits inside the
            # try: a fault anywhere in the drain must clear the admission
            # record before step()'s prefill-fault handler rebuilds state,
            # or the next step() would advance a dead admission and
            # double-finish the request.
            try:
                self._start_chunked_admit(req, slot, plan)
                while self._admitting is not None:
                    self._advance_chunked_admit()
            except Exception:
                self._admitting = None
                raise  # step()'s prefill-fault handler releases via _fail_resident
            return
        cfg = req.config
        bucket_len = self._pick_prompt_bucket(int(req.prompt.size), cfg)
        ids = np.full((1, bucket_len), cfg.pad_token_id, np.int32)
        ids[0, bucket_len - req.prompt.size:] = req.prompt
        pad = np.asarray([bucket_len - req.prompt.size], np.int32)
        executor = self._prefill_executor(bucket_len)
        t0 = self._clock()
        # queue wait ends when the prefill STARTS (the bucket engine's
        # batch-assembly convention) — prefill time is its own histogram,
        # not queue wait
        req.started_at = t0
        self.registry.observe("serving_queue_wait_ms", (t0 - req.submitted_at) * 1e3)
        self._note_readmitted(req, slot)
        if self._pool is not None:
            # the scheduler's admission gate verified capacity; reserve the
            # worst case (or, under preemption, just the prompt + headroom —
            # except for a replayed victim, which re-admits pessimistically
            # so it can never be re-evicted by exhaustion) and map the
            # prompt's pages (decode steps map the rest page-by-page as
            # positions fill)
            self._reserve_admit(slot, int(req.prompt.size), cfg.max_new_tokens,
                                pessimistic=bool(req.preemptions))
            self._pool.set_owner(slot, tenant_label(req.tenant))
            self._pool.ensure(slot, int(req.prompt.size))
            self._push_table()
            self._update_kv_gauges()
            self._state = executor(
                self._exec_params, jnp.asarray(ids), jnp.asarray(pad),
                np.int32(slot), jnp.asarray(self._pool.table_row(slot)),
                self._state,
            )
        else:
            self._state = executor(
                self._exec_params, jnp.asarray(ids), jnp.asarray(pad),
                np.int32(slot), self._state,
            )
        # fetch one (tiny) output leaf: the executor is a single XLA program,
        # so this fences the whole prefill — without it, async dispatch (TPU)
        # would record ~0 here and bleed the real prefill cost into the next
        # decode step's histogram (same sync discipline as the bucket
        # engine's np.asarray before timing)
        np.asarray(self._state["length"])
        prefill_ms = (self._clock() - t0) * 1e3
        self.registry.observe("serving_prefill_ms", prefill_ms)
        self.registry.inc("serving_prefills_total")
        self.registry.inc("serving_prompt_tokens_real_total", int(req.prompt.size))
        self.registry.inc("serving_prompt_tokens_padded_total", bucket_len)
        self._slots[slot] = _Slot(
            req=req, slot=slot, max_new=cfg.max_new_tokens,
            m=min(bucket_len, cfg.num_latents),
        )
        self._tl_event(
            "admitted", request_id=req.request_id, slot=slot,
            tenant=req.tenant, priority=req.priority, chunks=0,
        )
        if self.tracer is not None:
            self.tracer.event(
                "serving.slot_assigned", trace_id=req.trace_id, slot=slot,
                bucket=bucket_len, prefill_ms=round(prefill_ms, 3),
            )
        if self._prefix_index is not None:
            self.registry.inc("kv_prefix_misses_total")
            self._publish_prefix(req, slot)

    def _start_chunked_admit(self, req: ServeRequest, slot: int,
                             plan: Optional[_PrefixPlan] = None) -> None:
        """Begin a chunked admission into ``slot``: build the row's window
        and chunk schedule host-side, allocate the batch-1 staging caches,
        and run the first chunk call (queue wait ends here — the bucket
        engine's prefill-starts convention). Subsequent chunks advance one
        per ``step()`` until the final call inserts the finished row.

        With a prefix-cache ``plan`` the admission is SHARED: cached
        blocks map by reference up front, the chunk schedule covers only
        the un-shared suffix ``[shared_tokens, prefix_len)``, staging goes
        straight into the slot's private pool pages through the shared
        prefill executor (no batch-1 staging caches), and a fully-hot
        prefix schedules zero chunks — just the finalize."""
        cfg = req.config
        n = self.model.max_seq_len
        L = int(req.prompt.size)
        bucket_len = self._pick_prompt_bucket(L, cfg)
        m0 = min(bucket_len, cfg.num_latents)
        window = np.full((1, n), cfg.pad_token_id, np.int32)
        window[0, n - L:] = req.prompt
        by_index = np.full((n,), cfg.pad_token_id, np.int32)
        by_index[:L] = req.prompt
        C = self._shared_chunk_size() if plan is not None else self.prefill_chunk
        # chunk starts cover the (un-shared) prefix token indices; starts
        # are clamped so a fixed-size chunk never runs past the cache (an
        # overrunning chunk re-covers earlier positions with identical
        # values — routed to the null block on the shared path — and
        # latent/future positions it grazes are overwritten by the
        # finalize / masked by length)
        start = plan.shared_tokens if plan is not None else 0
        if plan is not None:
            offsets = [min(o, n - C) for o in range(start, plan.prefix_len, C)]
        else:
            offsets = [min(o, n - C) for o in range(0, max(L - m0, 1), C)]
        t0 = self._clock()
        req.started_at = t0
        self.registry.observe("serving_queue_wait_ms", (t0 - req.submitted_at) * 1e3)
        self._note_readmitted(req, slot)
        stage_k = stage_v = None
        if self._pool is not None:
            self._pool.set_owner(slot, tenant_label(req.tenant))
        if plan is not None:
            # shared path: map the hit's pages (reserve excludes the
            # referenced blocks; the partial block COWs before any write)
            self._map_shared_prefix(req, slot, plan)
            self._update_kv_gauges()
        elif self._pool is not None:
            # worst-case (or lazy prompt-sized) reservation up front (the
            # admission gate checked capacity); pages map chunk-by-chunk as
            # the staged prefix grows
            self._reserve_admit(slot, L, cfg.max_new_tokens,
                                pessimistic=bool(req.preemptions))
            self._update_kv_gauges()
        if plan is None:
            _, cache_s = _prefill_shapes(self.model, self.params)
            stage_k = jnp.zeros(cache_s["cross_k"].shape, cache_s["cross_k"].dtype)
            stage_v = jnp.zeros(cache_s["cross_v"].shape, cache_s["cross_v"].dtype)
            if self.sharding is not None:
                # committed placement matching the chunk executor's pinned
                # output shardings — the first chunk call's input signature
                # must equal every later call's (AOT strictness)
                stage_k = self.sharding.put_leaf("stage_k", stage_k)
                stage_v = self.sharding.put_leaf("stage_v", stage_v)
        self._admitting = _ChunkedAdmit(
            req=req, slot=slot, bucket_len=bucket_len, m0=m0,
            window=window, pad=np.asarray([n - L], np.int32),
            by_index=by_index, offsets=offsets, chunk=C,
            stage_k=stage_k, stage_v=stage_v,
            plan=plan, lo=start,
            hi=plan.prefix_len if plan is not None else 0,
        )
        self._advance_chunked_admit()

    def _advance_chunked_admit(self) -> None:
        """Run the in-flight admission's next call: one staging chunk per
        ``step()``, then a pure finalize call (latent k/v + attend + stack,
        row inserted into the slot state). The finalize is its own call —
        not folded into the last chunk — so the admission's worst per-step
        stall is max(one chunk, one finalize), each well under the one-shot
        prefill."""
        admit = self._admitting
        req = admit.req
        C = admit.chunk
        i = admit.next_chunk
        final = i == len(admit.offsets)
        # the finalize branch ignores tokens/offset; reuse the first chunk's
        # slice so the call signature stays uniform
        off = 0 if final else admit.offsets[i]
        tokens = jnp.asarray(admit.by_index[off:off + C][None, :])
        if self._pool is not None:
            # "allocated on chunked-prefill progress": map the pages this
            # call's positions cover — every staged chunk extends the live
            # footprint; the finalize needs the whole prompt mapped before
            # its pool scatter. Shared admissions' referenced pages are
            # already in the table; ensure only extends past them.
            L = int(req.prompt.size)
            covered = L if final else min(off + C, L)
            if self._pool.ensure(admit.slot, covered):
                self._push_table()
            self._update_kv_gauges()
            table_row = jnp.asarray(self._pool.table_row(admit.slot))
        else:
            table_row = jnp.zeros((self._pages_per_slot(),), jnp.int32)
        t0 = self._clock()
        if admit.plan is not None:
            # shared admission: stage straight into the slot's private pool
            # pages; [lo, hi) bounds the writable span so shared pages are
            # never written through
            self._state = self._shared_prefill_executor()(
                self._exec_params, tokens, np.int32(off), np.bool_(final),
                jnp.asarray(admit.window), jnp.asarray(admit.pad),
                np.int32(admit.m0), np.int32(admit.slot), table_row,
                np.int32(admit.lo), np.int32(admit.hi), self._state,
            )
            # fence (host value fetch): the state dict is this program's
            # output, so one tiny leaf fences the whole call
            np.asarray(self._state["length"])
        else:
            executor = self._chunked_prefill_executor()
            admit.stage_k, admit.stage_v, self._state = executor(
                self._exec_params, tokens, np.int32(off), np.bool_(final),
                jnp.asarray(admit.window), jnp.asarray(admit.pad),
                np.int32(admit.m0), np.int32(admit.slot), table_row,
                admit.stage_k, admit.stage_v, self._state,
            )
            # fence the call (host value fetch — same sync discipline as the
            # bucket prefill path) so the chunk/stall histograms are real
            if final:
                np.asarray(self._state["length"])
            else:
                np.asarray(admit.stage_k[0, 0, 0, 0])
        chunk_ms = (self._clock() - t0) * 1e3
        admit.device_ms += chunk_ms
        admit.next_chunk += 1
        # the ms histogram covers every call (the finalize's stall is part of
        # the max(chunk, finalize) bound); the chunk counter covers staging
        # calls only, so it totals the per-admission serving_prefill_chunks
        self.registry.observe("serving_prefill_chunk_ms", chunk_ms)
        if not final:
            self.registry.inc("serving_prefill_chunks_total")
        self._tl_event(
            "chunks", request_id=req.request_id, slot=admit.slot,
            chunk=i, final=final, ms=round(chunk_ms, 3),
        )
        if self.tracer is not None:
            self.tracer.event(
                "serving.prefill_chunk", trace_id=req.trace_id, slot=admit.slot,
                chunk=i, offset=off, final=final, ms=round(chunk_ms, 3),
            )
        if final:
            self._admitting = None
            self.registry.observe("serving_prefill_ms", admit.device_ms)
            self.registry.observe("serving_prefill_chunks", len(admit.offsets))
            self.registry.inc("serving_prefills_total")
            self.registry.inc(
                "serving_prompt_tokens_real_total", int(req.prompt.size)
            )
            self.registry.inc(
                "serving_prompt_tokens_padded_total", admit.bucket_len
            )
            self._slots[admit.slot] = _Slot(
                req=req, slot=admit.slot, max_new=req.config.max_new_tokens,
                m=admit.m0,
            )
            self._tl_event(
                "admitted", request_id=req.request_id, slot=admit.slot,
                tenant=req.tenant, priority=req.priority,
                chunks=len(admit.offsets),
            )
            if self.tracer is not None:
                self.tracer.event(
                    "serving.slot_assigned", trace_id=req.trace_id,
                    slot=admit.slot, bucket=admit.bucket_len,
                    prefill_ms=round(admit.device_ms, 3),
                    chunks=len(admit.offsets),
                )
            if self._prefix_index is not None:
                if admit.plan is None:
                    self.registry.inc("kv_prefix_misses_total")
                # publish this row's full prefix blocks (a hit publishes
                # its EXTENSION blocks — conversation-history growth)
                self._publish_prefix(req, admit.slot)

    def _retire(self, entry: _Slot, status: str, *, error: Optional[str] = None,
                kv_cause: Optional[str] = None) -> None:
        if status == "ok":
            pad_id = entry.req.config.pad_token_id
            out = np.full((entry.max_new,), pad_id, np.int32)
            out[: len(entry.emitted)] = entry.emitted
            entry.req.result = out
        self._finish(entry.req, status, error=error)
        self._slots[entry.slot] = None
        # pool free-cause names (kv_pool.frees_by_cause): client-driven
        # reclaim, engine-fault reclaim, and fleet scale-down evacuation
        # (kv_cause override) stay separable from ordinary
        # EOS/max_new/deadline churn
        cause = kv_cause or {
            "cancelled": "cancelled", "failed": "failover",
        }.get(status, "retire")
        self._kv_release(entry.slot, cause=cause)
        if self.tracer is not None:
            self.tracer.event(
                "serving.slot_retired", trace_id=entry.req.trace_id,
                slot=entry.slot, status=status, decode_steps=len(entry.emitted),
            )

    def _fail_resident(self, error: str) -> int:
        """Executor-level fault: every resident request fails, the queue
        survives, and the (possibly donated-away) device state is rebuilt."""
        failed = 0
        for entry in self._active():
            self._retire(entry, "failed", error=error)
            failed += 1
        if self._pool is not None:
            # parked swap bundles reference pool blocks about to be blanked
            # — their queued requests fall back to replay-from-prompt
            # (still token-identical), and the retains must drop while the
            # pool's refcounts are still live
            for rid in list(self._swap_bundles):
                self._drop_bundle(rid, cause="failover")
            self._pool.release_all()
            if self._prefix_index is not None:
                # the device pool is about to be blanked: cached prefix
                # blocks would describe zeroed pages — drop them all
                self._prefix_index.flush(self._pool)
            self._push_table()
            self._update_kv_gauges()
            pool_tokens = self._pool_tokens()
        else:
            pool_tokens = None
        self._state = self._place_state(_blank_state(
            self.model, self.params, self.slots, self.config.pad_token_id,
            pool_tokens=pool_tokens,
            quantized=(self.kv_layout == "paged_int8"),
        ))
        self._update_slot_gauges()
        return failed

    # -- cancellation --------------------------------------------------------
    def cancel(self, request_id: int) -> bool:
        """Token-granular cancellation — the gateway's client-disconnect
        retirement route (docs/serving.md "Streaming"). Works at every
        stage of the request lifecycle and reclaims capacity IMMEDIATELY
        (within the current scheduling instant, i.e. before the next
        ``step()`` runs — the zero-leak bar the chaos drill pins):

        - **resident** — the slot retires ``cancelled`` right now: the slot
          frees for the next queued admission and, under the paged layout,
          every pool page (mapped + reserved) returns to the
          :class:`~perceiver_io_tpu.serving.kv_pool.KVPagePool` tagged
          ``cancelled``. Surviving residents are untouched — per-row
          independence means their token streams cannot shift (pinned).
        - **mid chunked admission** — the in-flight admission is dropped
          before its row ever enters the slot state; staging caches are
          garbage-by-construction and the reserved pages return.
        - **queued** — base-class behavior (leaves the queue).

        Exactly one terminal ``serving.request`` span (status
        ``cancelled``) plus one ``serving.cancelled`` event end the trace.
        Returns True when the request was found live."""
        admit = self._admitting
        if admit is not None and admit.req.request_id == request_id:
            self._admitting = None
            self._kv_release(admit.slot, cause="cancelled")
            if self.tracer is not None:
                self.tracer.event(
                    "serving.cancelled", trace_id=admit.req.trace_id,
                    stage="admitting", slot=admit.slot, tokens_emitted=0,
                )
            self._finish(admit.req, "cancelled")
            return True
        for entry in self._active():
            if entry.req.request_id == request_id:
                if self.tracer is not None:
                    self.tracer.event(
                        "serving.cancelled", trace_id=entry.req.trace_id,
                        stage="resident", slot=entry.slot,
                        tokens_emitted=len(entry.emitted),
                    )
                self._retire(entry, "cancelled")
                self._update_slot_gauges()
                return True
        if super().cancel(request_id):
            # a queued swap victim leaves with its parked bundle: the
            # shared-block retains return tagged like every other
            # cancellation reclaim
            self._drop_bundle(request_id, cause="cancelled")
            return True
        return False

    def evacuate(self, cause: str = "scale_down") -> int:
        """Withdraw every live request at once — the fleet scale-down path
        (docs/serving.md "Elasticity"), token-granular: the in-flight
        chunked admission drops (its staging caches are
        garbage-by-construction), every RESIDENT slot retires immediately
        with its pool pages (mapped + reserved) returned tagged ``cause``
        in the pool's ``frees_by_cause`` accounting — the zero-leak bar the
        scale-down drill pins — and queued requests leave through the base
        path. Per-row independence means nothing here could have shifted
        another engine's tokens; the fleet has already replayed this work
        on survivors, token-identical under greedy decoding."""
        evacuated = 0
        admit = self._admitting
        if admit is not None:
            self._admitting = None
            self._kv_release(admit.slot, cause=cause)
            if self.tracer is not None:
                self.tracer.event(
                    "serving.cancelled", trace_id=admit.req.trace_id,
                    stage="admitting", slot=admit.slot, tokens_emitted=0,
                    cause=cause,
                )
            self._finish(admit.req, "cancelled", error=f"evacuated ({cause})")
            evacuated += 1
        for entry in self._active():
            if self.tracer is not None:
                self.tracer.event(
                    "serving.cancelled", trace_id=entry.req.trace_id,
                    stage="resident", slot=entry.slot,
                    tokens_emitted=len(entry.emitted), cause=cause,
                )
            self._retire(
                entry, "cancelled", error=f"evacuated ({cause})",
                kv_cause=cause,
            )
            evacuated += 1
        # queued swap victims leave through the base path below — their
        # parked bundles' retains return tagged with the evacuation cause
        # (the scale-down drill's zero-leak bar counts them)
        for rid in list(self._swap_bundles):
            self._drop_bundle(rid, cause=cause)
        self._update_slot_gauges()
        return evacuated + super().evacuate(cause)

    def resize_slots(self, new_slots: int) -> int:
        """Grow or shrink the persistent decode state to ``new_slots`` —
        the autoscaler's slot-count elasticity knob (docs/serving.md
        "Elasticity"), riding the SAME rebuild-from-warm-cache path a
        warmup-time kv-layout switch uses: the device state (and, under the
        paged layout, the pool — re-scaled to the new slot count unless the
        operator sized it explicitly) is rebuilt blank via
        ``_init_kv_state``, while the executor caches are process-global —
        a slot count this process has compiled before costs ZERO fresh
        compiles, an unseen one compiles exactly the slot-specialized
        executors (decode pair + chunk/shared variants). Requires an idle
        engine (no residents, no in-flight admission) — resizing under
        traffic would decode residents from zeroed caches; drain or
        evacuate first. Queued requests survive (host-side numpy, no device
        state). Returns the previous slot count."""
        if new_slots < 1:
            raise ValueError(f"slots must be >= 1, got {new_slots}")
        if self.sharding is not None and new_slots % self.sharding.data_size:
            raise ValueError(
                f"slots ({new_slots}) must divide evenly over the mesh "
                f"data axis ({self.sharding.data_size})"
            )
        if any(s is not None for s in self._slots) or self._admitting is not None:
            raise RuntimeError(
                "resize_slots() with requests resident in slots would "
                "corrupt their decode state; drain() or evacuate() first"
            )
        old = self.slots
        if new_slots == old:
            return old
        self.slots = int(new_slots)
        self._slots = [None] * self.slots
        if not self._kv_sized:
            # default pool sizing tracks the slot count (dense-equivalent
            # capacity); an operator-sized pool is a fixed HBM budget and
            # must not silently change under a resize
            self.kv_blocks = self.slots * self._pages_per_slot()
        self._init_kv_state(self.kv_layout)
        self._update_slot_gauges()
        return old

    # -- the token-level scheduler ------------------------------------------
    def step(self) -> int:
        """Advance serving by ONE TOKEN: expire deadlines (queued, resident,
        and mid-admission), advance an in-flight chunked admission by one
        chunk, refill free slots from the queue, run one fixed-shape decode
        step over all slots, and retire rows that just finished
        (EOS / max_new_tokens). Returns the number of requests disposed of
        this call; ``pending()`` — not the return value — says whether more
        work remains (a mid-generation step legitimately disposes of 0).
        """
        return self._run_pass(self._step_pass)

    def _tl_record(self, t0: float, t1: float) -> None:
        """Slot-engine per-pass timeline record: the bucket shape plus the
        slot occupancy vector, real-vs-padded decode rows, KV pool
        occupancy, and per-tenant resident pages."""
        draft, self._tl_draft = self._tl_draft, None
        marks, self._tl_marks = self._tl_marks or {}, None
        phases = {"total": round((t1 - t0) * 1e3, 3)}
        if "admit_done_s" in marks:
            phases["admit"] = round((marks["admit_done_s"] - t0) * 1e3, 3)
        if "decode_ms" in marks:
            phases["decode"] = round(marks["decode_ms"], 3)
        if "token_at_s" in marks:
            phases["account"] = round((t1 - marks["token_at_s"]) * 1e3, 3)
        rec = {
            "engine": "slots",
            "t_start_s": round(t0, 6),
            "t_end_s": round(t1, 6),
            "queue_depth": len(self._queue),
            "slots": [
                None if s is None else s.req.request_id for s in self._slots
            ],
            "phases_ms": phases,
        }
        if "rows_active" in marks:
            active = int(marks["rows_active"])
            rec["rows"] = {
                "total": self.slots, "real": active,
                "padded": self.slots - active,
            }
        if self._pool is not None:
            rec["pool"] = {
                "in_use": self._pool.in_use,
                "reserved": self._pool.reserved,
                "headroom": self._pool.headroom_blocks,
            }
            tenants: Dict[str, int] = {}
            for tenant, held in self._tenant_pages().items():
                key = tenant_label(tenant)
                tenants[key] = tenants.get(key, 0) + held
            if tenants:
                rec["tenants"] = dict(sorted(tenants.items()))
        rec.update(draft or {})
        self.timeline.append(rec)

    def _step_pass(self) -> int:
        disposed = self._expire_overdue()
        if self._swap_bundles:
            # a parked bundle whose request left the queue by a path that
            # bypasses the drop hooks (deadline expiry while queued) must
            # not strand its shared-block retains
            queued = {r.request_id for r in self._queue}
            for rid in [r for r in self._swap_bundles if r not in queued]:
                self._drop_bundle(rid, cause="retire")
        now = self._clock()
        for entry in self._active():
            req = entry.req
            if req.deadline_at is not None and now >= req.deadline_at:
                self._retire(
                    entry, "timed_out",
                    error=f"deadline exceeded after {len(entry.emitted)} of "
                          f"{entry.max_new} tokens",
                )
                disposed += 1
        ran_chunk_call = False
        if self._admitting is not None:
            admit = self._admitting
            req = admit.req
            if req.deadline_at is not None and now >= req.deadline_at:
                self._admitting = None
                self._kv_release(admit.slot)
                self._finish(
                    req, "timed_out",
                    error=f"deadline exceeded after {admit.next_chunk} of "
                          f"{len(admit.offsets)} prefill chunks",
                )
                disposed += 1
            else:
                ran_chunk_call = True
                try:
                    self._advance_chunked_admit()
                except Exception as e:
                    # the slot state was donated into the failed call
                    self._admitting = None
                    self._kv_release(admit.slot)
                    self._finish(req, "failed", error=f"{type(e).__name__}: {e}")
                    return disposed + 1 + self._fail_resident(
                        "chunked-prefill fault poisoned the slot state: "
                        f"{type(e).__name__}: {e}"
                    )
        self._preempts_this_step = 0
        if self._queue and (
            self.preemption != "off" or any(r.priority for r in self._queue)
        ):
            # priority-ordered admission (stable: request_id keeps FIFO
            # within a tier, and puts a preempted request's replay back at
            # its original submission order). Pure-FIFO workloads with the
            # default tier skip the sort entirely — byte-identical cost.
            self._queue.sort(key=lambda r: (-r.priority, r.request_id))
        while self._queue:
            slot = self._free_slot()
            if slot is None:
                break
            head = self._queue[0]
            # a swap-preempted head restores from its parked bundle: no
            # prefix plan (its pages carry the prefix content already) and
            # no chunk lane (restore is one scatter, not a prefill)
            bundle = self._swap_bundles.get(head.request_id)
            plan = None
            if self._pool is not None and bundle is None:
                try:
                    plan = self._prefix_plan(head.prompt, head.config)
                except Exception:
                    plan = None  # infeasible heads fail in _admit as before

            def lane_blocked(plan_now):
                try:
                    is_chunked = self._chunk_eligible(head, plan_now)
                except Exception:
                    is_chunked = False
                # FIFO: both the spread-chunk path and a shared admission's
                # inline drain use the single chunked-admit lane, and the
                # lane runs at most one call per step (a finalize -> first-
                # chunk handoff in one step would stall residents past the
                # documented max(chunk, finalize) bound)
                if (is_chunked or plan_now is not None) and self._admitting is not None:
                    return True, is_chunked
                # an inline shared drain is also lane work: it must not run
                # in the same step the lane already ran a call (finalize ->
                # inline-drain handoff would stall residents past the bound)
                return (
                    (is_chunked or plan_now is not None) and ran_chunk_call,
                    is_chunked,
                )

            # lane check BEFORE the evicting gate: a head that cannot admit
            # this step anyway must not flush cached prefixes to make room
            # it cannot yet use
            blocked, chunked = (
                (False, False) if bundle is not None else lane_blocked(plan)
            )
            if blocked:
                break
            if self._pool is not None:
                # pool admission gate: the head waits (FIFO — later
                # requests must not starve it) until retirements free its
                # worst-case block count. check_feasible already rejected
                # requests that could NEVER fit, so this wait terminates.
                # Counted once per WAITING REQUEST, not per scheduler poll
                # (a long-blocked head is one wait, however many steps it
                # spans). Prefix sharing shrinks the need by the
                # referenced blocks, and under pressure unreferenced
                # cached prefixes LRU-drop BEFORE the head is made to
                # wait; each eviction can invalidate the match, so the
                # plan re-derives until the need is reservable or the
                # cache is dry. Under optimistic admission the need
                # shrinks to the head's PROMPT pages + headroom
                # (_admit_need), and a strictly-higher-tier head may
                # preempt lower tiers to get in ("interactive preempts
                # batch") — equal tiers still wait FIFO, so steady
                # same-tier load cannot thrash residents.
                while True:
                    need = self._admit_need(head, plan, bundle)
                    if self._pool.can_reserve(need):
                        break
                    if not self._evict_for(need) and not (
                        self.preemption != "off"
                        and self._preempt_lower_tier(head)
                    ):
                        break
                    if bundle is not None:
                        continue
                    try:
                        plan = self._prefix_plan(head.prompt, head.config)
                    except Exception:
                        plan = None
                if not self._pool.can_reserve(need):
                    if self._kv_waiting_id != head.request_id:
                        self._kv_waiting_id = head.request_id
                        self.registry.inc("kv_pool_admit_waits_total")
                        if self.flight_recorder is not None:
                            # pool exhaustion is incident-worthy exactly
                            # once per waiting request (the counter's own
                            # once-per-wait discipline), and the recorder's
                            # cooldown bounds a thrashing pool further
                            pool = self._pool.stats()
                            self.flight_recorder.trigger(
                                "pool_exhausted",
                                f"admission stalled: request "
                                f"{head.request_id} needs {int(need)} pool "
                                f"blocks, {pool['blocks'] - pool['reserved']}"
                                f" of {pool['blocks']} unreserved",
                                trace_ids=(
                                    [head.trace_id] if head.trace_id else []
                                ),
                                request_id=head.request_id,
                                blocks_needed=int(need),
                                blocks=pool["blocks"],
                                blocks_reserved=pool["reserved"],
                            )
                    break
                # eviction may have shrunk the plan and flipped the head
                # onto the (busy) chunk lane — re-check before admitting
                blocked, chunked = (
                    (False, False) if bundle is not None else lane_blocked(plan)
                )
                if blocked:
                    break
            req = self._queue.pop(0)
            if self._apply_request_chaos(req):
                self._drop_bundle(req.request_id, cause="failover")
                disposed += 1
                continue
            if bundle is not None:
                del self._swap_bundles[req.request_id]
                try:
                    self._restore_admit(req, slot, bundle)
                except Exception as e:
                    # the restore scatter donates the slot state and may
                    # have half-written the pool —
                    # _fail_resident releases every slot's pages, which
                    # covers whatever pool.restore had re-mapped
                    self._finish(req, "failed", error=f"{type(e).__name__}: {e}")
                    return disposed + 1 + self._fail_resident(
                        "swap-restore fault poisoned the slot state: "
                        f"{type(e).__name__}: {e}"
                    )
                continue
            if chunked:
                try:
                    self._start_chunked_admit(req, slot, plan)
                except Exception as e:
                    # the slot state was donated into the failed call
                    self._admitting = None
                    self._kv_release(slot)
                    self._finish(req, "failed", error=f"{type(e).__name__}: {e}")
                    return disposed + 1 + self._fail_resident(
                        "chunked-prefill fault poisoned the slot state: "
                        f"{type(e).__name__}: {e}"
                    )
                continue
            try:
                self._admit(req, slot, plan)
            except Exception as e:  # prefill fault: this request + residents
                self._finish(req, "failed", error=f"{type(e).__name__}: {e}")
                return disposed + 1 + self._fail_resident(
                    f"prefill fault poisoned the slot state: {type(e).__name__}: {e}"
                )
        self._update_slot_gauges()
        self._tl_mark_clock("admit_done_s")
        active = self._active()
        if not active:
            return disposed

        self._rng, key = jax.random.split(self._rng)
        t0 = self._clock()
        try:
            fault = self._chaos.hit("serving.batch") if self._chaos else None
            if fault is not None and fault.kind == "error":
                raise fault.make_error()
            if self._pool is not None:
                # map the page each active row's NEXT write lands on (a
                # block-boundary crossing maps one fresh block), then
                # refresh the device table. Reservation makes this
                # infallible under preemption="off"; under optimistic
                # admission a dry pool raises PoolExhausted and a victim
                # yields its pages instead (the boundary-crossing preempt
                # path; kv.exhaust chaos scripts that pressure
                # deterministically — consulted once per decode step).
                forced = None
                if self._chaos is not None and self.preemption != "off":
                    forced = self._chaos.hit("kv.exhaust")
                changed = False
                for entry in active:
                    if self._slots[entry.slot] is not entry:
                        continue  # preempted as an earlier row's victim
                    # speculative bursts map this round's WORST-CASE accepted
                    # span up front (atomically — ensure_many), so a
                    # mid-burst boundary crossing can never strand a
                    # half-mapped row; clamped to the request's remaining
                    # budget so a retiring row maps nothing it cannot emit
                    burst = 1 if self._spec is None else max(
                        1, min(self._spec.k + 1,
                               entry.max_new - len(entry.emitted))
                    )
                    next_len = (
                        int(entry.req.prompt.size) + len(entry.emitted) + burst
                    )
                    while True:
                        try:
                            if forced is not None and forced.kind == "error":
                                forced = None
                                raise PoolExhausted(
                                    "chaos: kv.exhaust scripted pool pressure"
                                )
                            if burst > 1:
                                changed |= self._pool.ensure_many(
                                    entry.slot, next_len
                                )
                            else:
                                changed |= self._pool.ensure(entry.slot, next_len)
                            # write-routing invariant: COW any still-shared
                            # page this step's append/migration would write
                            # through
                            changed |= self._cow_guard(entry, next_len)
                            break
                        except PoolExhausted as e:
                            if self.preemption == "off":
                                raise
                            outcome = self._reclaim_decode_page(entry)
                            if outcome == "yielded":
                                break
                            if outcome == "stuck":
                                raise RuntimeError(
                                    "preemption found no victim and no "
                                    "evictable prefix for the sole "
                                    "resident — single-request "
                                    "feasibility was checked at submit: "
                                    f"{e}"
                                ) from e
                if changed:
                    self._push_table()
                    self._update_kv_gauges()
                active = self._active()
                if not active:
                    # every resident yielded this step (an all-preempted
                    # instant): nothing to decode; the requeued replays
                    # admit next step
                    return disposed
            # armed by a serving_decode_step_ms p95 regression on a PRIOR
            # step: this step (dispatch + host-sync fence) runs under the
            # profiler capture; the step-number read (a registry lock) only
            # happens when a capture actually fires
            with self._device_capture(
                step=lambda: int(self.registry.counter("serving_decode_steps_total"))
            ):
                if self._spec is not None:
                    # speculative round: draft then verify, one fixed-shape
                    # dispatch each; the verify's lanes handle latent growth
                    # AND the m == max_latents boundary per row, so no
                    # boundary-variant executor choice exists on this path
                    cand = self._spec_draft_executor()(
                        self._exec_params, self._state
                    )
                    self._state, n_e = self._spec_verify_executor()(
                        self._exec_params, self._state, cand
                    )
                    cand = np.asarray(cand)
                    n_e = np.asarray(n_e)  # host sync: the scheduling point
                else:
                    boundary = any(
                        s.m >= self.model.max_latents for s in active
                    )
                    executor = self._decode_executor(boundary)
                    if self._pool is not None:
                        self._state, tokens = executor(
                            self._exec_params, self._state, self._table_dev, key
                        )
                    else:
                        self._state, tokens = executor(
                            self._exec_params, self._state, key
                        )
                    tokens = np.asarray(tokens)  # host sync: the scheduling point
        except Exception as e:
            self.registry.observe(
                "serving_decode_step_ms", (self._clock() - t0) * 1e3
            )
            return disposed + self._fail_resident(f"{type(e).__name__}: {e}")
        decode_ms = (self._clock() - t0) * 1e3
        self.registry.observe("serving_decode_step_ms", decode_ms)
        self._tl_mark("decode_ms", decode_ms)
        self._tl_mark("rows_active", len(active))
        if self.profiler_trigger is not None:
            self.profiler_trigger.observe(decode_ms)
        self.registry.inc("serving_decode_steps_total")
        if self._pool is not None:
            from perceiver_io_tpu.ops import ragged_attention as ragged_mod
            if ragged_mod.kernel_requested():
                # decode steps served by the ragged paged-attention kernel
                # (vs the gather-to-dense reference) — docs/observability.md
                self.registry.inc("kv_ragged_kernel_steps_total")
        self.registry.inc("serving_decode_rows_total", self.slots)
        self.registry.inc("serving_decode_rows_padded_total", self.slots - len(active))
        eos = self.config.eos_token_id
        # Per-request token-latency accounting (docs/observability.md): the
        # np.asarray fence above materialized every slot's token, so all
        # active rows share this step's completion instant — TTFT for rows
        # that just emitted their first token (submit → that instant, queue
        # wait and prefill included), inter-token latency for the rest
        # (previous token's instant → this one, so a long admission or a
        # boundary-variant step shows up in every RESIDENT row's ITL).
        # A speculative round emits its whole accepted burst at this ONE
        # instant: the burst's first token carries the round's latency,
        # the rest sample 0.0 ms ITL — each emitted token still gets its
        # own sample, so TTFT + Σ ITL telescopes exactly to the stream
        # span, burst or not (pinned under FakeClock).
        token_at = self._clock()
        self._tl_mark("token_at_s", token_at)
        tier_tokens: Dict[str, int] = {}
        tenant_tokens: Dict[str, int] = {}
        emitted_this_step = 0
        for entry in active:
            if self._spec is None:
                row_tokens = [int(tokens[entry.slot])]
            else:
                # accepted burst, truncated host-side at EOS/max_new below
                # exactly as n_e sequential steps would have stopped
                row_tokens = [
                    int(t) for t in cand[entry.slot, : int(n_e[entry.slot])]
                ]
            for token in row_tokens:
                first = not entry.emitted
                entry.emitted.append(token)
                emitted_this_step += 1
                if entry.req.on_token is not None:
                    # incremental streaming: the fence above materialized
                    # this token, so the sink (the gateway's per-stream
                    # queue) gets it the same instant the scheduler does —
                    # burst tokens flush one callback per index, in order
                    self._emit_token(entry.req, len(entry.emitted) - 1, token)
                entry.m = min(entry.m + 1, self.model.max_latents)
                if first:
                    ttft_ms = (token_at - entry.req.ttft_from_s) * 1e3
                    self._observe_token_latency("serving_ttft_ms", ttft_ms)
                    if self.timeline is not None:
                        self._tl_event(
                            "tokens", request_id=entry.req.request_id,
                            slot=entry.slot, first=True,
                            ttft_ms=round(ttft_ms, 3),
                        )
                    if self.tracer is not None:
                        self.tracer.event(
                            "serving.first_token", trace_id=entry.req.trace_id,
                            slot=entry.slot, ttft_ms=round(ttft_ms, 3),
                        )
                else:
                    itl_ms = (token_at - entry.last_token_at) * 1e3
                    self._observe_token_latency("serving_inter_token_ms", itl_ms)
                    if self.timeline is not None:
                        self._tl_event(
                            "tokens", request_id=entry.req.request_id,
                            slot=entry.slot, first=False,
                            itl_ms=round(itl_ms, 3),
                        )
                entry.last_token_at = token_at
                # per-tier / per-tenant token attribution, batched to one
                # registry/dict bump per label per step (hot-path discipline)
                tkey = tier_label(entry.req.priority)
                tier_tokens[tkey] = tier_tokens.get(tkey, 0) + 1
                nkey = tenant_label(entry.req.tenant)
                tenant_tokens[nkey] = tenant_tokens.get(nkey, 0) + 1
                if (eos is not None and token == eos) or len(entry.emitted) >= entry.max_new:
                    self._retire(entry, "ok")
                    disposed += 1
                    break
        self.registry.inc("serving_tokens_generated_total", emitted_this_step)
        if self._spec is not None:
            # acceptance telemetry (docs/observability.md "spec_*"): the
            # measured signal autotune_speculation gates on, and the live
            # regression alarm a fleet watches after enabling speculation
            accepted = int(sum(int(n_e[e.slot]) - 1 for e in active))
            self.registry.inc("spec_rounds_total")
            self.registry.inc(
                "spec_tokens_proposed_total", self._spec.k * len(active)
            )
            self.registry.inc("spec_tokens_accepted_total", accepted)
            self.registry.inc("spec_tokens_emitted_total", emitted_this_step)
            if self.timeline is not None:
                self._tl_event(
                    "spec_round", rows=len(active),
                    proposed=self._spec.k * len(active),
                    accepted=accepted, emitted=emitted_this_step,
                )
        for tkey, n in tier_tokens.items():
            self.registry.inc(f"serving_tokens_tier_{tkey}_total", n)
        for nkey, n in tenant_tokens.items():
            self._tokens_by_tenant[nkey] = \
                self._tokens_by_tenant.get(nkey, 0) + n
        self._update_slot_gauges()
        return disposed

    def run_until_idle(self) -> int:
        served = 0
        while self.pending():
            served += self.step()
        return served

    def drain(self) -> int:
        """Graceful shutdown, token-granular (API parity with
        :meth:`ServingEngine.drain` — the serve CLI and the fleet router's
        rolling restart call one method on either engine instead of
        hand-rolling ``while pending(): step()`` loops): stop accepting
        submissions, then run every QUEUED request, the in-flight chunked
        admission, and every RESIDENT slot to completion — a resident row
        mid-generation finishes its remaining tokens rather than being
        dropped. The base implementation already does the right thing
        through the overridden :meth:`run_until_idle`; this override exists
        to document (and pin, ``tests/test_fleet.py``) the token-granular
        contract. Returns the number of requests disposed of; idempotent."""
        return super().drain()

    # -- ahead-of-time warmup ------------------------------------------------
    def warmup(self, config: Optional[GenerationConfig] = None) -> int:
        """Compile every executor the engine can ever dispatch — one prefill
        per feasible prompt bucket, the decode executor, its boundary
        variant, (when ``prefill_chunk`` is set) the one chunked-prefill
        executor, and (when ``speculation`` is on) the draft + verify pair —
        then wipe the warmup garbage from the slot state.
        Returns the number of fresh executor builds; after it, mixed-length
        traffic compiles nothing (pinned by tests).

        When ``decode_strategy="auto"`` was requested explicitly, the
        boundary autotuner runs first
        (:func:`~perceiver_io_tpu.inference.decode_strategy.autotune_boundary`
        — its cached-vs-recompute probe compiles two small generation
        executors, counted in the return value), so the boundary variant is
        compiled against the measured winner and steady-state traffic never
        retraces."""
        if config is not None and dataclasses.replace(
            config, max_new_tokens=self.config.max_new_tokens
        ) != self.config:
            raise ValueError(
                "slot engine warmup config must match the engine config "
                "(only max_new_tokens may differ)"
            )
        if any(s is not None for s in self._slots) or self._admitting is not None:
            # warmup ends by blanking the device state; doing that under
            # resident requests would silently decode them from zeroed caches
            raise RuntimeError(
                "warmup() with requests resident in slots would corrupt "
                "their decode state; warm up before traffic or after drain()"
            )
        cfg = self.config
        before = executor_cache_stats()["misses"]
        if self.decode_strategy == "auto":
            decode_strategy_mod.autotune_boundary(self.model, self.params)
        if self.kv_layout_requested == "auto" and not self._kv_sized:
            # measure dense-vs-paged decode at the bound shape once per
            # process (memoized; the probe's own executor compiles count in
            # the return value), then rebuild onto the winner BEFORE
            # compiling the grid — no residents here, so the switch is
            # free. Skipped when the operator sized the pool explicitly:
            # sizing is a layout choice, and a dense verdict would discard
            # the budget. The probe engines published THEIR footprints on
            # the process-global ledger gauge, so re-publish ours after.
            verdict = decode_strategy_mod.autotune_kv_layout(
                self.model, self.params, block_size=self.kv_block_size,
            )
            if verdict != self.kv_layout:
                self._init_kv_state(verdict)
            else:
                self._update_kv_gauges()
            entry = decode_strategy_mod.kv_entry(self.model)
            gate = (entry or {}).get("quant_gate")
            if gate is not None and not gate.get("passed", False):
                # the quality gate vetoed int8 at this shape — the verdict
                # degraded to exact "paged"/dense; surface it on a counter
                # so a fleet rollout notices quality-driven fallbacks
                self.registry.inc("kv_quant_fallback_total")
            if self.prefix_cache_requested == "on" and \
                    self.kv_layout not in decode_strategy_mod.PAGED_KV_LAYOUTS:
                # the ctor deferred this check for kv_layout="auto" (the
                # autotuner could still pick paged); it didn't — an
                # explicit sharing request must not be dropped silently
                raise ValueError(
                    "prefix_cache='on' requires a paged kv_layout but the "
                    "kv-layout autotuner resolved dense at this shape — "
                    "pass kv_layout='paged' explicitly to share prefixes"
                )
        # no residents here (checked above), so re-resolving is safe: the
        # boundary variant compiles against the freshest verdict
        self._pinned_boundary_mode = None
        paged = self._pool is not None
        pages = self._pages_per_slot()
        # an all-zero table routes every warmup write to the null block and
        # every gather to its (finite) trash — the executors trace the same
        # programs live traffic dispatches
        row0 = jnp.zeros((pages,), jnp.int32)
        max_prefix = self.model.max_prefix_len
        for bucket_len in self.table.prompt_lens:
            if bucket_len - min(bucket_len, cfg.num_latents) > max_prefix:
                continue
            ids = jnp.full((1, bucket_len), cfg.pad_token_id, jnp.int32)
            pad = jnp.zeros((1,), jnp.int32)
            if paged:
                self._state = self._prefill_executor(bucket_len)(
                    self._exec_params, ids, pad, np.int32(0), row0, self._state
                )
            else:
                self._state = self._prefill_executor(bucket_len)(
                    self._exec_params, ids, pad, np.int32(0), self._state
                )
        if self.prefill_chunk is not None:
            n = self.model.max_seq_len
            _, cache_s = _prefill_shapes(self.model, self.params)
            sk = jnp.zeros(cache_s["cross_k"].shape, cache_s["cross_k"].dtype)
            sv = jnp.zeros(cache_s["cross_v"].shape, cache_s["cross_v"].dtype)
            if self.sharding is not None:
                # match the live admission path's committed staging
                # placement (AOT signature discipline)
                sk = self.sharding.put_leaf("stage_k", sk)
                sv = self.sharding.put_leaf("stage_v", sv)
            tokens = jnp.full((1, self.prefill_chunk), cfg.pad_token_id, jnp.int32)
            window = jnp.full((1, n), cfg.pad_token_id, jnp.int32)
            pad = jnp.zeros((1,), jnp.int32)
            m0 = np.int32(min(cfg.num_latents, self.model.max_latents))
            executor = self._chunked_prefill_executor()
            for final in (False, True):  # one program: lax.cond traces both
                sk, sv, self._state = executor(
                    self._exec_params, tokens, np.int32(0), np.bool_(final),
                    window, pad, m0, np.int32(0), row0, sk, sv, self._state,
                )
        if self._prefix_index is not None:
            # prefix-sharing executors: the shared (suffix-only) prefill —
            # both lax.cond branches of one program — and the COW page
            # copy, so the first hot admission compiles nothing
            C = self._shared_chunk_size()
            tokens = jnp.full((1, C), cfg.pad_token_id, jnp.int32)
            window = jnp.full((1, self.model.max_seq_len), cfg.pad_token_id,
                              jnp.int32)
            pad = jnp.zeros((1,), jnp.int32)
            m0 = np.int32(min(cfg.num_latents, self.model.max_latents))
            executor = self._shared_prefill_executor()
            for final in (False, True):
                self._state = executor(
                    self._exec_params, tokens, np.int32(0), np.bool_(final),
                    window, pad, m0, np.int32(0), row0,
                    np.int32(0), np.int32(0), self._state,
                )
            self._state = self._page_copy_executor()(
                self._state, np.int32(0), np.int32(0)
            )
        for boundary in (False, True):
            self._rng, key = jax.random.split(self._rng)
            if paged:
                # placed like the live _table_dev (AOT signature discipline)
                table0 = self._place_table(
                    np.zeros((self.slots, pages), np.int32)
                )
                self._state, _ = self._decode_executor(boundary)(
                    self._exec_params, self._state, table0, key
                )
            else:
                self._state, _ = self._decode_executor(boundary)(
                    self._exec_params, self._state, key
                )
        if self._spec is not None:
            # the speculative round's pair (+2 on the compile bound): the
            # lane verify handles both phases per row, so no boundary
            # variant exists on this path
            cand0 = self._spec_draft_executor()(self._exec_params, self._state)
            self._state, _ = self._spec_verify_executor()(
                self._exec_params, self._state, cand0
            )
        if paged and self.preemption in ("swap", "auto"):
            # the host-swap pair (+2 on the compile bound): one dummy
            # extract/restore round trip on the all-zero table (null-block
            # trash both ways), so the first real victim compiles nothing
            out0 = self._swap_extract_executor()(
                self._state, row0, np.int32(0)
            )
            self._state = self._swap_restore_executor()(
                self._state, out0, row0, np.int32(0), np.int32(0)
            )
        if self._prefix_index is not None:
            # the state blank below zeroes the device pool; cached blocks
            # must not survive it
            self._prefix_index.flush(self._pool)
            self._update_kv_gauges()
        # parked swap bundles (possible when warmup is re-run after
        # traffic drained mid-queue) reference pool content the blank
        # below zeroes — their requests fall back to replay-from-prompt
        for rid in list(self._swap_bundles):
            self._drop_bundle(rid, cause="retire")
        self._state = self._place_state(_blank_state(
            self.model, self.params, self.slots, cfg.pad_token_id,
            pool_tokens=self._pool_tokens() if paged else None,
            quantized=(self.kv_layout == "paged_int8"),
        ))
        return executor_cache_stats()["misses"] - before

    # -- observability -------------------------------------------------------
    def stats(self) -> dict:
        out = super().stats()
        counts = self.registry.counters()
        rows = counts.get("serving_decode_rows_total", 0)
        padded = counts.get("serving_decode_rows_padded_total", 0)
        reg = self.registry
        out.update({
            "engine": "slots",
            "slots": self.slots,
            "slots_active": sum(1 for s in self._slots if s is not None),
            "decode_steps": int(counts.get("serving_decode_steps_total", 0)),
            "prefills": int(counts.get("serving_prefills_total", 0)),
            "slot_occupancy": round((rows - padded) / max(1.0, rows), 4),
            "decode_rows_padding_waste": round(padded / max(1.0, rows), 4),
            "decode_step_ms": {
                "p50": _round_ms(reg.percentile("serving_decode_step_ms", 50.0)),
                "p95": _round_ms(reg.percentile("serving_decode_step_ms", 95.0)),
            },
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunks": int(counts.get("serving_prefill_chunks_total", 0)),
            "prefill_chunk_ms": {
                "p50": _round_ms(reg.percentile("serving_prefill_chunk_ms", 50.0)),
                "p95": _round_ms(reg.percentile("serving_prefill_chunk_ms", 95.0)),
            },
            "decode_strategy_boundary": self._boundary_mode(),
            "kv_layout": self.kv_layout,
        })
        out["speculation"] = {"mode": self.speculation}
        if self._spec is not None:
            rounds = int(counts.get("spec_rounds_total", 0))
            proposed = int(counts.get("spec_tokens_proposed_total", 0))
            accepted = int(counts.get("spec_tokens_accepted_total", 0))
            emitted = int(counts.get("spec_tokens_emitted_total", 0))
            out["speculation"].update({
                "k": self._spec.k,
                "draft_layers": self._spec.draft_layers,
                "rounds": rounds,
                "proposed": proposed,
                "accepted": accepted,
                "emitted": emitted,
                # the autotuner's gate signal: drafted tokens the verify
                # kept, over drafted tokens proposed
                "acceptance_rate": round(accepted / max(1, proposed), 4),
                "tokens_per_round": round(emitted / max(1, rounds), 4),
            })
        if self.sharding is not None:
            out["mesh"] = {
                "data": self.sharding.data_size,
                "model": self.sharding.model_size,
                "devices": self.sharding.num_devices,
                "spec": self.sharding.describe(),
            }
        if self._pool is not None:
            out["kv_pool"] = {
                **self._pool.stats(),
                "layout": self.kv_layout,
                "dtype": str(jnp.dtype(self._state["pool_k"].dtype)),
                "admit_waits": int(counts.get("kv_pool_admit_waits_total", 0)),
                "resident_bytes": int(
                    self.registry.gauge("kv_cache_resident_bytes") or 0
                ),
                "capacity_bytes": self._kv_capacity_bytes,
                "block_bytes": self.kv_block_size * self._kv_token_bytes,
                "block_scale_bytes":
                    self.kv_block_size * self._kv_scale_token_bytes,
                "quant_fallbacks": int(
                    counts.get("kv_quant_fallback_total", 0)
                ),
            }
            out["preemption"] = {
                "mode": self.preemption,
                "admit_headroom_blocks": self.admit_headroom_blocks,
                "preemptions": int(counts.get("kv_preemptions_total", 0)),
                "readmissions": int(counts.get("kv_readmissions_total", 0)),
                # host-swap disposition (docs/serving.md "Host-swap
                # preemption"): victims swapped out / bundles restored /
                # bytes moved both directions / victims parked right now
                "swaps": int(counts.get("kv_swaps_total", 0)),
                "swap_restores": int(counts.get("kv_swap_restores_total", 0)),
                "swap_bytes": int(counts.get("kv_swap_bytes_total", 0)),
                "swapped_waiting": len(self._swap_bundles),
                "swap_link_gbps": self.swap_link_gbps,
                "by_tier": dict(sorted(self._preempted_by_tier.items())),
                "by_tenant": dict(sorted(self._preempted_by_tenant.items())),
                "headroom_blocks": self._pool.headroom_blocks,
                # per-victim recompute-vs-swap post-mortems
                # (docs/observability.md "Scheduler timeline &
                # post-mortems"): the measured crossover evidence ROADMAP
                # item 2's host-swap policy starts from
                "postmortems": self.postmortems(),
            }
            out["prefix_cache"] = {"enabled": self._prefix_index is not None}
            if self._prefix_index is not None:
                hits = int(counts.get("kv_prefix_hits_total", 0))
                misses = int(counts.get("kv_prefix_misses_total", 0))
                out["prefix_cache"].update({
                    "hits": hits,
                    "misses": misses,
                    "hit_ratio": round(hits / max(1, hits + misses), 4),
                    "shared_blocks": int(
                        counts.get("kv_prefix_shared_blocks_total", 0)
                    ),
                    "shared_tokens": int(
                        counts.get("kv_prefix_shared_tokens_total", 0)
                    ),
                    "cow_copies": int(
                        counts.get("kv_prefix_cow_copies_total", 0)
                    ),
                    "evicted": int(
                        counts.get("kv_prefix_evicted_blocks_total", 0)
                    ),
                    "published": int(
                        counts.get("kv_prefix_published_blocks_total", 0)
                    ),
                    **self._prefix_index.stats(),
                })
        # per-tenant attribution rollup (sanitized labels): resident pool
        # pages + generated tokens + preemption victims per tenant — the
        # fleet router sums these across replicas, and the serve CLI's
        # serve_stats carries the fleet-level rollup
        pages_by_tenant: Dict[str, int] = {}
        if self._pool is not None:
            for tenant, held in self._tenant_pages().items():
                key = tenant_label(tenant)
                pages_by_tenant[key] = pages_by_tenant.get(key, 0) + held
        tenant_keys = (
            set(pages_by_tenant) | set(self._tokens_by_tenant)
            | set(self._preempted_by_tenant)
        )
        if tenant_keys:
            out["tenants"] = {
                key: {
                    "blocks_in_use": pages_by_tenant.get(key, 0),
                    "tokens": self._tokens_by_tenant.get(key, 0),
                    "preemptions": self._preempted_by_tenant.get(key, 0),
                }
                for key in sorted(tenant_keys)
            }
        return out

    def health(self) -> dict:
        out = super().health()
        out["slots"] = self.slots
        out["slots_active"] = sum(1 for s in self._slots if s is not None)
        out["admitting"] = self._admitting is not None
        out["kv_layout"] = self.kv_layout
        out["prefix_cache"] = self.prefix_cache
        out["preemption"] = self.preemption
        out["speculation"] = self.speculation
        out["mesh"] = (
            None if self.sharding is None else self.sharding.describe()
        )
        return out

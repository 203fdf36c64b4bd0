"""Autoregressive generation for Perceiver AR sequence models.

Reference semantics (``perceiver/model/text/clm/huggingface.py:53-143``):
the initial prompt tail of ``num_latents`` positions is latent; per generated
token the latent count grows to ``max_latents``, then the prefix grows to
``max_prefix_len``, then the window slides. The reference re-runs the full
model per token from Python; here the **whole generation is one
``lax.scan``** over a static-shape decode step, so it compiles once and stays
on-device.

Static shapes come from a right-aligned window formulation: the token window
is always ``(b, max_seq_len)`` with left padding tracked by ``pad_count``;
the latent segment is always the last ``max_latents`` positions, with a
dynamic scalar ``m`` (true latent count) masking which of them are real
latents. The phase schedule then reduces to ``m = min(m + 1, max_latents)``
per token — no per-phase control flow. Garbage query rows (window positions
classified latent but currently prefix) are computed and discarded; their
keys are masked at every layer, so real rows match the reference's ragged
computation exactly (same trick as the left-padded batches the reference
supports natively, ``clm/lightning.py:71-77``).

The prefix/latent boundary feeds the computation in two places that a KV
cache must respect: boundary-side key normalization (prefix keys use
``kv_norm``, latent keys use ``q_norm`` — reference ``modules.py:188-203``)
and latent-stack membership. Both are masked dynamically here.

Cache coverage by phase (``use_cache=True`` spans all of ``max_new_tokens``
in a single chained-scan program):

1. **Latent growth** (``_decode_step``): fully incremental — only the new
   token runs through the model, attending over cross- and per-layer stack
   caches. O(1) tokens of compute per step.
2. **Prefix growth** (``_decode_step_boundary``): token positions are stable
   (the window still slides over left pads), but the latent/prefix boundary
   migrates one position per step: the oldest latent becomes prefix, so its
   cross k/v are recomputed ``kv_norm``-side and overwritten in the cache
   (reference ``modules.py:188-203``). Because every latent attends to the
   migrated key, all latent cross-attention outputs — and therefore the
   whole self-attention stack — change each step and are recomputed; what
   the cache elides is the full-window embedding + cross k/v projections
   (the ``2·n·c²`` matmuls, the dominant projection cost for ``n ≫ m``).
3. **Sliding window** (``_decode_forward`` recompute): with the reference's
   learned absolute position embedding (``abs_pos_emb=True``, the default),
   incremental caching in this phase is *semantically impossible*, not
   merely hard: positions are window-relative (reference
   ``clm/huggingface.py:66`` truncates to the last ``max_seq_len`` tokens),
   so every surviving token's position embedding — and hence every key,
   value, and latent input — changes on every step. The only exact step is
   a full recompute, which is what the reference itself does each token;
   here it stays inside ``lax.scan``, compiled once. (For a rotary-only
   model, ``abs_pos_emb=False``, positions enter attention only relatively
   and a stable-angle cache would be mathematically exact — but not
   bit-exact against the window-relative recompute, so it is not used.)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.inference.samplers import (
    SamplingConfig,
    apply_min_new_tokens,
    sample_logits,
)
from perceiver_io_tpu.ops.position import RotaryEmbedding, positions


@dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 64
    num_latents: int = 1
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    sampling: SamplingConfig = SamplingConfig()
    #: beam width; > 1 dispatches :func:`generate` to beam search (greedy
    #: candidate expansion, HF ``GenerationMixin`` semantics).
    num_beams: int = 1
    #: HF exponent on generated length when ranking hypotheses (matches the
    #: vectorized ``_beam_search`` in transformers >= 4.50).
    length_penalty: float = 1.0
    #: EOS is masked to -inf until this many new tokens exist — greedy,
    #: sampled, and beam decoding alike (HF MinNewTokensLengthLogitsProcessor).
    min_new_tokens: int = 0


def _decode_forward(mdl, window: jnp.ndarray, pad_count: jnp.ndarray, m: jnp.ndarray):
    """Static-shape forward over the right-aligned window; returns next-token
    logits for the last position.

    :param mdl: bound ``AutoregressiveSequenceModel``.
    :param window: ``(b, N)`` tokens, right-aligned, left pads arbitrary ids.
    :param pad_count: ``(b,)`` number of left-pad slots per row.
    :param m: true latent count (last ``m`` window positions) — scalar, or
        per-row ``(b,)`` (the speculative verify lanes give each row its own
        post-candidate latent count; the scalar path is unchanged).
    """
    ar = mdl.perceiver_ar
    b, n = window.shape
    num_latents = mdl.max_latents  # static query length I

    pad_mask = jnp.arange(n)[None, :] < pad_count[:, None]  # (b, N) True = pad
    abs_pos = positions(b, n, shift=pad_count[:, None])
    emb, frq = ar.input_adapter(window, abs_pos=abs_pos)

    # Cross-attention layer (reference CrossAttentionLayer with the
    # x_kv_prefix path): latent-classified keys are q_norm'ed, prefix keys
    # kv_norm'ed — selected by mask since the boundary is dynamic.
    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention
    m = jnp.asarray(m)
    m_col = m[:, None] if m.ndim else m  # (b, 1) per-row or scalar
    is_latent = (jnp.arange(n) >= n - num_latents)[None, :] & (
        jnp.arange(n)[None, :] >= n - m_col
    )
    x_q_all = ca.q_norm(emb)
    x_kv = jnp.where(is_latent[..., None], x_q_all, ca.kv_norm(emb))

    x_q = x_q_all[:, -num_latents:]
    rot_q = RotaryEmbedding(frq, right_align=True)
    rot_k = RotaryEmbedding(frq, right_align=True)
    q = mha.project_q(x_q, rot_q)
    k, v = mha.project_kv(x_kv, rot_k)
    attn = mha.attend(q, k, v, pad_mask=pad_mask, deterministic=True)
    x = attn + emb[:, -num_latents:]
    x = layer.mlp(x) + x

    # Self-attention stack over the (padded) latent segment. Positions that
    # are not yet real latents are masked as keys at every layer; the
    # reference passes no per-row pad mask to its stack (modules.py:730-733),
    # so none is added here either.
    stack_pad = jnp.broadcast_to(
        jnp.arange(num_latents)[None, :] < num_latents - m_col, (b, num_latents)
    )
    frq_latent = frq[:, -num_latents:]
    x = ar.self_attention(
        x, stack_pad, RotaryEmbedding(frq_latent, right_align=True), True
    )

    x_last = x[:, -1]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(
        x_last[:, None], ar.input_adapter.embeddings
    )[:, 0]
    return logits


def _latent_stack_capture(ar, x, stack_pad, rot_latent, seg_idx):
    """Self-attention stack over the latent segment with per-layer k/v
    capture at the ``m`` real latents' segment slots (rotary on layer 0
    only, mirroring the stack's first-layer-rotary semantics) — ONE
    implementation shared by the one-shot prefill and both finalize paths
    (dense chunked, paged shared-prefix), so the admission paths cannot
    drift bitwise: same masks, same capture indices.

    :return: ``(x, stack_k, stack_v)`` — the stack output and the per-layer
        captured caches.
    """
    stack_k, stack_v = [], []
    for i, sa_layer in enumerate(ar.self_attention.layers):
        sa = sa_layer.self_attn
        r = rot_latent if (i == 0 or ar.self_attention.rotary_all_layers) else None
        normed = sa.norm(x)
        q_s = sa.attention.project_q(normed, r)
        k_s, v_s = sa.attention.project_kv(normed, r)
        stack_k.append(jnp.take_along_axis(k_s, seg_idx[None, None, :, None], axis=2))
        stack_v.append(jnp.take_along_axis(v_s, seg_idx[None, None, :, None], axis=2))
        attn = sa.attention.attend(q_s, k_s, v_s, pad_mask=stack_pad, deterministic=True)
        x = attn + x
        x = sa_layer.mlp(x) + x
    return x, stack_k, stack_v


def _decode_prefill(mdl, window: jnp.ndarray, pad_count: jnp.ndarray, m: jnp.ndarray):
    """Forward over the right-aligned window that additionally builds the
    decode caches for the latent-growth phase.

    Cache layout is **left-aligned by token index** ``p = slot - pad_count``
    (stable as the window slides over left pads), so appends are in-place
    writes, not rolls:

    - ``cross_k/v``: ``(b, h, N, d)`` — cross-attention keys/values of every
      real token, in its boundary-side normalization (latent keys q_norm'd,
      prefix keys kv_norm'd — reference ``modules.py:188-203``), rotary
      applied at angle ``p`` (relative, so shared offsets cancel).
    - ``stack_k/v``: per layer ``(b, h, max_latents, d)`` over the ``m`` real
      latents (left-aligned by latent age); rotary on layer 0 only,
      mirroring the stack's first-layer-rotary semantics.

    :return: (next-token logits, cache dict, length ``(b,)``, m).
    """
    ar = mdl.perceiver_ar
    b, n = window.shape
    num_latents = mdl.max_latents

    pad_mask = jnp.arange(n)[None, :] < pad_count[:, None]
    abs_pos = positions(b, n, shift=pad_count[:, None])
    emb, frq = ar.input_adapter(window, abs_pos=abs_pos)

    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention
    is_latent = (jnp.arange(n) >= n - num_latents)[None, :] & (
        jnp.arange(n)[None, :] >= n - m
    )
    x_q_all = ca.q_norm(emb)
    x_kv = jnp.where(is_latent[..., None], x_q_all, ca.kv_norm(emb))

    x_q = x_q_all[:, -num_latents:]
    rot = RotaryEmbedding(frq, right_align=True)
    q = mha.project_q(x_q, rot)
    k, v = mha.project_kv(x_kv, rot)
    attn = mha.attend(q, k, v, pad_mask=pad_mask, deterministic=True)
    x = attn + emb[:, -num_latents:]
    x = layer.mlp(x) + x

    # Left-align the window-slot cross k/v by token index p = slot - pad_count.
    slot_idx = jnp.clip(jnp.arange(n)[None, :] + pad_count[:, None], 0, n - 1)
    cross_k = jnp.take_along_axis(k, slot_idx[:, None, :, None], axis=2)
    cross_v = jnp.take_along_axis(v, slot_idx[:, None, :, None], axis=2)
    length = (n - pad_count).astype(jnp.int32)

    # Self-attention stack, capturing per-layer k/v of the m real latents
    # (segment slot num_latents - m + t for latent age t).
    stack_pad = jnp.broadcast_to(
        jnp.arange(num_latents)[None, :] < num_latents - m, (b, num_latents)
    )
    frq_latent = frq[:, -num_latents:]
    rot_latent = RotaryEmbedding(frq_latent, right_align=True)
    seg_idx = jnp.clip(num_latents - m + jnp.arange(num_latents), 0, num_latents - 1)
    x, stack_k, stack_v = _latent_stack_capture(ar, x, stack_pad, rot_latent, seg_idx)

    x_last = x[:, -1]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(x_last[:, None], ar.input_adapter.embeddings)[:, 0]
    cache = {"cross_k": cross_k, "cross_v": cross_v,
             "stack_k": stack_k, "stack_v": stack_v}
    return logits, cache, length, m


def _prefill_chunk_kv(mdl, tokens: jnp.ndarray, offset: jnp.ndarray):
    """Cross k/v (``kv_norm``-side) for a fixed-size chunk of **prefix**
    token positions — the unit of chunked prefill (``serving/slots.py``).

    The full prefill's cross-k/v cache is per-position math: embedding at
    the token's absolute index, ``kv_norm``, k/v projection with rotary at
    angle ``p`` (:func:`_decode_prefill`'s left-aligned layout). None of it
    couples positions, so a chunk of ``C`` consecutive prefix positions
    computes values identical to the one-shot full-window pass — which is
    what lets the slot engine split a long admission into bounded-stall
    pieces interleaved with resident decode steps.

    :param tokens: ``(b, C)`` token ids at absolute indices
        ``offset .. offset + C - 1``.
    :param offset: traced scalar — the chunk's first absolute token index
        (one compiled program serves every chunk of every bucket).
    :return: ``(k, v)`` of shape ``(b, h, C, d)`` for those positions.
    """
    ar = mdl.perceiver_ar
    b, c = tokens.shape
    pos = jnp.broadcast_to(
        offset + jnp.arange(c, dtype=jnp.int32)[None, :], (b, c)
    )
    emb, frq = ar.input_adapter(tokens, abs_pos=pos)
    ca = ar.cross_attention.cross_attn
    return ca.attention.project_kv(ca.kv_norm(emb), RotaryEmbedding(frq))


def _prefill_finalize(mdl, window: jnp.ndarray, pad_count: jnp.ndarray,
                      m: jnp.ndarray, cross_k, cross_v):
    """Complete a chunked prefill: with the prefix cross k/v already staged
    by :func:`_prefill_chunk_kv` calls, project the ``m`` real latents'
    ``q_norm``-side k/v into the cache, attend the latent segment over the
    cache gathered back into window-slot alignment (pad slots gather
    garbage the pad mask zeroes out — the :func:`_decode_step_boundary`
    argument), and run the self-attention stack capturing its caches.

    Returns the same ``(logits, cache, length, m)`` contract as
    :func:`_decode_prefill`, so the slot engine inserts either path's
    output identically.
    """
    ar = mdl.perceiver_ar
    b, n = window.shape
    num_latents = mdl.max_latents
    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention
    rows = jnp.arange(b)

    # Latent segment (last max_latents window slots) at true token indices;
    # p_seg < 0 marks pad slots (prompt shorter than the latent budget).
    p_seg = jnp.arange(n - num_latents, n)[None, :] - pad_count[:, None]
    lat_abs = jnp.maximum(p_seg, 0)
    emb_lat, frq_lat = ar.input_adapter(window[:, n - num_latents:], abs_pos=lat_abs)
    x_q_lat = ca.q_norm(emb_lat)

    # q_norm-side k/v of the m real latents, written at their abs indices.
    # Segment slots that are prefix-classified (m < max_latents) or pads
    # route to the out-of-bounds sentinel ``n`` and are DROPPED: their
    # kv_norm-side entries came from the chunk passes and must survive.
    k_lat, v_lat = mha.project_kv(x_q_lat, RotaryEmbedding(frq_lat))
    is_real = jnp.arange(num_latents)[None, :] >= num_latents - m
    idx = jnp.where(is_real, jnp.clip(p_seg, 0, n - 1), n)
    cross_k = cross_k.at[rows[:, None], :, idx].set(
        k_lat.transpose(0, 2, 1, 3), mode="drop"
    )
    cross_v = cross_v.at[rows[:, None], :, idx].set(
        v_lat.transpose(0, 2, 1, 3), mode="drop"
    )

    # Gather into window-slot alignment and attend exactly as
    # _decode_prefill's direct pass does (masking included).
    slot_abs = jnp.maximum(jnp.arange(n)[None, :] - pad_count[:, None], 0)
    k_slots = jnp.take_along_axis(cross_k, slot_abs[:, None, :, None], axis=2)
    v_slots = jnp.take_along_axis(cross_v, slot_abs[:, None, :, None], axis=2)
    pad_mask = jnp.arange(n)[None, :] < pad_count[:, None]
    q = mha.project_q(x_q_lat, RotaryEmbedding(frq_lat, right_align=True))
    attn = mha.attend(q, k_slots, v_slots, pad_mask=pad_mask, deterministic=True)
    x = attn + emb_lat
    x = layer.mlp(x) + x

    # Self-attention stack with per-layer cache capture (_decode_prefill's
    # shared helper: same masks, same first-layer-rotary semantics).
    stack_pad = jnp.broadcast_to(
        jnp.arange(num_latents)[None, :] < num_latents - m, (b, num_latents)
    )
    rot_latent = RotaryEmbedding(frq_lat, right_align=True)
    seg_idx = jnp.clip(num_latents - m + jnp.arange(num_latents), 0, num_latents - 1)
    x, stack_k, stack_v = _latent_stack_capture(ar, x, stack_pad, rot_latent, seg_idx)

    x_last = x[:, -1]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(x_last[:, None], ar.input_adapter.embeddings)[:, 0]
    length = (n - pad_count).astype(jnp.int32)
    cache = {"cross_k": cross_k, "cross_v": cross_v,
             "stack_k": stack_k, "stack_v": stack_v}
    return logits, cache, length, m


def _prefill_finalize_paged(
    mdl, window: jnp.ndarray, pad_count: jnp.ndarray, m: jnp.ndarray,
    pool_k, pool_v, table_row: jnp.ndarray, block_size: int,
    scale_k=None, scale_v=None,
):
    """:func:`_prefill_finalize` over the block-paged KV layout with a
    **suffix-only** contract (docs/serving.md "Prefix sharing"): cross k/v
    for every prefix position are ALREADY RESIDENT in the pool — shared
    prefix blocks another request published (never re-projected: the TTFT
    win prefix sharing exists for) and/or this admission's own staged
    chunks, which only covered ``[start_position, prefix_len)`` — so this
    call only projects the ``m`` real latents' ``q_norm``-side k/v,
    scatters them through the slot's block table, gathers the WHOLE window
    back from the pool, and runs the attend + self-attention stack exactly
    as the dense finalize does. A fully-hot prefix stages zero chunks and
    the admission collapses to block-table writes plus this one call.

    ``scale_k``/``scale_v`` (both or neither) carry the int8 layout's
    per-(position, head) dequant scales: appends quantize through
    :func:`~perceiver_io_tpu.ops.paged_attention.scatter_kv` and the
    updated scales join the return tuple right after the pools.

    Latent scatter routing: non-real segment slots (prompt shorter than
    the latent budget) route to the null block — the paged analogue of the
    dense finalize's ``mode="drop"`` — so staged/shared prefix values
    survive, and the gather + masked attend is bitwise identical to the
    dense path (the parity bar ``tests/test_prefix_cache.py`` pins).

    :return: ``(logits, pool_k, pool_v, stack cache, length, m)``.
    """
    from perceiver_io_tpu.ops import paged_attention as paged

    ar = mdl.perceiver_ar
    b, n = window.shape
    num_latents = mdl.max_latents
    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention
    table = table_row[None] if table_row.ndim == 1 else table_row

    # Latent segment (last max_latents window slots) at true token indices;
    # p_seg < 0 marks pad slots (prompt shorter than the latent budget).
    p_seg = jnp.arange(n - num_latents, n)[None, :] - pad_count[:, None]
    lat_abs = jnp.maximum(p_seg, 0)
    emb_lat, frq_lat = ar.input_adapter(window[:, n - num_latents:], abs_pos=lat_abs)
    x_q_lat = ca.q_norm(emb_lat)

    # q_norm-side k/v of the m real latents, scattered at their abs
    # indices through the block table; prefix-classified or pad segment
    # slots route to the null block (their kv_norm-side pool entries came
    # from chunk passes / shared blocks and must survive).
    k_lat, v_lat = mha.project_kv(x_q_lat, RotaryEmbedding(frq_lat))
    is_real = jnp.arange(num_latents)[None, :] >= num_latents - m
    idx = jnp.clip(p_seg, 0, n - 1)
    flat_lat = paged.flat_write_indices(table, idx, block_size)
    flat_lat = jnp.where(is_real, flat_lat, idx % block_size)  # null-route
    pool_k, scale_k = paged.scatter_kv(
        pool_k, scale_k, flat_lat[0], k_lat[0].transpose(1, 0, 2)
    )
    pool_v, scale_v = paged.scatter_kv(
        pool_v, scale_v, flat_lat[0], v_lat[0].transpose(1, 0, 2)
    )

    # Window-aligned attend exactly as the dense finalize's (gather path:
    # pad slots re-read position 0 and the pad mask zeroes them out of
    # the softmax — the _decode_step_boundary argument; kernel path: the
    # ragged kernel over the live span [0, n - pad_count)).
    q = mha.project_q(x_q_lat, RotaryEmbedding(frq_lat, right_align=True))
    attn = paged.paged_window_attention(
        mha.attend, q, pool_k, pool_v, table,
        block_size=block_size, n=n, pad_count=pad_count,
        scale_k=scale_k, scale_v=scale_v, project_out=mha.project_out,
    )
    x = attn + emb_lat
    x = layer.mlp(x) + x

    # Self-attention stack with per-layer cache capture (the shared
    # helper: same masks, same first-layer-rotary semantics as the dense
    # prefill/finalize — the bitwise half of the parity claim).
    stack_pad = jnp.broadcast_to(
        jnp.arange(num_latents)[None, :] < num_latents - m, (b, num_latents)
    )
    rot_latent = RotaryEmbedding(frq_lat, right_align=True)
    seg_idx = jnp.clip(num_latents - m + jnp.arange(num_latents), 0, num_latents - 1)
    x, stack_k, stack_v = _latent_stack_capture(ar, x, stack_pad, rot_latent, seg_idx)

    x_last = x[:, -1]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(x_last[:, None], ar.input_adapter.embeddings)[:, 0]
    length = (n - pad_count).astype(jnp.int32)
    cache = {"stack_k": stack_k, "stack_v": stack_v}
    if scale_k is not None:
        return logits, pool_k, pool_v, scale_k, scale_v, cache, length, m
    return logits, pool_k, pool_v, cache, length, m


def _decode_step(mdl, token: jnp.ndarray, cache: dict, length: jnp.ndarray, m: jnp.ndarray):
    """One cached decode step: run ONLY the new token through the model,
    attending over the caches — valid while the new token is a fresh latent
    (latent-growth phase: no boundary migration, no position shifts).

    :param token: ``(b,)`` the token just appended.
    :return: (next-token logits, cache, length + 1, m + 1).
    """
    ar = mdl.perceiver_ar
    b = token.shape[0]
    n = cache["cross_k"].shape[2]
    num_latents = mdl.max_latents

    p_new = length[:, None]  # (b, 1) token index of the new position
    emb, frq = ar.input_adapter(token[:, None], abs_pos=p_new)
    rot = RotaryEmbedding(frq)

    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention
    x_q = ca.q_norm(emb)  # the new token is a latent: q_norm on both sides
    q = mha.project_q(x_q, rot)
    k_new, v_new = mha.project_kv(x_q, rot)
    rows = jnp.arange(b)
    cross_k = cache["cross_k"].at[rows, :, length].set(k_new[:, :, 0])
    cross_v = cache["cross_v"].at[rows, :, length].set(v_new[:, :, 0])
    future = jnp.arange(n)[None, :] > length[:, None]  # True = not yet written
    attn = mha.attend(q, cross_k, cross_v, pad_mask=future, deterministic=True)
    x = attn + emb
    x = layer.mlp(x) + x

    stack_k, stack_v = [], []
    stack_future = jnp.broadcast_to(jnp.arange(num_latents)[None, :] > m, (b, num_latents))
    for i, sa_layer in enumerate(ar.self_attention.layers):
        sa = sa_layer.self_attn
        r = rot if (i == 0 or ar.self_attention.rotary_all_layers) else None
        normed = sa.norm(x)
        q_s = sa.attention.project_q(normed, r)
        k_s, v_s = sa.attention.project_kv(normed, r)
        k_i = jax.lax.dynamic_update_slice(cache["stack_k"][i], k_s, (0, 0, m, 0))
        v_i = jax.lax.dynamic_update_slice(cache["stack_v"][i], v_s, (0, 0, m, 0))
        stack_k.append(k_i)
        stack_v.append(v_i)
        attn = sa.attention.attend(q_s, k_i, v_i, pad_mask=stack_future, deterministic=True)
        x = attn + x
        x = sa_layer.mlp(x) + x

    x_last = x[:, 0]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(x_last[:, None], ar.input_adapter.embeddings)[:, 0]
    cache = {"cross_k": cross_k, "cross_v": cross_v,
             "stack_k": stack_k, "stack_v": stack_v}
    return logits, cache, length + 1, m + 1


def _slot_decode_step(mdl, token: jnp.ndarray, cache: dict, length: jnp.ndarray, m: jnp.ndarray):
    """Per-row variant of :func:`_decode_step` for the slot serving engine
    (``serving/slots.py``): ``m`` is a ``(b,)`` vector, not a scalar, because
    persistent slots are admitted at different times and therefore sit at
    different latent counts. The stack-cache append and the stack future
    mask become per-row scatters; every other op is already per-row. For a
    row whose ``m`` equals the batch scalar, the math is identical to
    :func:`_decode_step` — that is the slot engine's token-parity claim.

    Write indices are clamped (``min(length, N-1)``, ``min(m, I-1)``) so
    retired/idle slots whose counters have saturated stay in-bounds; active
    rows never hit the clamp (the engine rejects requests that would
    overrun the window).

    :param token: ``(b,)`` the token just appended.
    :param length: ``(b,)`` real-token count before the append.
    :param m: ``(b,)`` per-row latent count before the append.
    :return: (next-token logits, cache, length + 1, m + 1).
    """
    ar = mdl.perceiver_ar
    b = token.shape[0]
    n = cache["cross_k"].shape[2]
    num_latents = mdl.max_latents

    wl = jnp.minimum(length, n - 1)  # write index; no-op clamp for active rows
    p_new = wl[:, None]  # (b, 1) token index of the new position
    emb, frq = ar.input_adapter(token[:, None], abs_pos=p_new)
    rot = RotaryEmbedding(frq)

    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention
    x_q = ca.q_norm(emb)  # the new token is a latent: q_norm on both sides
    q = mha.project_q(x_q, rot)
    k_new, v_new = mha.project_kv(x_q, rot)
    rows = jnp.arange(b)
    cross_k = cache["cross_k"].at[rows, :, wl].set(k_new[:, :, 0])
    cross_v = cache["cross_v"].at[rows, :, wl].set(v_new[:, :, 0])
    future = jnp.arange(n)[None, :] > length[:, None]  # True = not yet written
    attn = mha.attend(q, cross_k, cross_v, pad_mask=future, deterministic=True)
    x = attn + emb
    x = layer.mlp(x) + x

    wm = jnp.minimum(m, num_latents - 1)
    stack_k, stack_v = [], []
    stack_future = jnp.arange(num_latents)[None, :] > m[:, None]
    for i, sa_layer in enumerate(ar.self_attention.layers):
        sa = sa_layer.self_attn
        r = rot if (i == 0 or ar.self_attention.rotary_all_layers) else None
        normed = sa.norm(x)
        q_s = sa.attention.project_q(normed, r)
        k_s, v_s = sa.attention.project_kv(normed, r)
        k_i = cache["stack_k"][i].at[rows, :, wm].set(k_s[:, :, 0])
        v_i = cache["stack_v"][i].at[rows, :, wm].set(v_s[:, :, 0])
        stack_k.append(k_i)
        stack_v.append(v_i)
        attn = sa.attention.attend(q_s, k_i, v_i, pad_mask=stack_future, deterministic=True)
        x = attn + x
        x = sa_layer.mlp(x) + x

    x_last = x[:, 0]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(x_last[:, None], ar.input_adapter.embeddings)[:, 0]
    cache = {"cross_k": cross_k, "cross_v": cross_v,
             "stack_k": stack_k, "stack_v": stack_v}
    return logits, cache, length + 1, m + 1


def _slot_decode_step_paged(
    mdl, token: jnp.ndarray, pool_k, pool_v, block_table: jnp.ndarray,
    stack_cache: dict, length: jnp.ndarray, m: jnp.ndarray,
    block_size: int, write_ok: Optional[jnp.ndarray] = None,
    scale_k=None, scale_v=None,
):
    """:func:`_slot_decode_step` over the block-paged KV layout
    (``serving/kv_pool.py``): the per-slot dense ``cross_k/cross_v`` rows
    are replaced by ONE flat ``(pool_tokens, h, d)`` pool addressed through
    ``block_table`` (``(b, pages)``; block 0 is the null/trash block). The
    new token's k/v scatter lands at the table-translated append index, and
    the attend runs through
    :func:`~perceiver_io_tpu.ops.paged_attention.paged_decode_attention` —
    a gather back to the dense view (bitwise-identical masked attend) or
    the ragged Pallas kernel when ``PERCEIVER_RAGGED_KERNEL=1``. The latent-stack cache stays dense:
    it is bounded by ``max_latents`` (a model constant), not the context
    length, so it is outside the ``slots × max_context`` scaling the pool
    exists to break (docs/serving.md).

    ``write_ok`` (per-row bool) redirects a row's append write to the null
    block — the boundary-variant executor passes ``~is_boundary`` so the
    per-row select between this step and the boundary step becomes *write
    routing*: each live pool position is written by exactly the step the
    dense layout's ``where`` select would have kept.

    ``scale_k``/``scale_v`` (both or neither) carry the int8 layout's
    dequant scales; appends quantize via ``scatter_kv`` and the updated
    scales join the return tuple right after the pools.

    :return: (next-token logits, pool_k, pool_v, [scale_k, scale_v,]
        stack cache, length + 1, m + 1).
    """
    from perceiver_io_tpu.ops import paged_attention as paged

    ar = mdl.perceiver_ar
    b = token.shape[0]
    n = mdl.max_seq_len
    num_latents = mdl.max_latents

    wl = jnp.minimum(length, n - 1)  # write index; no-op clamp for active rows
    p_new = wl[:, None]
    emb, frq = ar.input_adapter(token[:, None], abs_pos=p_new)
    rot = RotaryEmbedding(frq)

    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention
    x_q = ca.q_norm(emb)  # the new token is a latent: q_norm on both sides
    q = mha.project_q(x_q, rot)
    k_new, v_new = mha.project_kv(x_q, rot)
    flat_w = paged.flat_write_indices(block_table, wl, block_size)
    if write_ok is not None:
        # boundary rows' appends are owned by the boundary step; route this
        # one to the null block (flat index < block_size is always trash)
        flat_w = jnp.where(write_ok, flat_w, flat_w % block_size)
    pool_k, scale_k = paged.scatter_kv(pool_k, scale_k, flat_w, k_new[:, :, 0])
    pool_v, scale_v = paged.scatter_kv(pool_v, scale_v, flat_w, v_new[:, :, 0])
    future = jnp.arange(n)[None, :] > length[:, None]  # True = not yet written
    attn = paged.paged_decode_attention(
        mha.attend, q, pool_k, pool_v, block_table,
        block_size=block_size, n=n, pad_mask=future,
        lengths=jnp.minimum(length + 1, n),
        scale_k=scale_k, scale_v=scale_v, project_out=mha.project_out,
    )
    x = attn + emb
    x = layer.mlp(x) + x

    wm = jnp.minimum(m, num_latents - 1)
    rows = jnp.arange(b)
    stack_k, stack_v = [], []
    stack_future = jnp.arange(num_latents)[None, :] > m[:, None]
    for i, sa_layer in enumerate(ar.self_attention.layers):
        sa = sa_layer.self_attn
        r = rot if (i == 0 or ar.self_attention.rotary_all_layers) else None
        normed = sa.norm(x)
        q_s = sa.attention.project_q(normed, r)
        k_s, v_s = sa.attention.project_kv(normed, r)
        k_i = stack_cache["stack_k"][i].at[rows, :, wm].set(k_s[:, :, 0])
        v_i = stack_cache["stack_v"][i].at[rows, :, wm].set(v_s[:, :, 0])
        stack_k.append(k_i)
        stack_v.append(v_i)
        attn = sa.attention.attend(q_s, k_i, v_i, pad_mask=stack_future, deterministic=True)
        x = attn + x
        x = sa_layer.mlp(x) + x

    x_last = x[:, 0]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(x_last[:, None], ar.input_adapter.embeddings)[:, 0]
    stack = {"stack_k": stack_k, "stack_v": stack_v}
    if scale_k is not None:
        return logits, pool_k, pool_v, scale_k, scale_v, stack, length + 1, m + 1
    return logits, pool_k, pool_v, stack, length + 1, m + 1


def _decode_step_boundary_paged(
    mdl, window: jnp.ndarray, pad_count: jnp.ndarray, pool_k, pool_v,
    block_table: jnp.ndarray, length: jnp.ndarray, block_size: int,
    write_ok: Optional[jnp.ndarray] = None,
    scale_k=None, scale_v=None,
):
    """:func:`_decode_step_boundary` over the block-paged KV layout: the
    migration + append writes become table-translated pool scatters and the
    window-slot-aligned gather reads the pool instead of a dense per-row
    cache. The computation between scatter and gather — latent embedding,
    boundary-side re-normalization, attend, the full self-attention stack —
    is the dense step's verbatim, so live rows' logits are bitwise
    identical to the dense layout (the paged engine's parity claim).

    ``write_ok`` routes NON-boundary rows' writes to the null block (the
    inverse of :func:`_slot_decode_step_paged`'s routing — together they
    reproduce the dense executor's per-row ``where`` select at every live
    pool position).

    ``scale_k``/``scale_v`` follow the same int8-layout contract as
    :func:`_slot_decode_step_paged`.

    :return: (next-token logits, pool_k, pool_v, [scale_k, scale_v,]
        length + 1).
    """
    from perceiver_io_tpu.ops import paged_attention as paged

    ar = mdl.perceiver_ar
    b, n = window.shape
    num_latents = mdl.max_latents
    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention

    mig_abs = jnp.maximum((n - num_latents - 1) - pad_count[:, None], 0)
    # append index clamped only for idle rows (saturated length); active
    # boundary rows always satisfy length < n, matching the dense step
    write_idx = jnp.concatenate(
        [mig_abs, jnp.minimum(length, n - 1)[:, None]], axis=1
    )

    lat_abs = jnp.maximum(
        jnp.arange(n - num_latents, n)[None, :] - pad_count[:, None], 0
    )
    emb_lat, frq_lat = ar.input_adapter(window[:, n - num_latents :], abs_pos=lat_abs)
    x_q_lat = ca.q_norm(emb_lat)

    emb_mig, frq_mig = ar.input_adapter(
        window[:, n - num_latents - 1 : n - num_latents], abs_pos=mig_abs
    )
    k_mig, v_mig = mha.project_kv(ca.kv_norm(emb_mig), RotaryEmbedding(frq_mig))
    k_new, v_new = mha.project_kv(
        x_q_lat[:, -1:], RotaryEmbedding(frq_lat[:, -1:])
    )
    k_upd = jnp.concatenate([k_mig, k_new], axis=2).transpose(0, 2, 1, 3)
    v_upd = jnp.concatenate([v_mig, v_new], axis=2).transpose(0, 2, 1, 3)
    flat_wi = paged.flat_write_indices(block_table, write_idx, block_size)
    if write_ok is not None:
        flat_wi = jnp.where(write_ok[:, None], flat_wi, flat_wi % block_size)
    pool_k, scale_k = paged.scatter_kv(pool_k, scale_k, flat_wi, k_upd)
    pool_v, scale_v = paged.scatter_kv(pool_v, scale_v, flat_wi, v_upd)

    q = mha.project_q(x_q_lat, RotaryEmbedding(frq_lat, right_align=True))
    attn = paged.paged_window_attention(
        mha.attend, q, pool_k, pool_v, block_table,
        block_size=block_size, n=n, pad_count=pad_count,
        scale_k=scale_k, scale_v=scale_v, project_out=mha.project_out,
    )
    x = attn + emb_lat
    x = layer.mlp(x) + x

    stack_pad = jnp.zeros((b, num_latents), bool)
    x = ar.self_attention(
        x, stack_pad, RotaryEmbedding(frq_lat, right_align=True), True
    )

    x_last = x[:, -1]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(x_last[:, None], ar.input_adapter.embeddings)[:, 0]
    if scale_k is not None:
        return logits, pool_k, pool_v, scale_k, scale_v, length + 1
    return logits, pool_k, pool_v, length + 1


def _decode_step_boundary(
    mdl, window: jnp.ndarray, pad_count: jnp.ndarray, cross_k, cross_v, length,
    write_idx: Optional[jnp.ndarray] = None,
):
    """One cached decode step for the **prefix-growth** phase (the latent
    count is pinned at ``max_latents`` and the boundary migrates one position
    per step — reference window schedule ``clm/huggingface.py:56-62``).

    Token positions are stable in this phase (every row still slides over
    left pads), so the abs-indexed cross k/v cache stays valid except at two
    positions, which are (re)projected per step:

    - the **new token** enters as the freshest latent (``q_norm``-side k/v,
      appended at index ``length``);
    - the **oldest latent** (abs index ``n - max_latents - 1 - pad_count``)
      becomes prefix — its k/v are recomputed ``kv_norm``-side (the
      boundary-side normalization swap, reference ``modules.py:188-203``).

    Both cache updates land in ONE fused scatter per array (the step is
    bookkeeping-bound on a CPU, so the
    fixed per-step overhead matters as much as the FLOPs). The migrated and
    appended indices are always distinct (``length - max_latents`` vs
    ``length``), so the fused scatter stays deterministic.

    Every latent attends to the migrated key, so all latent cross-attention
    outputs and the self-attention stack are recomputed (their inputs
    changed); the cache elides the ``2·n·c²`` full-window k/v projections
    and the full-window embedding. The attend itself runs over the cache
    gathered back into window-slot alignment so the computation — including
    masking — is bitwise identical to :func:`_decode_forward`.

    :param window: ``(b, N)`` tokens, right-aligned (new token last).
    :param pad_count: ``(b,)`` left-pad counts *after* the append.
    :param cross_k/cross_v: ``(b, h, N, d)`` abs-indexed cross k/v cache.
    :param length: ``(b,)`` real-token count *before* the append.
    :param write_idx: optional ``(b, 2)`` precomputed ``[migrated index,
        append index]`` — the generation executor hoists this arithmetic
        out of the scan body; None derives it from ``pad_count``/``length``
        (the slot engine's per-call path).
    :return: (next-token logits, cross_k, cross_v, length + 1).
    """
    ar = mdl.perceiver_ar
    b, n = window.shape
    num_latents = mdl.max_latents
    layer = ar.cross_attention
    ca = layer.cross_attn
    mha = ca.attention
    rows = jnp.arange(b)

    if write_idx is None:
        mig_abs = jnp.maximum((n - num_latents - 1) - pad_count[:, None], 0)
        write_idx = jnp.concatenate([mig_abs, length[:, None]], axis=1)
    else:
        mig_abs = write_idx[:, :1]

    # Latent segment: the last max_latents window slots, all real tokens
    # (guaranteed by the caller's phase-2 precondition).
    lat_abs = jnp.maximum(
        jnp.arange(n - num_latents, n)[None, :] - pad_count[:, None], 0
    )
    emb_lat, frq_lat = ar.input_adapter(window[:, n - num_latents :], abs_pos=lat_abs)
    x_q_lat = ca.q_norm(emb_lat)

    # Boundary migration: recompute the ex-latent's k/v kv_norm-side.
    emb_mig, frq_mig = ar.input_adapter(
        window[:, n - num_latents - 1 : n - num_latents], abs_pos=mig_abs
    )
    k_mig, v_mig = mha.project_kv(ca.kv_norm(emb_mig), RotaryEmbedding(frq_mig))

    # The new token's q_norm-side k/v at its abs index, fused with the
    # migration write: one (b, 2)-indexed scatter per cache array.
    k_new, v_new = mha.project_kv(
        x_q_lat[:, -1:], RotaryEmbedding(frq_lat[:, -1:])
    )
    k_upd = jnp.concatenate([k_mig, k_new], axis=2).transpose(0, 2, 1, 3)
    v_upd = jnp.concatenate([v_mig, v_new], axis=2).transpose(0, 2, 1, 3)
    cross_k = cross_k.at[rows[:, None], :, write_idx].set(k_upd)
    cross_v = cross_v.at[rows[:, None], :, write_idx].set(v_upd)

    # Gather the abs-indexed cache into window-slot alignment and attend
    # exactly as the uncached forward does (pad slots gather garbage that the
    # pad mask zeroes out of the softmax).
    slot_abs = jnp.maximum(jnp.arange(n)[None, :] - pad_count[:, None], 0)
    k_slots = jnp.take_along_axis(cross_k, slot_abs[:, None, :, None], axis=2)
    v_slots = jnp.take_along_axis(cross_v, slot_abs[:, None, :, None], axis=2)
    pad_mask = jnp.arange(n)[None, :] < pad_count[:, None]
    q = mha.project_q(x_q_lat, RotaryEmbedding(frq_lat, right_align=True))
    attn = mha.attend(q, k_slots, v_slots, pad_mask=pad_mask, deterministic=True)
    x = attn + emb_lat
    x = layer.mlp(x) + x

    # Full self-attention stack over the max_latents latents (all real; the
    # all-False mask keeps the masking ops bitwise identical to
    # _decode_forward with m == max_latents).
    stack_pad = jnp.zeros((b, num_latents), bool)
    x = ar.self_attention(
        x, stack_pad, RotaryEmbedding(frq_lat, right_align=True), True
    )

    x_last = x[:, -1]
    if mdl.config.output_norm:
        x_last = mdl.out_norm(x_last)
    logits = mdl.output_adapter(x_last[:, None], ar.input_adapter.embeddings)[:, 0]
    return logits, cross_k, cross_v, length + 1


def generate(
    model,
    params,
    input_ids: jnp.ndarray,
    config: GenerationConfig,
    *,
    rng: Optional[jax.Array] = None,
    prompt_pad_count: Optional[jnp.ndarray] = None,
    use_cache: bool = True,
    decode_strategy=None,
) -> jnp.ndarray:
    """Generate ``config.max_new_tokens`` tokens after ``input_ids``.

    :param model: an ``AutoregressiveSequenceModel`` (CLM / symbolic audio).
    :param input_ids: ``(b, prompt_len)`` prompt, left-padded if ragged.
    :param prompt_pad_count: ``(b,)`` left-pad counts for ragged prompts.
    :param decode_strategy: per-phase cache strategy —
        ``"auto" | "cached" | "recompute"`` or a
        :class:`~perceiver_io_tpu.inference.decode_strategy.DecodeStrategy`.
        ``None`` defers to ``PERCEIVER_DECODE_STRATEGY`` then ``"auto"``
        (the measured winner for this shape when the autotuner has run,
        else the cached default). Every strategy is exact; greedy output is
        token-identical across all of them. Beam search (``num_beams > 1``)
        ignores the strategy (its executor has no boundary segment).
    :return: ``(b, max_new_tokens)`` generated ids (pad after EOS).
    """
    if config.num_beams > 1:
        from perceiver_io_tpu.inference.beam import beam_search

        return beam_search(
            model,
            params,
            input_ids,
            config,
            num_beams=config.num_beams,
            length_penalty=config.length_penalty,
            prompt_pad_count=prompt_pad_count,
        )
    b, prompt_len = input_ids.shape
    n = model.max_seq_len
    max_latents = model.max_latents
    if not 0 < prompt_len <= n:
        raise ValueError(f"prompt length out of valid range [1..{n}]")
    if not 0 < config.num_latents <= max_latents:
        raise ValueError(
            f"num_latents={config.num_latents} out of valid range [1..{max_latents}]"
        )
    num_latents = min(prompt_len, config.num_latents)
    prefix_len = prompt_len - num_latents
    if prefix_len > model.max_prefix_len:
        raise ValueError(
            f"for sequence length {prompt_len}, num_latents must be >= "
            f"{num_latents + prefix_len - model.max_prefix_len}"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if prompt_pad_count is None:
        prompt_pad_count = jnp.zeros((b,), jnp.int32)

    # Phase schedule (see module docstring). Phase 1 (latent growth) is
    # fully incremental; phase 2 (prefix growth) reuses the cross k/v cache
    # with per-step boundary migration — valid only while pads never occupy
    # latent slots (prompt pads fit in the nominal prefix); phase 3 (slide)
    # is windowed recompute, semantically forced by the learned absolute
    # position embedding (reference window schedule ``clm/huggingface.py:
    # 53-74``). The per-phase cached-vs-recompute choice is the decode
    # strategy (``inference/decode_strategy.py`` — measured, env- and
    # flag-overridable; the cached boundary phase loses to recompute on a
    # CPU and is not measured on the chip). The schedule is host-side
    # static, so it is part of the executor cache key rather than traced
    # control flow.
    from perceiver_io_tpu.inference import decode_strategy as _strategy

    strat = _strategy.resolve(decode_strategy, model)
    latent_cached = use_cache and strat.latent == "cached"
    s1 = (
        min(config.max_new_tokens, max_latents - num_latents, n - prompt_len)
        if latent_cached
        else 0
    )
    phase2_ok = (
        use_cache
        and strat.boundary_cached
        and bool((np.asarray(jax.device_get(prompt_pad_count)) <= prefix_len).all())
    )
    s2 = min(config.max_new_tokens, n - prompt_len) if phase2_ok else s1
    s2 = max(s1, s2)

    executor = _generation_executor(
        model, config, b, prompt_len, num_latents, s1, s2, str(input_ids.dtype)
    )
    return executor(params, input_ids, rng, prompt_pad_count)


def _pad_positions(pad_count: jnp.ndarray, n: int) -> jnp.ndarray:
    """(b, n) True where the right-aligned window slot is left padding."""
    return jnp.arange(n)[None, :] < pad_count[:, None]


_FINGERPRINTS: dict = {}  # id(model) -> (weakref, repr string)


def model_fingerprint(model) -> str:
    """Architecture fingerprint for executor-cache keys. Flax modules with
    mutable config dataclasses are not hashable, and ``repr(model)`` renders
    the whole module tree — too slow to rebuild per call — so the repr is
    memoized per live module instance (id-keyed, weakref-validated)."""
    import weakref

    entry = _FINGERPRINTS.get(id(model))
    if entry is not None:
        ref, fingerprint = entry
        if ref() is model:
            return fingerprint
    fingerprint = repr(model)
    try:
        ref = weakref.ref(model)
    except TypeError:  # un-weakref-able object: don't cache
        return fingerprint
    _FINGERPRINTS[id(model)] = (ref, fingerprint)
    if len(_FINGERPRINTS) > 256:  # drop dead entries
        for mid in [m for m, (r, _) in _FINGERPRINTS.items() if r() is None]:
            del _FINGERPRINTS[mid]
    return fingerprint


def ledger_model_id(model) -> str:
    """Short stable identity for ledger components: the architecture
    fingerprint is a whole module-tree repr — far too long to display in a
    compile table or diff line — so components carry its hash. Two models
    share an ID iff they share a fingerprint (the same equivalence the
    executor cache keys use)."""
    import hashlib

    digest = hashlib.md5(model_fingerprint(model).encode()).hexdigest()[:10]
    return f"{type(model).__qualname__}:{digest}"


#: Process-wide hit/miss/evict counters across ALL executor caches (the
#: generation cache here and the beam cache in ``beam.py``). A miss means a
#: fresh trace+compile (~1.5 s at test scale) — the serving layer reads these
#: so retracing under real traffic is observable rather than silent. The
#: counters live on the process-wide observability registry under the
#: canonical ``executor_cache_*_total`` names (docs/observability.md); the
#: bare "hits"/"misses"/"evictions" keys remain as deprecation aliases.
_CACHE_COUNTERS = {
    "hits": "executor_cache_hits_total",
    "misses": "executor_cache_misses_total",
    "evictions": "executor_cache_evictions_total",
}


def executor_cache_stats() -> dict:
    """Snapshot of the shared executor-cache counters, under both the
    canonical registry names (``executor_cache_hits_total``, ...) and the
    legacy short keys (``hits``, ...) — prefer the canonical ones; the
    aliases exist for the serve CLI and tests written before the
    unified telemetry layer."""
    from perceiver_io_tpu.observability import default_registry

    reg = default_registry()
    out = {}
    for alias, name in _CACHE_COUNTERS.items():
        value = int(reg.counter(name))
        out[alias] = value
        out[name] = value
    return out


#: extra executor caches (e.g. the slot engine's, ``serving/slots.py``)
#: registered so :func:`reset_executor_caches` clears them too without a
#: static import cycle (serving imports this module, not vice versa)
_EXTRA_CACHES: list = []


def register_executor_cache(cache: dict) -> dict:
    """Register an executor cache dict for :func:`reset_executor_caches`;
    returns it for inline use at module scope."""
    _EXTRA_CACHES.append(cache)
    return cache


def reset_executor_caches() -> None:
    """Drop every cached executor and zero the counters (test isolation and
    serving-warmup measurement hook). Rewinding the global counters makes
    live ``ServingEngine`` instances' construction-time snapshots stale —
    their ``stats()`` deltas clamp at 0 rather than going negative, but
    create engines after the reset when exact counts matter. The compile
    ledger's records and identity history reset too: the builds they
    describe no longer exist, and a post-reset rebuild is a cold compile,
    not a retrace of a dropped executor."""
    from perceiver_io_tpu.inference import beam
    from perceiver_io_tpu.observability import default_ledger, default_registry

    _EXECUTOR_CACHE.clear()
    beam._EXECUTOR_CACHE.clear()
    for cache in _EXTRA_CACHES:
        cache.clear()
    default_registry().reset("executor_cache_")
    default_registry().reset("compile_")
    default_registry().reset("retrace_")
    default_ledger().reset()


def cached_executor(cache: dict, key, build, *, max_entries: int = 64,
                    ledger_site: Optional[str] = None,
                    ledger_components: Optional[dict] = None):
    """FIFO-bounded compile-once cache shared by the generation, beam, and
    slot executors: ``build()`` is called (and jitted) only on a key miss.

    ``ledger_site``/``ledger_components`` opt the fresh build into the
    device-cost ledger (``observability/ledger.py``): the executor is
    wrapped so its first call is AOT-compiled, timed, and cost/memory-
    analyzed under ``ledger_site``, with the NAMED ``ledger_components``
    diffed against the previous build of the same (site, model) identity
    for retrace attribution. Pass ``ledger_components`` as a ZERO-ARG
    CALLABLE: component assembly (model-id hashing, config normalization)
    is miss-only work, and every caller sits on a per-dispatch hot path
    where the cache hits."""
    from perceiver_io_tpu.observability import default_registry

    reg = default_registry()
    cached = cache.get(key)
    if cached is not None:
        reg.inc("executor_cache_hits_total")
        return cached
    reg.inc("executor_cache_misses_total")
    executor = build()
    if ledger_site is not None:
        from perceiver_io_tpu.observability import default_ledger

        components = (
            ledger_components() if callable(ledger_components)
            else (ledger_components or {})
        )
        executor = default_ledger().wrap(
            executor, site=ledger_site, components=components
        )
    if len(cache) >= max_entries:
        cache.pop(next(iter(cache)))
        reg.inc("executor_cache_evictions_total")
    cache[key] = executor
    return executor


_EXECUTOR_CACHE: dict = {}


def _generation_executor(
    model, config: GenerationConfig, b: int, prompt_len: int,
    num_latents: int, s1: int, s2: int, ids_dtype: str,
):
    """Build (once) and jit the full generation program for one static plan.

    Re-tracing the eager body cost ~1.5 s per :func:`generate` call (vs
    ~2 ms/token of actual compute at test scale); this cache makes repeated
    pipeline calls with the same shape/config dispatch a compiled program.
    Keyed by the module's fingerprint, the frozen :class:`GenerationConfig`,
    shapes, the phase plan, and the one trace-time environment switch
    (:func:`~perceiver_io_tpu.ops.ragged_attention.trace_env`) — a
    mid-process toggle must rebuild the executor, not silently reuse a trace
    captured under the other setting."""
    from perceiver_io_tpu.ops.ragged_attention import trace_env

    key = (
        type(model).__qualname__, model_fingerprint(model), config,
        b, prompt_len, num_latents, s1, s2, ids_dtype, trace_env(),
    )
    return cached_executor(
        _EXECUTOR_CACHE, key,
        lambda: _build_generation_executor(
            model, config, b, prompt_len, num_latents, s1, s2, ids_dtype
        ),
        ledger_site="generate",
        ledger_components=lambda: {
            "model": ledger_model_id(model),
            # max_new_tokens is routine per-request variation already
            # captured by phase_plan (s2 is the compiled scan length);
            # `config` means sampling/eos/latents (docs/observability.md)
            "config": dataclasses.replace(config, max_new_tokens=0),
            "bucket_shape": f"{b}x{prompt_len}",
            "num_latents": num_latents,
            "phase_plan": f"s1={s1},s2={s2}",
            "ids_dtype": ids_dtype,
            "trace_env": trace_env(),
        },
    )


def _build_generation_executor(
    model, config: GenerationConfig, b: int, prompt_len: int,
    num_latents: int, s1: int, s2: int, ids_dtype: str,
):
    n = model.max_seq_len
    max_latents = model.max_latents

    def advance(window, pad_count, finished, token, m):
        if config.eos_token_id is not None:
            token = jnp.where(finished, config.pad_token_id, token)
            finished = finished | (token == config.eos_token_id)
        window = jnp.concatenate(
            [window[:, 1:], token[:, None].astype(window.dtype)], axis=1
        )
        pad_count = jnp.maximum(pad_count - 1, 0)
        m = jnp.minimum(m + 1, max_latents)
        return window, pad_count, finished, token, m

    # EOS unreachable until min_new_tokens (applies to greedy and sampling,
    # not only beam — HF MinNewTokensLengthLogitsProcessor).
    min_new = (
        min(config.min_new_tokens, config.max_new_tokens)
        if config.eos_token_id is not None
        else 0
    )

    def mask_eos_until_min(logits, t):
        return apply_min_new_tokens(logits, t, min_new, config.eos_token_id or 0)

    def run(params, input_ids, rng, prompt_pad_count):
        # Right-align the prompt into the full-size window.
        window = jnp.full((b, n), config.pad_token_id, input_ids.dtype)
        window = window.at[:, n - prompt_len :].set(input_ids)
        pad_count = prompt_pad_count.astype(jnp.int32) + (n - prompt_len)
        step_rngs = jax.random.split(rng, config.max_new_tokens)

        token_blocks = []
        m0 = jnp.asarray(num_latents, jnp.int32)
        finished = jnp.zeros((b,), bool)
        cache = length = logits = None

        if s2 > 0:
            logits, cache, length, _ = model.apply(
                {"params": params}, window, pad_count, m0, method=_decode_prefill
            )

        if s1 > 0:

            def cached_step(carry, xs):
                step_rng, t = xs
                window, pad_count, finished, logits, cache, length, m = carry
                token = sample_logits(
                    step_rng, mask_eos_until_min(logits, t), config.sampling,
                    window, _pad_positions(pad_count, n),
                )
                window, pad_count, finished, token, _ = advance(
                    window, pad_count, finished, token, m
                )
                logits, cache, length, m = model.apply(
                    {"params": params}, token, cache, length, m, method=_decode_step
                )
                return (window, pad_count, finished, logits, cache, length, m), token

            carry = (window, pad_count, finished, logits, cache, length, m0)
            carry, tokens = jax.lax.scan(
                cached_step, carry, (step_rngs[:s1], jnp.arange(s1))
            )
            window, pad_count, finished, logits, cache, length, m0 = carry
            token_blocks.append(tokens)

        if s2 > s1:
            cross_k, cross_v = cache["cross_k"], cache["cross_v"]
            m_full = jnp.asarray(max_latents, jnp.int32)

            # Hoisted scatter-index arithmetic: the migrated and appended
            # cache indices are affine in the step counter, so the whole
            # (T, b, 2) sequence is computed once here and fed through the
            # scan's xs instead of being re-derived inside every iteration
            # (the boundary step is bookkeeping-bound on CPU).
            t_rel = jnp.arange(s2 - s1, dtype=jnp.int32)
            pad_seq = jnp.maximum(pad_count[None, :] - (t_rel + 1)[:, None], 0)
            mig_seq = jnp.maximum((n - max_latents - 1) - pad_seq, 0)
            len_seq = length[None, :] + t_rel[:, None]
            write_idx_seq = jnp.stack([mig_seq, len_seq], axis=-1)

            def boundary_step(carry, xs):
                step_rng, t, write_idx = xs
                window, pad_count, finished, logits, cross_k, cross_v, length = carry
                token = sample_logits(
                    step_rng, mask_eos_until_min(logits, t), config.sampling,
                    window, _pad_positions(pad_count, n),
                )
                window, pad_count, finished, token, _ = advance(
                    window, pad_count, finished, token, m_full
                )
                logits, cross_k, cross_v, length = model.apply(
                    {"params": params},
                    window,
                    pad_count,
                    cross_k,
                    cross_v,
                    length,
                    write_idx,
                    method=_decode_step_boundary,
                )
                return (
                    (window, pad_count, finished, logits, cross_k, cross_v, length),
                    token,
                )

            carry = (window, pad_count, finished, logits, cross_k, cross_v, length)
            carry, tokens = jax.lax.scan(
                boundary_step, carry,
                (step_rngs[s1:s2], jnp.arange(s1, s2), write_idx_seq),
            )
            window, pad_count, finished = carry[0], carry[1], carry[2]
            m0 = m_full
            token_blocks.append(tokens)

        if config.max_new_tokens > s2:

            def step(carry, xs):
                step_rng, t = xs
                window, pad_count, m, finished = carry
                logits = model.apply(
                    {"params": params}, window, pad_count, m, method=_decode_forward
                )
                token = sample_logits(
                    step_rng, mask_eos_until_min(logits, t), config.sampling,
                    window, _pad_positions(pad_count, n),
                )
                window, pad_count, finished, token, m = advance(
                    window, pad_count, finished, token, m
                )
                return (window, pad_count, m, finished), token

            carry = (window, pad_count, m0, finished)
            _, tokens = jax.lax.scan(
                step, carry, (step_rngs[s2:], jnp.arange(s2, config.max_new_tokens))
            )
            token_blocks.append(tokens)

        return jnp.concatenate(token_blocks, axis=0).T.astype(
            jnp.dtype(ids_dtype)
        )

    return jax.jit(run)

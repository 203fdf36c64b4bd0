"""Learned sparse attention: the keys a lightning indexer selects for each
query (DeepSeek-V3.2's sparse attention; docs/lm.md), and the indexer's own
loss.

The indexer scores every causal pair, ``I[t, s] = sum_j w_j[t] relu(qI_j[t] .
kI[s])`` in float32 (its products take the compute dtype's inputs and
accumulate in float32), and query ``t`` keeps the ``min(t + 1, topk)`` keys
``s <= t`` of largest score: exactly the set ``jax.lax.top_k`` returns, ties
to the lower position. The selection is found without a sort: the
``topk``-th largest score of a row is read off the scores' bit patterns a hex
digit at a time (eight passes that count, for fifteen candidates each, the
scores at or over it), and the ties at that score are cut at a position found
the same way.

**The bits.** The selection travels as a packed ``(b, n / 32, n)`` int32
mask laid out for the flash kernels' blocks of ``R = selection_rows(n)``
query rows: row ``r`` of a block is bit ``r // (R / 32)`` of word ``r % (R /
32)``, so a kernel unpacks its block pair's words with one stack and one shift
a row (``flash_attention._selected_block``). 32 MB a layer at 16,384
positions; a recomputed layer keeps it by name (``SELECTION_NAME``) and does
not select again.

**The loss.** ``L_I = mean_t KL(p[t] || softmax_{s in S_t} I[t, s])``, with
``p`` the heads' mean of the attention's probabilities over the selected keys,
detached: the indexer learns to score the keys the attention weighs. It is
computed in blocks of ``INDEX_BLOCK`` query rows, each recomputed in the
backward pass, so nothing ``(n, n)`` in float32 lives whole.

Both passes are XLA's (``jax.named_scope("indexer")`` around them in the
model): the kernels of this module are the flash kernels, which take the
selection block by block and skip a block pair that holds no selected key.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: traced attention calls that took the selection path (docs/observability.md)
SELECTION_COUNTER = "sparse_attention_call_total"
#: ``checkpoint_name`` of the packed selection: a recomputed layer keeps it
SELECTION_NAME = "sparse_selection"
#: query rows the indexer's two passes take at a time
INDEX_BLOCK = 128


def selection_rows(n: int) -> int:
    """``R``: the query rows whose bits share a column of words, the flash
    kernels' row block at ``n`` positions; where they have none, ``n`` rounded
    up to whole words (rows past ``n`` are padding, selected and read by no
    one)."""
    from perceiver_io_tpu.ops.flash_attention import _pick_block

    return _pick_block(n) or -(-n // 32) * 32


def _padded_rows(n: int) -> int:
    """Query rows the bits hold: ``n`` in whole blocks of ``selection_rows(n)``."""
    rows = selection_rows(n)
    return -(-n // rows) * rows


def _pad_rows(x, axis: int, n: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, _padded_rows(n) - n)
    return jnp.pad(x, pad) if pad[axis][1] else x


def _index_block(n: int) -> int:
    rows = selection_rows(n)
    return INDEX_BLOCK if rows % INDEX_BLOCK == 0 else rows


def indexer_scores(q_i: jnp.ndarray, k_i: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``(b, m, n)`` float32 ``sum_j w[.., j] relu(q_i[.., j, :] . k_i)`` for
    ``q_i`` ``(b, m, H, d)``, ``k_i`` ``(b, n, d)`` and ``w`` ``(b, m, H)``."""
    s = jnp.einsum("bqhd,bkd->bqhk", q_i, k_i, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w.astype(jnp.float32)[..., None], axis=2)


def _sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 in the same order (the total order ``lax.top_k``
    sorts by: ``-0 < +0``)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def _largest_under(count, bits: int) -> jnp.ndarray:
    """The largest ``v`` of ``bits`` bits with ``count(v)`` true, for a
    ``count`` that is true at 0 and false from some ``v`` on, one hex digit at
    a time: fifteen candidates a pass, ``count`` taking them ``(..., 15)``."""
    digits = jnp.arange(1, 16, dtype=jnp.uint32)
    found = None
    for shift in range(4 * ((bits + 3) // 4) - 4, -1, -4):
        base = jnp.uint32(0) if found is None else found
        cands = base[..., None] + (digits << shift) if found is not None else digits << shift
        digit = jnp.sum(count(cands), axis=-1, dtype=jnp.uint32)
        found = base + (digit << shift)
    return found


def select_block(scores: jnp.ndarray, rows: jnp.ndarray, topk: int) -> jnp.ndarray:
    """``(b, m, n)`` bool: for the queries at positions ``rows`` ``(m,)``, the
    ``min(t + 1, topk)`` keys ``s <= t`` of largest ``scores``, ties to the
    lower ``s``: the set ``lax.top_k`` takes from a row whose later keys are
    masked."""
    n = scores.shape[-1]
    cols = jnp.arange(n, dtype=jnp.int32)
    causal = cols[None, :] <= rows[:, None]
    # masked keys at 0, under every score's key
    keys = jnp.where(causal, _sortable(scores), jnp.uint32(0))
    want = jnp.minimum(rows + 1, topk).astype(jnp.int32)[None, :, None]  # (1, m, 1)

    def at_least(cands):  # (b, m, 15): rows with `want` keys at or over the candidate
        return jnp.sum(keys[..., None, :] >= cands[..., None], axis=-1) >= want

    kth = _largest_under(at_least, 32)[..., None]  # the want-th largest key
    over = keys > kth
    tied = keys == kth
    room = want - jnp.sum(over, axis=-1, keepdims=True)  # >= 1 of the tied keys to take

    def before(cands):  # tied keys at positions under the candidate, fewer than room
        below = cols < cands[..., None].astype(jnp.int32)
        return jnp.sum(tied[..., None, :] & below, axis=-1) < room

    last = _largest_under(before, max(1, (n - 1).bit_length()))[..., None].astype(jnp.int32)
    return causal & (over | (tied & (cols <= last)))


def pack(chosen: jnp.ndarray, first_row, rows_per_block: int) -> jnp.ndarray:
    """``(b, R / 32, n)`` int32: the bits of ``chosen`` ``(b, m, n)``, the
    rows from ``first_row`` on, placed in the words of their block of ``R =
    rows_per_block`` rows. ``m`` is a multiple of ``R / 32`` and the rows do
    not cross a block; what other rows of the block set is added by the
    caller (the bits differ, so a sum is an or)."""
    b, m, n = chosen.shape
    per = rows_per_block // 32
    first_bit = (first_row % rows_per_block) // per
    shifts = (first_bit + jnp.arange(m // per)).astype(jnp.uint32)[:, None, None]
    words = chosen.reshape(b, m // per, per, n).astype(jnp.uint32) << shifts
    return jax.lax.bitcast_convert_type(jnp.sum(words, axis=1, dtype=jnp.uint32), jnp.int32)


def unpack(bits: jnp.ndarray, first_row, m: int) -> jnp.ndarray:
    """``(b, m, n)`` bool: the selection of the ``m`` rows from ``first_row``
    on (within one block of ``selection_rows(n)`` rows; ``m`` a multiple of
    its words a column)."""
    b, _, n = bits.shape
    rows = selection_rows(n)
    per = rows // 32
    words = jax.lax.dynamic_slice_in_dim(bits, (first_row // rows) * per, per, axis=1)
    shifts = (first_row % rows + jnp.arange(m)) // per
    return (jnp.right_shift(jnp.tile(words, (1, m // per, 1)), shifts[None, :, None]) & 1) != 0


def unpack_all(bits: jnp.ndarray) -> jnp.ndarray:
    """``(b, n, n)`` bool: every row's selection."""
    b, words, n = bits.shape
    rows = selection_rows(n)
    blocks = bits.reshape(b, words * 32 // rows, 1, rows // 32, n)
    shifts = jnp.arange(32)[None, None, :, None, None]
    return ((jnp.right_shift(blocks, shifts) & 1) != 0).reshape(b, words * 32, n)[:, :n]


def select(q_i: jnp.ndarray, k_i: jnp.ndarray, w: jnp.ndarray, topk: int) -> jnp.ndarray:
    """The packed selection ``(b, rows / 32, n)`` int32 of every query row
    (``rows`` is ``n`` in whole blocks of ``selection_rows(n)``), from
    the indexer's ``q_i`` ``(b, n, H, d)``, ``k_i`` ``(b, n, d)`` and ``w``
    ``(b, n, H)``, in blocks of ``INDEX_BLOCK`` rows. No gradient."""
    q_i, k_i, w = (jax.lax.stop_gradient(x) for x in (q_i, k_i, w))
    b, n, heads, d = q_i.shape
    rows, m, total = selection_rows(n), _index_block(n), _padded_rows(n)
    q_i, w = _pad_rows(q_i, 1, n), _pad_rows(w, 1, n)

    def block(at):
        q_blk, w_blk, first = at
        chosen = select_block(indexer_scores(q_blk, k_i, w_blk), first + jnp.arange(m), topk)
        return pack(chosen, first, rows)

    by_block = lambda x: jnp.moveaxis(x.reshape(b, total // m, m, *x.shape[2:]), 1, 0)
    with jax.named_scope("selection"):
        parts = jax.lax.map(block, (by_block(q_i), by_block(w), jnp.arange(0, total, m)))
    # (rows / m, b, R / 32, n): the row blocks' parts of each kernel block, summed
    parts = parts.reshape(total // rows, rows // m, b, rows // 32, n)
    parts = jax.lax.bitcast_convert_type(parts, jnp.uint32).sum(axis=1, dtype=jnp.uint32)
    bits = jnp.moveaxis(jax.lax.bitcast_convert_type(parts, jnp.int32), 0, 1)
    return bits.reshape(b, total // 32, n)


def attention_xla(q, k, v, bits, pad_mask: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The einsum path of :func:`flash_attention.flash_attention_selected`:
    ``(o, lse)`` with the ``(b, h, n, n)`` scores whole. Grouped heads as
    ``ops/attention.py`` reads them."""
    b, h, n, d = q.shape
    hk = k.shape[1]
    chosen = unpack_all(bits)
    if pad_mask is not None:
        chosen = chosen & ~pad_mask[:, None, :]
    qg = q.reshape(b, hk, h // hk, n, d)
    logits = jnp.einsum("bkgic,bkjc->bkgij", qg, k, preferred_element_type=jnp.float32)
    logits = jnp.where(chosen[:, None, None], logits, jnp.finfo(jnp.float32).min)
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[..., None]).astype(v.dtype)
    o = jnp.einsum("bkgij,bkjc->bkgic", probs, v)
    return o.reshape(b, h, n, v.shape[-1]), lse.reshape(b, h, n)


def indexer_loss(q, k, lse, q_i, k_i, w, bits: Optional[jnp.ndarray]) -> jnp.ndarray:
    """``mean_t KL(p[t] || softmax_{s in S_t} I[t, s])`` over the batch's
    rows: ``p`` the heads' mean of ``exp(q . k - lse)`` over the selected keys
    (``q`` ``(b, h, n, d)`` as the attention was given it, scaled and rotated,
    ``k`` ``(b, hk, n, d)``, ``lse`` ``(b, h, n)``, or None to take it from
    the scores here), detached; ``I`` the indexer's scores. ``bits`` None:
    every causal key is selected. Gradients reach ``q_i``, ``k_i`` and ``w``
    alone."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    b, h, n, d = q.shape
    own_lse = lse is None
    lse = jnp.zeros((b, h, n), jnp.float32) if own_lse else jax.lax.stop_gradient(lse)
    hk = k.shape[1]
    m, total = _index_block(n), _padded_rows(n)
    q, lse, q_i, w = _pad_rows(q, 2, n), _pad_rows(lse, 2, n), _pad_rows(q_i, 1, n), _pad_rows(w, 1, n)
    cols = jnp.arange(n)

    def block(q_blk, lse_blk, qi_blk, w_blk, first):
        rows = first + jnp.arange(m)
        chosen = (cols[None, :] <= rows[:, None])[None] if bits is None else unpack(bits, first, m)
        chosen = chosen & (rows < n)[:, None]  # rows past n are padding
        logits = jnp.einsum("bkgic,bkjc->bkgij", q_blk.reshape(b, hk, h // hk, m, d), k,
                            preferred_element_type=jnp.float32).reshape(b, h, m, n)
        if own_lse:
            lse_blk = jax.nn.logsumexp(jnp.where(chosen[:, None], logits, jnp.finfo(jnp.float32).min), axis=-1)
        probs = jnp.where(chosen[:, None], jnp.exp(logits - lse_blk[..., None]), 0.0)
        p = jax.lax.stop_gradient(jnp.mean(probs, axis=1))  # (b, m, n)
        scores = jnp.where(chosen, indexer_scores(qi_blk, k_i, w_blk), jnp.finfo(jnp.float32).min)
        log_q = jnp.where(chosen, scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True), 0.0)
        kl = jnp.where(chosen, jax.scipy.special.xlogy(p, p) - p * log_q, 0.0)
        return jnp.sum(kl)

    block = jax.checkpoint(block)

    def step(total, at):
        return total + block(*at), None

    by_block = lambda x, axis: jnp.moveaxis(
        x.reshape(*x.shape[:axis], total // m, m, *x.shape[axis + 1:]), axis, 0)
    each = (by_block(q, 2), by_block(lse, 2), by_block(q_i, 1), by_block(w, 1), jnp.arange(0, total, m))
    with jax.named_scope("indexer_loss"):
        total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), each)
    return total / (b * n)

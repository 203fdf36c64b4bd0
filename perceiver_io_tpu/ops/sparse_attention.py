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
detached: the indexer learns to score the keys the attention weighs. Where the
rows are whole blocks of the flash kernels' (``_pick_block(n)``, the
selection's words in whole tiles), two Mosaic kernels compute it over the
causal block pairs that hold a selected key, which the flash kernels' flags
name (``flash_attention.block_flags``, by scalar prefetch); no other pair is
computed, nor are its keys fetched. In a grid step ``p`` is formed from the
attention's ``q``, ``k`` and the flash forward's ``lse`` (query head ``h``
reads key-value head ``h // group``, as in the flash kernels) and ``I`` from
the indexer's products, both in float32 and in VMEM alone. ``indexer_kl``
keeps, for each row, a running log-sum-exp of its selected scores and the
sums ``P = sum p``, ``sum p log p`` and ``sum p I``: ``KL = sum p log p - sum
p I + P lse_I``. ``indexer_kl_grad`` then forms ``dI = P softmax(I) - p`` on
the selected pairs, once rounded to the compute dtype for the products as
XLA's gradient is, and from it the float32 gradients of ``q_I`` and ``w``
(resident for a row block) and ``k_I`` (a query block's part a key block,
summed after). Under a gradient both run in the forward pass and the backward
scales the gradients by the cotangent; a recomputed layer keeps them by name
(``KL_GRAD_NAMES``). No logit, probability or score reaches HBM. Elsewhere
(rows that are no whole blocks, or no selection) the loss is XLA's,
:func:`indexer_loss_xla`: blocks of ``INDEX_BLOCK`` query rows, each
recomputed in the backward pass, so nothing ``(n, n)`` in float32 lives
whole.

The selection is XLA's (``jax.named_scope("indexer")`` around it and the loss
in the model); the flash kernels take it block by block and skip a block pair
that holds no selected key. The loss's kernels are named ``indexer_kl*``, so
nothing that reads the flash kernels by name counts them.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops import flash_attention as fa

#: traced attention calls that took the selection path (docs/observability.md)
SELECTION_COUNTER = "sparse_attention_call_total"
#: ``checkpoint_name`` of the packed selection: a recomputed layer keeps it
SELECTION_NAME = "sparse_selection"
#: query rows the indexer's two passes take at a time
INDEX_BLOCK = 128
#: traced indexer losses that ran the KL kernels (docs/observability.md)
KL_COUNTER = "indexer_kl_kernel_total"
#: ``checkpoint_name``s of the KL kernels' gradients of ``q_I``, ``k_I`` and
#: ``w``: a recomputed layer keeps them and does not run the kernels again
KL_GRAD_NAMES = ("indexer_kl_dq", "indexer_kl_dk", "indexer_kl_dw")


def selection_rows(n: int) -> int:
    """``R``: the query rows whose bits share a column of words, the flash
    kernels' row block at ``n`` positions; where they have none, ``n`` rounded
    up to whole words (rows past ``n`` are padding, selected and read by no
    one)."""
    return fa._pick_block(n) or -(-n // 32) * 32


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
    alone. The KL kernels where there is a selection and a log-sum-exp and
    the rows are whole blocks of the flash kernels' (counted at trace time in
    ``indexer_kl_kernel_total``), else :func:`indexer_loss_xla`."""
    from perceiver_io_tpu.observability import default_registry

    # declared by every indexer loss, so a program that ran XLA's exports 0
    default_registry().declare_counters(KL_COUNTER)
    b, _, n, _ = q.shape
    if bits is None or lse is None or not _kl_kernels_fit(q, k, q_i):
        return indexer_loss_xla(q, k, lse, q_i, k_i, w, bits)
    default_registry().inc(KL_COUNTER)
    q, k, lse = (jax.lax.stop_gradient(x) for x in (q, k, lse))
    with jax.named_scope("indexer_loss"):
        total = _kl_over_mesh(q, k, lse, q_i, k_i, w, bits)
    return total / (b * n)


def indexer_loss_xla(q, k, lse, q_i, k_i, w, bits: Optional[jnp.ndarray]) -> jnp.ndarray:
    """:func:`indexer_loss` in XLA, in blocks of ``INDEX_BLOCK`` query rows,
    each recomputed in the backward pass: at any ``n``, with or without a
    selection and a log-sum-exp."""
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


# The KL kernels. Both walk the grid ``(b, n / R, n / R)`` of block pairs
# (``R = selection_rows(n)``, the flash kernels' blocks), the key blocks
# innermost, and run the body of a pair whose flag says it holds a selected
# key: a causal pair, since the selection is a subset of the causal mask. A
# key block's index is held at the query block's own past the diagonal, so
# the pairs above it fetch nothing. Per-row sums stay lane-dense, ``(R,
# 128)``, each lane summing its keys of every block, and are reduced across
# the lanes once, at a row block's last step. ``p`` walks the key-value heads
# in a loop, the query heads of a group unrolled in it (a head's ``lse`` is a
# column of its group's ``(R, group)`` block); the indexer's heads go by in
# groups of up to 8 the same way, a head's ``w`` a column of its group's.
#
# MXU work a selected pair, in products of R x R: ``p`` one a query head of
# ``d`` channels and ``I`` one an indexer head of ``d_I`` in each kernel; in
# ``indexer_kl_grad`` three more an indexer head (its scores again beside its
# two gradients). At 16,384 rows, 32 heads of 128 and 16 of 64, 528 pairs:
# 3.7 TFLOP a layer.

#: the most VMEM a KL kernel asks for (``_kl_vmem_bytes``); past it the loss is XLA's
_KL_VMEM_CAP_BYTES = 64 * 1024 * 1024


def _kl_kernels_fit(q, k, q_i) -> bool:
    """Whether the KL kernels take these shapes: the flash kernels' row blocks
    with the selection's words in whole tiles, and both kernels within the
    VMEM cap."""
    return (fa.selection_blocks_fit(q.shape[2])
            and _kl_vmem_bytes(q, k, q_i, grads=True) <= _KL_VMEM_CAP_BYTES)


def _kl_vmem_bytes(q, k, q_i, grads: bool) -> int:
    """What a KL kernel asks for as ``vmem_limit_bytes``: every block in two
    buffers (lanes padded to 128, as Mosaic lays them out), the scratch, and
    eight ``(R, R)`` float32 temporaries. ``q_i`` as the model gives it,
    ``(b, n, H, d_I)``."""
    lanes = lambda x: -(-x // fa.LANES) * fa.LANES
    _, h, n, d = q.shape
    _, _, heads_i, d_i = q_i.shape
    hk = k.shape[1]
    r, itemsize = fa._pick_block(n), q.dtype.itemsize
    blocks = (
        (h + hk) * r * lanes(d) * itemsize           # q and k, every head of a block
        + hk * r * lanes(h // hk) * 4                # lse, (hk, R, group)
        + (heads_i + 1) * r * lanes(d_i) * itemsize  # q_I and k_I
        + heads_i * r * fa.LANES * 4 // _index_group(heads_i)  # w, (H / group_I, R, group_I)
        + r // 32 * r * 4                            # the bits
        + r * fa.LANES * 4                           # the rows' statistics
    )
    fixed = 5 * r * fa.LANES * 4 + 10 * r * r * 4    # the running sums, p and I, eight temporaries
    if grads:
        blocks += (heads_i + 1) * r * lanes(d_i) * 4 + r * lanes(heads_i) * 4  # dq_I, dk_I's part, dw
        fixed += heads_i * r * fa.LANES * 4                                    # dw's lane sums
    return 2 * blocks + fixed


def _kl_over_mesh(q, k, lse, q_i, k_i, w, bits):
    """``_kl`` (the summed KL of the rows), inside ``shard_map`` when the
    ambient mesh has more than one device: batch over the ``data``/``fsdp``
    axes where they divide it, everything else whole on each device (the
    attention's heads, sharded over ``model`` outside, are gathered: every
    head is in ``p``)."""
    from jax.sharding import PartitionSpec as P

    from perceiver_io_tpu.ops.attention import _ambient_mesh
    from perceiver_io_tpu.parallel.mesh import BATCH_AXES

    mesh = _ambient_mesh()
    if mesh is None or mesh.size == 1:
        return _kl(q, k, lse, q_i, k_i, w, bits)
    axes = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    batch = axes if axes and q.shape[0] % math.prod(mesh.shape[a] for a in axes) == 0 else None
    args = (q, k, lse, q_i, k_i, w, bits)
    in_specs = tuple(P(batch, *(None,) * (x.ndim - 1)) for x in args)
    body = lambda *xs: _kl(*xs)[None]
    return jnp.sum(jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(batch),
                                 check_vma=False)(*args))


@jax.custom_vjp
def _kl(q, k, lse, q_i, k_i, w, bits):
    """The summed KL of every row: ``indexer_kl`` alone."""
    return jnp.sum(_kl_rows(q, k, lse, q_i, k_i, w, bits)[0][..., 2])


def _kl_fwd(q, k, lse, q_i, k_i, w, bits):
    stats, args = _kl_rows(q, k, lse, q_i, k_i, w, bits)
    dq, dk, dw = (checkpoint_name(g, name) for g, name in zip(_kl_grads(stats, *args), KL_GRAD_NAMES))
    zeros = jnp.zeros((0,), q_i.dtype), jnp.zeros((0,), k_i.dtype)  # the inputs' dtypes
    return jnp.sum(stats[..., 2]), (dq, dk, dw, zeros)


def _kl_bwd(res, ct):
    dq, dk, dw, (q_dtype, k_dtype) = res
    # the gradients of the sum, float32 until the cotangent has scaled them
    dq = jnp.swapaxes(dq * ct, 1, 2).astype(q_dtype.dtype)
    return None, None, None, dq, (dk * ct).astype(k_dtype.dtype), dw * ct, None


_kl.defvjp(_kl_fwd, _kl_bwd)


def _kl_rows(q, k, lse, q_i, k_i, w, bits):
    """``indexer_kl``: ``(b, n, 128)`` float32, lane 0 of row ``t`` its
    ``lse_I``, lane 1 its ``P``, lane 2 its KL; and the kernels' inputs as
    ``indexer_kl_grad`` takes them too."""
    b, h, n, _ = q.shape
    hk = k.shape[1]
    # lse (b, hk, n, group): a key-value head's query heads side by side, a
    # row's lse of each a column; q_I's heads leading, a head an (R, d_I) slice
    lse_g = jnp.swapaxes(lse.reshape(b, hk, h // hk, n), 2, 3)
    heads_i = q_i.shape[2]
    # w likewise, the indexer's heads in groups of up to 8 (``_index_group``)
    w_g = jnp.swapaxes(w.reshape(b, n, heads_i // _index_group(heads_i), -1), 1, 2)
    args = (q, k, lse_g, jnp.swapaxes(q_i, 1, 2), k_i, w_g, bits)
    flags = fa.block_flags(bits, n)
    r = fa._pick_block(n)

    def kernel(flags_ref, *refs):
        *ins, stats_ref, m_sc, l_sc, mass_sc, plogp_sc, pi_sc, p_sc, scores_sc = refs
        i_idx, j_idx = pl.program_id(1), pl.program_id(2)

        @pl.when(j_idx == 0)
        def _():
            m_sc[:] = jnp.full_like(m_sc, fa._MASK)
            for acc in (l_sc, mass_sc, plogp_sc, pi_sc):
                acc[:] = jnp.zeros_like(acc)

        @pl.when(fa._flagged(flags_ref, i_idx, j_idx, n // r, n // r))
        def _():
            chosen, p, scores = _kl_block(ins, p_sc, scores_sc)
            # a running log-sum-exp of the selected scores, a lane of each key block's
            masked = jnp.where(chosen, scores, fa._MASK)
            m_prev = m_sc[:]
            m_new = jnp.maximum(m_prev, _fold(masked, jnp.maximum))
            e = jnp.where(chosen, jnp.exp(masked - pltpu.repeat(m_new, r // fa.LANES, 1)), 0.0)
            l_sc[:] = jnp.exp(m_prev - m_new) * l_sc[:] + _fold(e)
            m_sc[:] = m_new
            mass_sc[:] += _fold(p)
            plogp_sc[:] += _fold(p * jnp.log(jnp.where(p > 0.0, p, 1.0)))
            pi_sc[:] += _fold(p * scores)

        @pl.when(j_idx == n // r - 1)
        def _():
            m = m_sc[:]
            top = jnp.max(m, axis=1, keepdims=True)
            total = jnp.sum(l_sc[:] * jnp.exp(m - top), axis=1, keepdims=True)
            # a row without a selected key: lse_I 0, P 0, KL 0
            lse_i = jnp.where(total > 0.0, top + jnp.log(jnp.where(total > 0.0, total, 1.0)), 0.0)
            mass = jnp.sum(mass_sc[:], axis=1, keepdims=True)
            kl = (jnp.sum(plogp_sc[:], axis=1, keepdims=True) - jnp.sum(pi_sc[:], axis=1, keepdims=True)
                  + mass * lse_i)
            lane = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape[1:], 1)
            stats_ref[0] = jnp.where(lane == 0, lse_i, jnp.where(lane == 1, mass, jnp.where(lane == 2, kl, 0.0)))

    stats = fa._pallas(
        kernel, list(args), flags, name="indexer_kl", grid=(b, n // r, n // r),
        in_specs=_kl_in_specs(args, r),
        out_specs=pl.BlockSpec((1, r, fa.LANES), lambda b_, i_, j_: (b_, i_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, fa.LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((r, fa.LANES), jnp.float32)] * 5 + [pltpu.VMEM((r, r), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_kl_vmem_bytes(q, k, q_i, grads=False),
        ),
    )
    return stats, (*args, flags)


def _kl_grads(stats, q, k, lse_g, q_it, k_i, w_g, bits, flags):
    """``indexer_kl_grad``: the gradients of the summed KL, float32: of
    ``q_I`` ``(b, H, n, d_I)`` (the heads leading), of ``k_I`` and of ``w``.
    ``k_I``'s is written a query block's part a key block (zeros where the
    pair holds no selected key) and summed over the query blocks here."""
    b, h, n, _ = q.shape
    heads_i, d_i = q_it.shape[1], q_it.shape[3]
    group_i = w_g.shape[3]
    r = fa._pick_block(n)
    args = (q, k, lse_g, q_it, k_i, w_g, bits, stats)

    def kernel(flags_ref, *refs):
        *ins, stats_ref, dq_ref, dk_ref, dw_ref, dw_sc, p_sc, scores_sc = refs
        qi_ref, ki_ref, w_ref = ins[3], ins[4], ins[5]
        i_idx, j_idx = pl.program_id(1), pl.program_id(2)
        flagged = fa._flagged(flags_ref, i_idx, j_idx, n // r, n // r)

        @pl.when(j_idx == 0)
        def _():
            dq_ref[:] = jnp.zeros_like(dq_ref)
            dw_sc[:] = jnp.zeros_like(dw_sc)

        @pl.when(flagged)
        def _():
            chosen, p, scores = _kl_block(ins, p_sc, scores_sc)
            row = stats_ref[0]
            # dI, before the cotangent: P softmax(I) - p on the selected keys
            p_sc[:] = jnp.where(chosen, row[:, 1:2] * jnp.exp(scores - row[:, 0:1]) - p, 0.0)
            ki = ki_ref[0]
            dk_ref[:] = jnp.zeros_like(dk_ref)

            def by_group(grp, carry):
                cols, grad = w_ref[0, grp], p_sc[:]
                for g in range(group_i):
                    at = grp * group_i + g
                    qi = qi_ref[0, at]
                    s = _dot_t(qi, ki)
                    ds = jnp.where(s > 0.0, grad * cols[:, g:g + 1], 0.0).astype(qi.dtype)
                    dq_ref[0, at] += jax.lax.dot_general(
                        ds, ki, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                    dk_ref[0, 0] += jax.lax.dot_general(
                        ds, qi, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                    dw_sc[at] += _fold(grad * jnp.maximum(s, 0.0))
                return carry

            jax.lax.fori_loop(0, heads_i // group_i, by_group, 0)

        @pl.when(jnp.logical_not(flagged) & (j_idx <= i_idx))
        def _():
            dk_ref[:] = jnp.zeros_like(dk_ref)

        @pl.when(j_idx == n // r - 1)
        def _():
            for at in range(heads_i):
                dw_ref[0, :, at:at + 1] = jnp.sum(dw_sc[at], axis=1, keepdims=True)

    in_specs = _kl_in_specs(args[:-1], r) + [pl.BlockSpec((1, r, fa.LANES), lambda b_, i_, j_: (b_, i_, 0))]
    ni = n // r
    dq, dk, dw = fa._pallas(
        kernel, list(args), flags, name="indexer_kl_grad", grid=(b, ni, ni),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, heads_i, r, d_i), lambda b_, i_, j_: (b_, 0, i_, 0)),
            # a query block's part of a key block's gradient, held past the diagonal
            pl.BlockSpec((1, 1, r, d_i), lambda b_, i_, j_: (b_, i_, jnp.minimum(j_, i_), 0)),
            pl.BlockSpec((1, r, heads_i), lambda b_, i_, j_: (b_, i_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, heads_i, n, d_i), jnp.float32),
            jax.ShapeDtypeStruct((b, ni, n, d_i), jnp.float32),
            jax.ShapeDtypeStruct((b, n, heads_i), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((heads_i, r, fa.LANES), jnp.float32)] + [pltpu.VMEM((r, r), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_kl_vmem_bytes(q, k, jnp.swapaxes(q_it, 1, 2), grads=True),
        ),
    )
    # the parts above the diagonal were never written
    below = jnp.arange(ni)[:, None] >= jnp.arange(n)[None, :] // r
    dk = jnp.sum(jnp.where(below[None, :, :, None], dk, 0.0), axis=1)
    return dq, dk, dw


def _kl_in_specs(args, r: int) -> list:
    """The blocks of ``q``, ``k``, ``lse`` ``(b, hk, n, group)``, ``q_I``
    ``(b, H, n, d_I)``, ``k_I``, ``w`` ``(b, H / group_I, n, group_I)`` and
    the bits under the grid ``(b, i, j)``: the query side by row block, the
    key side by key block, held at the diagonal past it."""
    q, k, lse_g, q_it, k_i, w_g, _ = args
    kv = lambda i_, j_: jnp.minimum(j_, i_)
    return [
        pl.BlockSpec((1, q.shape[1], r, q.shape[3]), lambda b_, i_, j_: (b_, 0, i_, 0)),
        pl.BlockSpec((1, k.shape[1], r, k.shape[3]), lambda b_, i_, j_: (b_, 0, kv(i_, j_), 0)),
        pl.BlockSpec((1, lse_g.shape[1], r, lse_g.shape[3]), lambda b_, i_, j_: (b_, 0, i_, 0)),
        pl.BlockSpec((1, q_it.shape[1], r, q_it.shape[3]), lambda b_, i_, j_: (b_, 0, i_, 0)),
        pl.BlockSpec((1, r, k_i.shape[2]), lambda b_, i_, j_: (b_, kv(i_, j_), 0)),
        pl.BlockSpec((1, w_g.shape[1], r, w_g.shape[3]), lambda b_, i_, j_: (b_, 0, i_, 0)),
        pl.BlockSpec((1, r // 32, r), lambda b_, i_, j_: (b_, i_, kv(i_, j_))),
    ]


def _dot_t(a, c):
    """``a @ c.T`` in float32, from the inputs' dtype."""
    return jax.lax.dot_general(a, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _index_group(heads: int) -> int:
    """The indexer's heads a loop step unrolls: up to 8, dividing them."""
    return math.gcd(heads, 8)


def _kl_block(ins, p_sc, scores_sc):
    """A selected block pair's ``(chosen, p, I)``: the selection, the heads'
    mean of ``exp(q . k - lse)`` over it (0 elsewhere) and the indexer's
    scores, ``(R, R)`` float32, summed in ``p_sc`` and ``scores_sc`` a group
    of heads at a time: a key-value head's query heads, up to 8 indexer
    heads."""
    q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, bits_ref = ins
    hk, group = k_ref.shape[1], q_ref.shape[1] // k_ref.shape[1]
    r = q_ref.shape[2]

    def by_group(kv, carry):
        kb, cols = k_ref[0, kv], lse_ref[0, kv]
        total = None
        for g in range(group):
            e = jnp.exp(_dot_t(q_ref[0, kv * group + g], kb) - cols[:, g:g + 1])
            total = e if total is None else total + e
        p_sc[:] += total
        return carry

    def scores_by_group(grp, carry):
        cols, total = w_ref[0, grp], None
        for g in range(group_i):
            part = jnp.maximum(_dot_t(qi_ref[0, grp * group_i + g], ki), 0.0) * cols[:, g:g + 1]
            total = part if total is None else total + part
        scores_sc[:] += total
        return carry

    p_sc[:] = jnp.zeros_like(p_sc)
    jax.lax.fori_loop(0, hk, by_group, 0)
    ki, group_i = ki_ref[0], w_ref.shape[3]
    scores_sc[:] = jnp.zeros_like(scores_sc)
    jax.lax.fori_loop(0, w_ref.shape[1], scores_by_group, 0)
    chosen = fa._selected_block(bits_ref[0], r)
    return chosen, jnp.where(chosen, p_sc[:] / q_ref.shape[1], 0.0), scores_sc[:]


def _fold(x, op=jnp.add):
    """``(R, C)`` -> ``(R, 128)``: the lane tiles of ``x`` combined by ``op``."""
    out = x[:, :fa.LANES]
    for c in range(fa.LANES, x.shape[1], fa.LANES):
        out = op(out, x[:, c:c + fa.LANES])
    return out

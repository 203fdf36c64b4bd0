"""Pallas TPU flash attention for the Perceiver attention patterns.

The reference bounds attention memory by serializing over head groups
(``max_heads_parallel``, reference ``perceiver/model/core/modules.py:129-151``)
and still materializes the full ``(b, h, i, j)`` attention matrix per group.
Here the matrix never leaves VMEM: queries/keys/values are streamed block by
block from HBM, softmax runs online (running max / running sum), and the
backward pass recomputes probabilities blockwise from the saved logsumexp —
the standard flash-attention schedule, laid out for the TPU MXU.

Perceiver specifics the stock kernels don't cover:

- **right-aligned causal masking of unequal q/kv** — Perceiver AR latents
  (length ``i``) attend causally over ``[prefix ‖ latents]`` (length ``j``),
  so position ``r`` of the query may see kv positions ``c ≤ r + (j - i)``
  (reference mask ``triu(j-i+1)``, ``modules.py:120-125``). The offset is
  baked into the block mask and into block-level skipping: kv blocks wholly
  above the shifted diagonal are never computed.
- **key padding masks** (``True`` = pad, reference ``modules.py:97``) for the
  left-padded batches the text models use. Kernels are statically
  specialized on pad presence, so the common unpadded call streams no mask.
- **a sliding window** on a causal call (``window=W``): query ``t`` sees key
  ``s`` iff ``0 <= t + offset - s < W``. The window is a second static bound
  on the same mask, and the kernels' grids walk the band alone: the innermost
  grid dimension counts the blocks a band can touch (``_Band``), an index map
  adds the band's first block, and what little of that grid lies outside the
  band is skipped with its block index clamped, so it fetches nothing. A call
  without a window compiles to the kernels it always did.
- **a selection of keys per query** (learned sparse attention,
  :func:`flash_attention_selected`): query ``t`` sees the keys whose bits are
  set in its row of a packed ``(b, i / 32, j)`` mask
  (:mod:`perceiver_io_tpu.ops.sparse_attention` lays the bits out for the
  kernels' row blocks), a subset of the causal ones. The three kernels take
  the bits block by block and, through scalar prefetch, one flag a block
  pair: a pair that holds no selected key is skipped and costs no products.
  Every other pair costs what a causal pair costs. A call without a selection
  compiles to the kernels it always did.

Layout notes (mirroring what Mosaic compiles well): grid is
``(b, h, i_blocks, j_blocks)`` with the kv dimension innermost and
"arbitrary" semantics so the running-softmax scratch carries across kv
blocks; logsumexp residuals are kept lane-replicated ``(b, h, i, 128)`` in
float32 — cheap because every Perceiver query length is the latent count,
not the sequence length. Matmuls feed the MXU in the input dtype (bf16 in
training) with float32 accumulation; softmax math is float32 on the VPU.

The forward keeps the running max and sum lane-dense, ``(rows, 128)`` with
every lane equal, and widens them to the score tile's lanes, and to the
accumulator's where its width is a multiple of 128, with ``pltpu.repeat``:
read as a ``(rows, 1)`` column and broadcast back, as they were, they cost 9
to 43 % of the kernel on a v5e (the table above ``_forward``). A row's
arithmetic is the same either way: the same maximum over the same keys, the
same ``exp``, the same float32 sum and the same two products, so ``o`` and
``lse`` are the same bits.

The backward is one kernel, ``flash_bwd_dkv``, wherever the float32 dQ of one
query head fits ``_DQ_VMEM_BUDGET_BYTES`` (16 MiB: 32,768 rows at up to 128
channels). Its grid is ``(b, hk * slices, j_blocks, g * i_blocks)``: dK and dV
of a kv block accumulate over the q blocks (innermost), and each block pair's
``ds @ k`` is added to its rows of a dQ accumulator that stays in VMEM across
both block dimensions and is rounded once, when the last kv block has added
its part. Scores, mask, ``exp`` and ``dP`` are computed once per block pair:
five products. ``g`` is the largest divisor of the group (the query heads that
share a key-value head) whose dQ fits the budget together
(``_resident_heads``). The whole group, as in every Perceiver shape (for the
same reason as above) and with four 64-wide heads on 8192 rows: one grid slice
a key-value head. A smaller divisor (seven 128-wide heads on 16,384 rows: one):
``group // g`` slices a key-value head, each writing its own dK and dV in
float32, which are summed over the slices in float32 and rounded once, as the
resident accumulators are across the group; the traced backward is counted in
``flash_backward_sliced_total``. Where one head alone does not fit, dQ has a
kernel of its own, ``flash_bwd_dq``, which recomputes scores, mask, ``exp``
and ``dP`` (seven products; ``flash_bwd_dkv`` then returns dK and dV alone),
and the traced backward is counted in ``flash_backward_two_call_total``. dQ
is the same sums in the same order in all three forms, dK and dV up to the
order of a float32 sum. The choice is made from the shapes at trace time.

Queries arrive pre-scaled and pre-rotated (see
:func:`perceiver_io_tpu.ops.attention.dot_product_attention`): the attention
module finishes q and k on the projections' flat output, under the ``rotary``
scope, and the head split is the last thing before this kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_BLOCK_CANDIDATES = (512, 256, 128)
# Large-but-finite mask value (f32 min would overflow when subtracted).
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
# The float32 dQ that the one-kernel backward may keep in VMEM across a grid
# slice's kv blocks (``_resident_heads``): A = heads * rows * max(d, 128) * 4
# bytes, lanes padded to 128 as Mosaic lays it out. The v5e has 128 MiB of VMEM
# and a kernel gets 16 MiB of it unless it asks (``vmem_limit_bytes``); Mosaic
# allocates what the kernel needs, not the limit. What it needs, by compiling
# for a v5e without the chip and halving the limit until Mosaic refused
# (512 x 512 blocks): the dK/dV blocks and temporaries B (3.7 MiB in bfloat16
# at 64-wide heads, 5 at 128-wide with float32 dK/dV slices, 7.5 at 256-wide;
# in float32 6.4 at 128-wide, 9.7 at 256, 13.7 at 384, 17.2 at 512), the
# accumulator A, and the dQ output block twice over (A / 2 each in bfloat16,
# A in float32). So B + 2 A in bfloat16 and B + 3 A in float32:
#   four 64-wide query heads on 8192 rows (lfm2moe-train-8k)  A 16 MiB  35.8 MiB
#   one 256-wide head on 8192 rows (glm47flash-train-8k)      A  8 MiB  23.5 MiB
#   one 128-wide head of 7 on 16,384 rows (smallthinker-...)   A  8 MiB  21.0 MiB
#   float32, one 256-wide head on 16,384 rows                  A 16 MiB  57.7 MiB
#   float32, one 512-wide head on 8192 rows                    A 16 MiB  65.2 MiB
# 16 MiB of A keeps the worst of these at half the v5e's VMEM (limits of 96 to
# 128 MiB compiled too, but nothing was run there), and holds the three ``lm``
# cells: 32,768 rows of one head of up to 128 channels, 16,384 of 256. On the
# chip the one kernel ran 0.68 to 0.76 of the two kernels' time at those three
# shapes, and a group kept whole beat the same group in 2 or 4 slices by 4 and
# 5 % (PERF.md, PR 36), hence the largest divisor. ``_fused_vmem_limit`` asks
# for an upper estimate of B from the blocks' shapes (3 to 5 MiB over each
# figure above) plus the 2 or 3 A, and never for less than the 32 MiB every
# such kernel asked for while A was held to 2 MiB, so the Perceiver cells'
# kernels (A 0.5 and 1 MiB) are the programs they were. A chip with less VMEM
# than 128 MiB (64 MiB a core on a v7x) wants this constant derived again by
# the same rule, B + 3 A within half of it: 8 MiB there; a shape past the
# constant then runs as the two kernels, as one past 16 MiB does here. The
# choice reads the shapes alone: the lowering platform is not known at trace
# time, and the v5e is what every cell runs on.
_DQ_VMEM_BUDGET_BYTES = 16 * 1024 * 1024
# the one-kernel backward's ``vmem_limit_bytes`` up to 2 MiB of resident dQ, and the least above
_FUSED_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# backwards traced as two kernels because one head's dQ is over the budget (docs/observability.md)
_TWO_CALL_COUNTER = "flash_backward_two_call_total"
# backwards traced as one kernel over slices of a group whose dQ is over it whole
_SLICED_COUNTER = "flash_backward_sliced_total"
# traced forward calls that carried a window (docs/observability.md)
_WINDOW_COUNTER = "flash_window_call_total"
# ``checkpoint_name`` of the forward's output and log-sum-exp under a gradient
SAVED_NAMES = ("flash_out", "flash_lse")


def _pick_block(n: int) -> Optional[int]:
    for b in _BLOCK_CANDIDATES:
        if n % b == 0:
            return b
    return None


def pallas_call_on_lowering_platform(kernel, *args, name: str, **spec):
    """``pl.pallas_call(kernel, name=name, **spec)(*args)``, compiled by Mosaic
    when the program is lowered for a TPU and run by the Pallas interpreter on
    any other platform. ``lax.platform_dependent`` makes the choice when the
    lowering platform is known, so lowering for ``tpu`` on a CPU host goes
    through Mosaic and a CPU run never depends on what
    ``jax.default_backend()`` said at trace time. Only the chosen branch is
    lowered.

    ``name`` becomes the Mosaic kernel's ``kernel_name`` and the last scope of
    its location, which is what the compiled program's instruction, and with
    it the profiler's event, is called (``flash_fwd.3``, not
    ``branch_0_fun.3``)."""

    def call(*args, interpret):
        return pl.pallas_call(kernel, interpret=interpret, name=name, **spec)(*args)

    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True),
    )


def supported(q, k, v, *, causal: bool, window: Optional[int] = None) -> bool:
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return False
    if window is not None and (not causal or window < 1):
        return False  # the window is a bound on the causal mask
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if q.dtype != k.dtype or q.dtype != v.dtype:
        return False
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        return False  # grouped heads: each key-value head serves h / hk query heads
    i, j = q.shape[2], k.shape[2]
    if causal and j < i:
        return False
    if _pick_block(i) is None or _pick_block(j) is None:
        return False
    # Head dims must be lane-tileable; Mosaic pads, but tiny dims would waste
    # most of the MXU — leave those to the XLA path.
    if q.shape[3] < 32 or v.shape[3] < 32:
        return False
    return True


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    pad_mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Flash attention with Perceiver masking semantics.

    :param q: ``(b, h, i, d)`` pre-scaled queries.
    :param k: ``(b, hk, j, d)`` keys; ``hk`` divides ``h`` and query head
        ``n`` reads key-value head ``n // (h // hk)`` (grouped-query
        attention). The kernels index the shared head from their grid: no
        key or value array is ever repeated to ``h`` heads.
    :param v: ``(b, hk, j, dv)`` values.
    :param pad_mask: optional boolean ``(b, j)``, True marks padding.
    :param causal: right-aligned causal masking (offset ``j - i``).
    :param window: with ``causal``, the keys a query sees: query ``t`` sees
        key ``s`` iff ``0 <= t + (j - i) - s < window`` (its own position
        counts). Block pairs outside that band are not computed and not
        fetched, in the forward and in both backward kernels.

    Under a mesh with more than one device the caller wraps this in
    ``jax.shard_map`` (:func:`perceiver_io_tpu.ops.attention.dot_product_attention`
    does): Mosaic kernels are not partitioned automatically.

    Dead-row semantics: a query row whose entire visible window is padded
    gets **zero output and zero gradients** here. The einsum path (like the
    torch reference) instead softmaxes a uniform distribution over the masked
    keys, leaking activations/gradients into padding. Such rows are
    themselves padding in every Perceiver model (their loss contribution is
    masked), so the results never differ for real positions — the flash
    behavior is the deliberate one.
    """
    # (b, 1, j): the kernels block it as (1, 1, bj), whose second-to-last dim
    # equals the array's, so the TPU (8, 128) tiling rule holds at any batch.
    from perceiver_io_tpu.observability import default_registry

    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window} needs causal=True and at least one key")
    # trace time, so once per traced call; declared here so that a program
    # that calls the kernels without a window exports 0
    default_registry().declare_counters(_WINDOW_COUNTER)
    if window is not None:
        default_registry().inc(_WINDOW_COUNTER)
    pad = None if pad_mask is None else pad_mask.astype(jnp.float32)[:, None, :]
    return _flash(q, k, v, pad, causal, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash(q, k, v, pad, causal, window):
    o, _ = _forward(q, k, v, pad, causal, window)
    return o


def _flash_fwd(q, k, v, pad, causal, window):
    o, lse = _forward(q, k, v, pad, causal, window)
    # named so that a layer's recomputation can keep them (``SAVED_NAMES``):
    # its backward then hands these to ``_flash_bwd`` without a second forward
    o = checkpoint_name(o, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return o, (q, k, v, pad, o, lse)


def _flash_bwd(causal, window, res, do):
    q, k, v, pad, o, lse = res
    return _backward(q, k, v, pad, o, lse, do, causal, window)


def _backward(q, k, v, pad, o, lse, do, causal, window, sel=None):
    """``(dq, dk, dv, dpad)``: one kernel or, past the budget, two."""
    from perceiver_io_tpu.observability import default_registry

    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))
    default_registry().declare_counters(_TWO_CALL_COUNTER, _SLICED_COUNTER)
    heads = _resident_heads(q, k)
    if heads:
        if heads < q.shape[1] // k.shape[1]:
            default_registry().inc(_SLICED_COUNTER)  # trace time, as below
        dk, dv, dq = _backward_dkv(q, k, v, pad, lse, delta, do, causal, window, dq_heads=heads, sel=sel)
    else:
        # trace time, so once per traced backward; correct, only slower: no warning
        default_registry().inc(_TWO_CALL_COUNTER)
        dq = _backward_dq(q, k, v, pad, lse, delta, do, causal, window, sel=sel)
        dk, dv = _backward_dkv(q, k, v, pad, lse, delta, do, causal, window, sel=sel)
    dpad = None if pad is None else jnp.zeros_like(pad)
    return dq, dk, dv, dpad


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_selected(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bits: jnp.ndarray,
    *,
    pad_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal attention of each query over the keys its row of ``bits``
    selects, and the rows' log-sum-exp.

    :param bits: ``(b, i / 32, j)`` int32, the selection packed for the
        kernels' row blocks (``sparse_attention.pack``); a subset of the
        causal mask with at least one key a row (``i == j``).
    :return: ``o`` ``(b, h, i, dv)`` and ``lse`` ``(b, h, i)`` float32 over
        the selected keys. ``lse`` carries no gradient: it is for a reader
        under ``stop_gradient`` (the indexer's loss).

    Block pairs whose flag (:func:`block_flags`) is 0 cost nothing, forward
    and backward. Under a mesh the caller wraps this in ``shard_map``, as
    for :func:`flash_attention`."""
    pad = None if pad_mask is None else pad_mask.astype(jnp.float32)[:, None, :]
    o, lse = _flash_selected(q, k, v, pad, bits, block_flags(bits, k.shape[2]))
    return o, lse[..., 0]


@jax.custom_vjp
def _flash_selected(q, k, v, pad, bits, flags):
    return _forward(q, k, v, pad, True, sel=(bits, flags))


def _flash_selected_fwd(q, k, v, pad, bits, flags):
    o, lse = _forward(q, k, v, pad, True, sel=(bits, flags))
    o = checkpoint_name(o, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return (o, lse), (q, k, v, pad, bits, flags, o, lse)


def _flash_selected_bwd(res, cts):
    q, k, v, pad, bits, flags, o, lse = res
    # the log-sum-exp's cotangent is dropped: it carries no gradient
    return (*_backward(q, k, v, pad, o, lse, cts[0], True, None, sel=(bits, flags)), None, None)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def _resident_heads(q, k) -> int:
    """How many of a key-value head's query heads the one-kernel backward
    keeps dQ resident for at a time: the largest divisor of the group whose
    float32 dQ, as Mosaic lays it out, is within the budget. The group itself:
    one pass a key-value head; a smaller divisor: the group in slices; 0: one
    head alone is over it, and the backward is two kernels."""
    group, i, d = q.shape[1] // k.shape[1], q.shape[2], q.shape[3]
    head = i * max(d, LANES) * 4
    return max((g for g in range(1, group + 1)
                if group % g == 0 and g * head <= _DQ_VMEM_BUDGET_BYTES), default=0)


def _fused_vmem_limit(bi: int, bj: int, d: int, dv: int, rows: int, itemsize: int, kv_out_itemsize: int) -> int:
    """``vmem_limit_bytes`` of the one-kernel backward with ``rows`` rows of
    dQ resident: an upper estimate of what Mosaic allocates, from the shapes
    (the figures behind it are at ``_DQ_VMEM_BUDGET_BYTES``), and never under
    ``_FUSED_VMEM_LIMIT_BYTES``, what every such kernel asked for while the
    resident dQ was at most 2 MiB."""
    lanes = lambda n: -(-n // LANES) * LANES
    d, dv = lanes(d), lanes(dv)
    blocks = (
        2 * (bi + bj) * (d + dv) * itemsize    # q, dO, k, v: each block in two buffers
        + 2 * 2 * bi * LANES * 4               # lse, delta
        + 2 * bj * (d + dv) * kv_out_itemsize  # the dK, dV output blocks
        + bj * (d + dv) * 4                    # their float32 accumulators
        + 4 * bi * bj * 4                      # scores, probabilities, dP, dS
        + (bj * (d + dv) + bi * d) * 4         # the three products before they are added
    )
    resident = rows * d * (4 + 2 * itemsize)   # the accumulator, the dQ output block twice
    return max(_FUSED_VMEM_LIMIT_BYTES, blocks + resident)


def _block_mask(i_idx, j_idx, bi: int, bj: int, offset: int, causal: bool, pad_blk,
                window: Optional[int] = None):
    """Boolean (bi, bj) "allowed" mask for the current block pair, or None
    when the block is unconstrained."""
    allowed = None
    if pad_blk is not None:
        allowed = jnp.broadcast_to(pad_blk < 0.5, (bi, bj))  # (1, bj) over rows
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0) + i_idx * bi
        cols = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1) + j_idx * bj
        cm = cols <= rows + offset
        if window is not None:
            cm = jnp.logical_and(cm, cols > rows + (offset - window))
        allowed = cm if allowed is None else jnp.logical_and(allowed, cm)
    return allowed


def _run_block(i_idx, j_idx, bi: int, bj: int, offset: int, causal: bool,
               window: Optional[int] = None):
    """Whether this (i, j) block intersects the allowed region."""
    if not causal:
        return None  # statically always
    run = j_idx * bj <= i_idx * bi + (bi - 1) + offset
    if window is not None:  # its last key is inside the first row's window
        run = jnp.logical_and(run, j_idx * bj + (bj - 1) > i_idx * bi + (offset - window))
    return run


def _floor0(x):
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


@dataclasses.dataclass(frozen=True)
class _Band:
    """The block pairs a windowed causal call computes: q block ``i`` (rows
    ``i * bi`` on) meets kv blocks ``first_j(i) .. last_j(i)``, kv block ``j``
    q blocks ``first_i(j) .. last_i(j)`` (which may lie past the last q block,
    or before the first where no query sees a key of the block). The kernels'
    innermost grid dimension counts ``kv_blocks`` (``q_blocks``), the most a
    block of the other side meets; block indices take plain ints (the counts)
    and the kernels' scalars alike."""

    bi: int
    bj: int
    ni: int
    nj: int
    offset: int
    window: int

    def first_j(self, i_idx):
        return _floor0(i_idx * self.bi + (self.offset - self.window + 1)) // self.bj

    def last_j(self, i_idx):
        return (i_idx * self.bi + (self.bi - 1 + self.offset)) // self.bj

    def first_i(self, j_idx):
        return _floor0(j_idx * self.bj - self.offset) // self.bi

    def last_i(self, j_idx):
        return (j_idx * self.bj + (self.bj - 1 - self.offset + self.window - 1)) // self.bi

    @property
    def kv_blocks(self) -> int:
        return max(self.last_j(i) - self.first_j(i) + 1 for i in range(self.ni))

    @property
    def q_blocks(self) -> int:
        return max(1, max(min(self.last_i(j), self.ni - 1) - self.first_i(j) + 1
                          for j in range(self.nj)))

    def kv_block(self, i_idx, step):
        """The kv block of grid step ``step`` of q block ``i_idx``, held at the
        band's last one past it (a block index that does not move fetches
        nothing)."""
        return jnp.minimum(self.first_j(i_idx) + step, self.last_j(i_idx))

    def q_block(self, j_idx, step):
        return jnp.minimum(self.first_i(j_idx) + step, self.ni - 1)


def _selected_block(words, bi: int):
    """Boolean ``(bi, bj)``: the selection of one block pair, from its
    ``(bi / 32, bj)`` words. Row ``r`` of the block is bit ``r // (bi / 32)``
    of word ``r % (bi / 32)`` (``sparse_attention.pack``): the words stacked
    32 times over give every row its word, and one shift a row its bit."""
    per = bi // 32
    rows = jnp.concatenate([words] * 32, axis=0)
    shift = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) >> (per.bit_length() - 1)
    return (jnp.right_shift(rows, shift) & 1) != 0


def selection_blocks_fit(i: int) -> bool:
    """Whether the kernels can take a selection at ``i`` query rows: a block's
    words, ``(bi / 32, bj)``, are whole sublane tiles or the whole array."""
    bi = _pick_block(i)
    return bi is not None and (bi // 32 % 8 == 0 or bi == i)


def block_flags(bits: jnp.ndarray, j: int) -> jnp.ndarray:
    """``(b * ni * nj,)`` int32: whether block pair ``(i, j)`` of row ``b``
    holds a selected key, for the kernels' scalar prefetch."""
    b, words, _ = bits.shape
    bi, bj = _pick_block(words * 32), _pick_block(j)
    blocks = bits.reshape(b, words * 32 // bi, bi // 32, j // bj, bj)
    return jnp.any(blocks != 0, axis=(2, 4)).astype(jnp.int32).reshape(-1)


def _flagged(flags_ref, i_idx, j_idx, ni: int, nj: int):
    """The flag of block pair ``(i_idx, j_idx)`` of the grid's batch row."""
    return flags_ref[(pl.program_id(0) * ni + i_idx) * nj + j_idx] != 0


def _pallas(kernel, args, flags, *, name: str, grid, in_specs, out_specs, scratch_shapes, **spec):
    """``pallas_call_on_lowering_platform`` as every kernel here makes it, or,
    with ``flags``, the same kernel under a grid whose index maps and body
    also receive the flags by scalar prefetch (the body's first ref)."""
    if flags is None:
        return pallas_call_on_lowering_platform(
            kernel, *args, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes, **spec)

    def prefetched(block):
        return pl.BlockSpec(block.block_shape, lambda *idx: block.index_map(*idx[:-1]))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=[prefetched(b) for b in in_specs],
        out_specs=(prefetched(out_specs) if isinstance(out_specs, pl.BlockSpec)
                   else [prefetched(b) for b in out_specs]),
        scratch_shapes=scratch_shapes,
    )
    return pallas_call_on_lowering_platform(kernel, flags, *args, name=name, grid_spec=grid_spec, **spec)


def _maybe_when(run, body):
    if run is None:
        body()
    else:
        pl.when(run)(body)


def _qk_spec(bi, d, by_dim2=True, group: int = 1):
    """Blocks of ``bi`` rows walking grid dim 2 (or 3). ``group`` > 1 is a
    key or value array of grouped heads under a grid over the query heads:
    grid head ``h_`` reads head ``h_ // group``. Ungrouped calls keep the
    index maps they had (no ``// 1``), so their kernels compile as before."""
    if group > 1:
        if by_dim2:
            return pl.BlockSpec((1, 1, bi, d), lambda b_, h_, x_, y_: (b_, h_ // group, x_, 0))
        return pl.BlockSpec((1, 1, bi, d), lambda b_, h_, x_, y_: (b_, h_ // group, y_, 0))
    if by_dim2:
        return pl.BlockSpec((1, 1, bi, d), lambda b_, h_, x_, y_: (b_, h_, x_, 0))
    return pl.BlockSpec((1, 1, bi, d), lambda b_, h_, x_, y_: (b_, h_, y_, 0))


def _group_q_spec(bi, d, group: int, ni: int):
    """A query-side array under the dK/dV kernel's grid over the key-value
    heads: grid dim 3 walks the ``group`` query heads of a key-value head,
    ``ni`` row blocks each."""
    return pl.BlockSpec(
        (1, 1, bi, d), lambda b_, h_, x_, y_: (b_, h_ * group + y_ // ni, y_ % ni, 0))


def _pad_spec(bj, by_dim2=False):
    if by_dim2:
        return pl.BlockSpec((1, 1, bj), lambda b_, h_, x_, y_: (b_, 0, x_))
    return pl.BlockSpec((1, 1, bj), lambda b_, h_, x_, y_: (b_, 0, y_))


def _q_grid_specs(bi, bj, d, dv, group: int, has_pad: bool, band: Optional[_Band]) -> list:
    """Blocks of q, k, v (and the pad mask) under a grid whose dim 2 walks the
    q blocks: dim 3 walks every kv block or, under a window, counts the kv
    blocks of each q block's band."""
    if band is None:
        specs = [
            _qk_spec(bi, d, by_dim2=True),
            _qk_spec(bj, d, by_dim2=False, group=group),
            _qk_spec(bj, dv, by_dim2=False, group=group),
        ]
        return specs + [_pad_spec(bj)] if has_pad else specs
    specs = [_qk_spec(bi, d, by_dim2=True)] + [
        pl.BlockSpec((1, 1, bj, w),
                     lambda b_, h_, x_, y_: (b_, h_ // group, band.kv_block(x_, y_), 0))
        for w in (d, dv)
    ]
    if has_pad:
        specs.append(pl.BlockSpec(
            (1, 1, bj), lambda b_, h_, x_, y_: (b_, 0, band.kv_block(x_, y_))))
    return specs


_DIM_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
)

# The forward alone on a v5e at every shape the benchmark's cells run (bfloat16,
# ms a call, host clock over back-to-back calls, median of 7 interleaved
# samples, each within 0.4 % but the 256 x 256 shape's, 4 %; PERF.md), with the
# running max and sum kept as a (rows, 1) column and broadcast back, as before,
# or lane-dense, (rows, 128); and two other bodies, lane-dense too: the q block
# as 2 or 4 independent row slices unrolled in one grid step, and kv sub-blocks
# of 256 keys each with its own online update:
#   q x kv, heads x width, batch     column  lane-dense  2 / 4 row slices  kv 256
#   1024 x 4608 causal, 8 x 64, 32    11.33     8.45       8.70 /  8.94       -
#   1024 x 1024 causal, 8 x 64, 32     2.62     2.09       2.12 /  2.24       -
#   256 x 256, 8 x 32 / 160, 32        0.43     0.39       0.38 /    -        -
#   256 x 2048, 8 x 32 / 160, 32       2.29     2.02       2.12 /    -        -
#   2048 x 256, 8 x 32 / 96, 32        3.06     2.34       2.41 /  2.54       -
#   8192 causal, 32 on 8 x 64, 2      22.30    16.56      16.80 / 17.96   25.80
#   8192 causal, 20 x 256, 1           8.25     7.00       7.08 /  7.66    6.56
#   16,384 causal, 28 on 4 x 128      37.97    22.36      21.96 / 26.02   25.53
#   16,384, 4096 window, same         16.13     9.21       9.06 / 10.37   10.58
# The column was the cost: lane-dense m and l take 9 to 43 % off at every shape
# and give the same bits. Row slices gain 1.6 to 1.8 % at 128-wide heads alone,
# about 0.2 % of that cell's step, and lose elsewhere; sub-blocks
# of 256 keys gain 6 % at 256-wide heads alone but round a row's sums in
# another order. Neither is taken.
def _forward(q, k, v, pad, causal, window=None, sel=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, h, i, d = q.shape
    j, dv = k.shape[2], v.shape[3]
    group = h // k.shape[1]
    bi, bj = _pick_block(i), _pick_block(j)
    offset = j - i
    nj = j // bj
    has_pad = pad is not None
    band = None if window is None else _Band(bi, bj, i // bi, nj, offset, window)
    steps = nj if band is None else band.kv_blocks  # grid dim 3
    has_sel = sel is not None  # (bits, flags): the selection is a subset of the causal mask

    def kernel(*refs):
        flags_ref = pad_ref = bits_ref = None
        if has_sel:
            flags_ref, *refs = refs
        q_ref, k_ref, v_ref, *rest = refs
        if has_pad:
            pad_ref, *rest = rest
        if has_sel:
            bits_ref, *rest = rest
        o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
        i_idx, step = pl.program_id(2), pl.program_id(3)
        j_idx = step if band is None else band.first_j(i_idx) + step

        @pl.when(step == 0)
        def _():
            m_sc[:] = jnp.full_like(m_sc, -jnp.inf)
            l_sc[:] = jnp.zeros_like(l_sc)
            acc_sc[:] = jnp.zeros_like(acc_sc)

        def body():
            s = jax.lax.dot_general(
                q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            allowed = _block_mask(
                i_idx, j_idx, bi, bj, offset, causal and not has_sel,
                pad_ref[0] if has_pad else None, window,
            )
            if has_sel:
                chosen = _selected_block(bits_ref[0], bi)
                allowed = chosen if allowed is None else jnp.logical_and(allowed, chosen)
            if allowed is not None:
                s = jnp.where(allowed, s, _MASK)

            # m and l stay lane-dense, (bi, 128) with every lane equal: as a
            # (bi, 1) column broadcast back they cost 9 to 43 % of the kernel
            m_prev = m_sc[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - pltpu.repeat(m_new, bj // LANES, 1))
            if allowed is not None:
                p = jnp.where(allowed, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_sc[:] + jnp.sum(p, axis=1, keepdims=True)
            alpha_o = pltpu.repeat(alpha, dv // LANES, 1) if dv % LANES == 0 else alpha[:, :1]
            acc_sc[:] = acc_sc[:] * alpha_o + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_sc[:] = m_new
            l_sc[:] = l_new

        run = (_flagged(flags_ref, i_idx, j_idx, i // bi, nj) if has_sel
               else _run_block(i_idx, j_idx, bi, bj, offset, causal, window))
        _maybe_when(run, body)

        @pl.when(step == steps - 1)
        def _():
            l = l_sc[:, :1]
            safe_l = jnp.where(l > 0.0, l, 1.0)
            o_ref[0, 0] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
            lse_ref[0, 0] = jnp.broadcast_to(
                m_sc[:, :1] + jnp.log(safe_l), lse_ref.shape[2:]
            )

    args = [q, k, v] + ([pad] if has_pad else [])
    in_specs = _q_grid_specs(bi, bj, d, dv, group, has_pad, band)
    if has_sel:
        args.append(sel[0])
        in_specs.append(pl.BlockSpec((1, bi // 32, bj), lambda b_, h_, x_, y_: (b_, x_, y_)))

    out = _pallas(
        kernel,
        args,
        sel[1] if has_sel else None,
        name="flash_fwd",
        grid=(b, h, i // bi, steps),
        in_specs=in_specs,
        out_specs=[
            _qk_spec(bi, dv, by_dim2=True),
            _qk_spec(bi, LANES, by_dim2=True),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, i, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, i, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bi, LANES), jnp.float32),
            pltpu.VMEM((bi, LANES), jnp.float32),
            pltpu.VMEM((bi, dv), jnp.float32),
        ],
        compiler_params=_DIM_SEMANTICS,
    )
    return out[0], out[1]


def _backward_dq(q, k, v, pad, lse, delta, do, causal, window=None, sel=None):
    b, h, i, d = q.shape
    j, dv = k.shape[2], v.shape[3]
    group = h // k.shape[1]
    bi, bj = _pick_block(i), _pick_block(j)
    offset = j - i
    nj = j // bj
    has_pad = pad is not None
    band = None if window is None else _Band(bi, bj, i // bi, nj, offset, window)
    steps = nj if band is None else band.kv_blocks  # grid dim 3
    has_sel = sel is not None

    def kernel(*refs):
        flags_ref = pad_ref = bits_ref = None
        if has_sel:
            flags_ref, *refs = refs
        q_ref, k_ref, v_ref, *rest = refs
        if has_pad:
            pad_ref, *rest = rest
        if has_sel:
            bits_ref, *rest = rest
        lse_ref, delta_ref, do_ref, dq_ref, dq_sc = rest
        i_idx, step = pl.program_id(2), pl.program_id(3)
        j_idx = step if band is None else band.first_j(i_idx) + step

        @pl.when(step == 0)
        def _():
            dq_sc[:] = jnp.zeros_like(dq_sc)

        def body():
            kb = k_ref[0, 0]
            s = jax.lax.dot_general(
                q_ref[0, 0], kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            allowed = _block_mask(
                i_idx, j_idx, bi, bj, offset, causal and not has_sel,
                pad_ref[0] if has_pad else None, window,
            )
            if has_sel:
                chosen = _selected_block(bits_ref[0], bi)
                allowed = chosen if allowed is None else jnp.logical_and(allowed, chosen)
            p = jnp.exp(s - lse_ref[0, 0][:, :1])
            if allowed is not None:
                p = jnp.where(allowed, p, 0.0)
            dp = jax.lax.dot_general(
                do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, 0][:, :1])
            dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
                ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        run = (_flagged(flags_ref, i_idx, j_idx, i // bi, nj) if has_sel
               else _run_block(i_idx, j_idx, bi, bj, offset, causal, window))
        _maybe_when(run, body)

        @pl.when(step == steps - 1)
        def _():
            dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)

    args = [q, k, v] + ([pad] if has_pad else [])
    in_specs = _q_grid_specs(bi, bj, d, dv, group, has_pad, band)
    if has_sel:
        args.append(sel[0])
        in_specs.append(pl.BlockSpec((1, bi // 32, bj), lambda b_, h_, x_, y_: (b_, x_, y_)))
    in_specs += [
        _qk_spec(bi, LANES, by_dim2=True),
        _qk_spec(bi, LANES, by_dim2=True),
        _qk_spec(bi, dv, by_dim2=True),
    ]
    args += [lse, delta, do]

    return _pallas(
        kernel,
        args,
        sel[1] if has_sel else None,
        name="flash_bwd_dq",
        grid=(b, h, i // bi, steps),
        in_specs=in_specs,
        out_specs=_qk_spec(bi, d, by_dim2=True),
        out_shape=jax.ShapeDtypeStruct((b, h, i, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bi, d), jnp.float32)],
        compiler_params=_DIM_SEMANTICS,
    )


def _backward_dkv(q, k, v, pad, lse, delta, do, causal, window=None, dq_heads: int = 0, sel=None):
    """dK and dV, and with ``dq_heads`` dQ as a third output of the same
    kernel: ``dq_heads`` is how many of a key-value head's query heads keep
    their float32 dQ in VMEM at a time (:func:`_resident_heads`). The whole
    group: one grid slice a key-value head. A smaller divisor of it: the
    group's heads in ``slices`` grid slices, each writing its own float32 dK
    and dV, which are summed here in float32 and rounded once."""
    b, h, i, d = q.shape
    hk, j, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // hk
    with_dq = dq_heads > 0
    walk = dq_heads or group  # the query heads a grid slice walks (grid dim 3)
    slices = group // walk    # the grid slices of a key-value head (grid dim 1)
    assert walk * slices == group, (group, dq_heads)
    bi, bj = _pick_block(i), _pick_block(j)
    offset = j - i
    ni, nj = i // bi, j // bj
    has_pad = pad is not None
    band = None if window is None else _Band(bi, bj, ni, nj, offset, window)
    # q blocks a query head walks in grid dim 3: all, or those of a kv block's band
    nq = ni if band is None else band.q_blocks

    # Grid dim 1 walks the key-value heads (each ``slices`` times over, slice
    # ``h_`` reading key-value head ``h_ // slices``), dim 2 kv blocks, dim 3
    # the q blocks of every query head of the slice (innermost, so the dk/dv
    # accumulators carry across q blocks and across the slice's heads).
    # With dQ, the float32 dQ of the slice's heads (``walk * i`` rows,
    # grid step ``t_idx`` owning rows ``t_idx * bi`` on) stays in VMEM across
    # dims 2 and 3: each kv block adds its part in ascending order, as
    # ``_backward_dq`` sums them, and the last rounds the rows once into the
    # output block, which is resident as long and written back when
    # dim 0 or 1 moves on. Under a window grid dim 3 walks, for each query
    # head, the ``nq`` q blocks from the kv block's first (``inside`` says the
    # step's q block exists; past the last one it is held there and skipped),
    # and a q block's rows of dQ are zeroed by the first kv block of its band
    # and rounded by the last.
    has_sel = sel is not None

    def kernel(*refs):
        flags_ref = pad_ref = bits_ref = None
        if has_sel:
            flags_ref, *refs = refs
        q_ref, k_ref, v_ref, *rest = refs
        if has_pad:
            pad_ref, *rest = rest
        if has_sel:
            bits_ref, *rest = rest
        if with_dq:
            lse_ref, delta_ref, do_ref, dk_ref, dv_ref, dq_ref, dk_sc, dv_sc, dq_sc = rest
        else:
            lse_ref, delta_ref, do_ref, dk_ref, dv_ref, dk_sc, dv_sc = rest
        j_idx, t_idx = pl.program_id(2), pl.program_id(3)
        if band is None:
            i_idx = t_idx if walk == 1 else t_idx % ni
            row_block, inside = t_idx, None
        else:
            i_idx = band.first_i(j_idx) + t_idx % nq
            inside = i_idx < ni
            row_block = t_idx // nq * ni + jnp.minimum(i_idx, ni - 1)
        if with_dq:
            dq_rows = pl.ds(pl.multiple_of(row_block * bi, bi), bi)

        @pl.when(t_idx == 0)
        def _():
            dk_sc[:] = jnp.zeros_like(dk_sc)
            dv_sc[:] = jnp.zeros_like(dv_sc)

        if with_dq:
            dq_first = j_idx == 0 if band is None else inside & (j_idx == band.first_j(i_idx))

            @pl.when(dq_first)
            def _():
                dq_sc[dq_rows, :] = jnp.zeros((bi, d), jnp.float32)

        def body():
            qb, kb, dob = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            allowed = _block_mask(
                i_idx, j_idx, bi, bj, offset, causal and not has_sel,
                pad_ref[0] if has_pad else None, window,
            )
            if has_sel:
                chosen = _selected_block(bits_ref[0], bi)
                allowed = chosen if allowed is None else jnp.logical_and(allowed, chosen)
            p = jnp.exp(s - lse_ref[0, 0][:, :1])
            if allowed is not None:
                p = jnp.where(allowed, p, 0.0)
            dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
                p.astype(qb.dtype), dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                dob, v_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta_ref[0, 0][:, :1])).astype(qb.dtype)
            dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if with_dq:
                dq_sc[dq_rows, :] = dq_sc[dq_rows, :] + jax.lax.dot_general(
                    ds, kb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

        if has_sel:
            run = _flagged(flags_ref, i_idx, j_idx, ni, nj)
        else:
            run = _run_block(i_idx, j_idx, bi, bj, offset, causal, window)
        _maybe_when(run if band is None else inside & run, body)

        @pl.when(t_idx == walk * nq - 1)
        def _():
            dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)

        if with_dq:
            dq_last = j_idx == nj - 1 if band is None else inside & (j_idx == band.last_j(i_idx))

            @pl.when(dq_last)
            def _():
                dq_ref[0, 0, dq_rows, :] = dq_sc[dq_rows, :].astype(dq_ref.dtype)

    # a slice's query heads are adjacent: grid head ``h_`` walks heads ``h_ * walk`` on
    if band is not None:
        q_side = lambda width: pl.BlockSpec(
            (1, 1, bi, width),
            lambda b_, h_, x_, y_: (b_, h_ * walk + y_ // nq, band.q_block(x_, y_ % nq), 0))
    elif walk == 1:
        q_side = lambda width: _qk_spec(bi, width, by_dim2=False)  # q blocks walk grid dim 3
    else:
        q_side = lambda width: _group_q_spec(bi, width, walk, ni)
    in_specs = [
        q_side(d),
        _qk_spec(bj, d, by_dim2=True, group=slices),    # k blocks walk grid dim 2
        _qk_spec(bj, dv, by_dim2=True, group=slices),
    ]
    args = [q, k, v]
    if has_pad:
        in_specs.append(_pad_spec(bj, by_dim2=True))
        args.append(pad)
    if has_sel:  # the bits of grid step y_'s q block against kv block x_
        in_specs.append(pl.BlockSpec((1, bi // 32, bj), lambda b_, h_, x_, y_: (b_, y_ % ni, x_)))
        args.append(sel[0])
    in_specs += [q_side(LANES), q_side(LANES), q_side(dv)]
    args += [lse, delta, do]

    out_specs = [
        _qk_spec(bj, d, by_dim2=True),
        _qk_spec(bj, dv, by_dim2=True),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, hk * slices, j, d), k.dtype if slices == 1 else jnp.float32),
        jax.ShapeDtypeStruct((b, hk * slices, j, dv), v.dtype if slices == 1 else jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((bj, d), jnp.float32),
        pltpu.VMEM((bj, dv), jnp.float32),
    ]
    compiler_params = _DIM_SEMANTICS
    if with_dq:
        # the query heads are adjacent, so (b, hk * slices, walk * i, d) is
        # (b, h, i, d) seen by slice: the reshape below moves nothing
        out_specs.append(pl.BlockSpec((1, 1, walk * i, d), lambda b_, h_, x_, y_: (b_, h_, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, hk * slices, walk * i, d), q.dtype))
        scratch_shapes.append(pltpu.VMEM((walk * i, d), jnp.float32))
        # the resident dQ carries across the kv blocks (grid dim 2) too
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_fused_vmem_limit(
                bi, bj, d, dv, walk * i, q.dtype.itemsize, out_shape[0].dtype.itemsize),
        )

    out = _pallas(
        kernel,
        args,
        sel[1] if has_sel else None,
        name="flash_bwd_dkv",
        grid=(b, hk * slices, nj, walk * nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=compiler_params,
    )
    dk_dv = out[:2]
    if slices > 1:  # one float32 sum over a key-value head's slices, one rounding
        dk_dv = [x.reshape(b, hk, slices, j, -1).sum(axis=2).astype(k.dtype) for x in dk_dv]
    return (*dk_dv, out[2].reshape(q.shape)) if with_dq else tuple(dk_dv)

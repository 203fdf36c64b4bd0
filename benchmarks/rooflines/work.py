"""Operations and bytes of matrix products and attention calls, and the
least time a chip could take for them.

A matmul is ``(m, k, n)``: ``2 m k n`` operations forward. An attention call
is a dict ``b, h, i, j, dk, dv, causal``: scores and weighted values,
``2 b h a (dk + dv)`` forward over the ``a`` query-key pairs the mask allows
(right-aligned causal: query ``q`` sees ``j - i + q + 1`` keys). Training
counts forward plus backward and never recomputation: three times a matmul's
forward; for attention the flash backward's five products beside the
forward's two (scores again, dV, dP, dQ, dK), which is its algorithm and not
a recomputation the program chose.
"""
from __future__ import annotations


def attention_pairs(a: dict, count_masked: bool = False) -> int:
    i, j = a["i"], a["j"]
    if a.get("causal") and not count_masked:
        return i * (j - i) + i * (i + 1) // 2
    return i * j


def attention_forward_flops(a: dict, count_masked: bool = False) -> int:
    return 2 * a["b"] * a["h"] * attention_pairs(a, count_masked) * (a["dk"] + a["dv"])


def attention_backward_flops(a: dict, count_masked: bool = False) -> int:
    return 2 * a["b"] * a["h"] * attention_pairs(a, count_masked) * (3 * a["dk"] + 2 * a["dv"])


def matmul_forward_flops(matmuls) -> int:
    return sum(2 * m * k * n for m, k, n in matmuls)


def forward_flops(work: dict, count_masked: bool = False) -> int:
    return matmul_forward_flops(work["matmuls"]) + sum(
        attention_forward_flops(a, count_masked) for a in work["attentions"]
    )


def train_step_flops(work: dict, count_masked: bool = False) -> int:
    """Forward and backward of one step."""
    return 3 * matmul_forward_flops(work["matmuls"]) + sum(
        attention_forward_flops(a, count_masked) + attention_backward_flops(a, count_masked)
        for a in work["attentions"]
    )


def attention_bytes(a: dict, itemsize: int, backward: bool) -> int:
    """Bytes a fused attention kernel has to move at the least: q, k, v in and
    o out once forward; q, k, v, o, dO in and dQ, dK, dV out once backward;
    the row statistics in float32 either way."""
    bh = a["b"] * a["h"]
    q, k = bh * a["i"] * a["dk"], bh * a["j"] * a["dk"]
    v, o = bh * a["j"] * a["dv"], bh * a["i"] * a["dv"]
    stats = 4 * bh * a["i"]
    if backward:
        return itemsize * (2 * q + 2 * k + 2 * v + 2 * o) + 2 * stats
    return itemsize * (q + k + v + o) + stats


def flash_least_time(attentions, peak: dict, itemsize: int = 2, training: bool = True) -> dict:
    """Least seconds for the flash kernels of a step: for each call the
    larger of operations over peak FLOP/s and bytes over peak bytes/s,
    forward and (training) backward. Says which bound holds most of it."""
    total, by_bound = 0.0, {"flops": 0.0, "bytes": 0.0}
    for a in attentions:
        passes = [(attention_forward_flops(a), attention_bytes(a, itemsize, False))]
        if training:
            passes.append((attention_backward_flops(a), attention_bytes(a, itemsize, True)))
        for flops, nbytes in passes:
            tf, tb = flops / peak["flops_per_s_bf16"], nbytes / peak["bytes_per_s"]
            total += max(tf, tb)
            by_bound["flops" if tf >= tb else "bytes"] += max(tf, tb)
    return {"seconds": total, "bound": max(by_bound, key=by_bound.get)}

"""Operations and bytes of grouped matrix products (rows sorted by group,
one weight matrix a group), and the least time a chip could take for them.

A product is ``(rows, k, n)`` over ``groups`` weight matrices: ``2 rows k n``
operations forward whatever the split of the rows over the groups; the bytes
are the rows in and out once and every group's matrix once. Training adds the
two products of the backward pass (the rows' gradient and the matrices'),
each the size of the forward's; recomputation is never counted.
"""
from __future__ import annotations


def expert_products(config: dict, rows: float) -> list:
    """The three grouped products of one expert layer (gate, up, down) for
    ``rows`` token-expert pairs."""
    c, f = config["hidden_size"], config["moe_intermediate_size"]
    return [(rows, c, f), (rows, c, f), (rows, f, c)]


def grouped_flops(products, training: bool = True) -> float:
    return (3 if training else 1) * sum(2 * m * k * n for m, k, n in products)


def grouped_least_time(products, groups: int, peak: dict, itemsize: int = 2,
                       training: bool = True) -> float:
    """Least seconds for ``products``: for each pass of each product the
    larger of operations over peak FLOP/s and bytes over peak bytes/s. The
    matrices' gradient is written in float32."""
    total = 0.0
    for m, k, n in products:
        flops = 2 * m * k * n
        rows_in, rows_out, weights = m * k * itemsize, m * n * itemsize, groups * k * n
        passes = [rows_in + weights * itemsize + rows_out]
        if training:
            passes += [rows_out + weights * itemsize + rows_in, rows_in + rows_out + weights * 4]
        total += sum(max(flops / peak["flops_per_s_bf16"], b / peak["bytes_per_s"]) for b in passes)
    return total

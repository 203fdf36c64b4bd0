"""Indexer losses that ran the KL kernels (``indexer_kl``,
``indexer_kl_grad``), counted while tracing (set-up and window): the sparse
layers of a step for every time the step is traced, twice in a ``--trace 1``
run (8 in ``keyevl2-train-16k``: four layers). 0 says every indexer loss ran
as XLA's blocked loop. Source: the program's ``indexer_kl_kernel_total``,
which every indexer loss declares; nothing from a program that has no such
counter."""

COUNTER = "indexer_kl_kernel_total"


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().counters().get(COUNTER)
    return None if value is None else float(value)

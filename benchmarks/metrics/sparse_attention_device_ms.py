"""Device milliseconds a traced training step spends in the flash kernels of
the sparse layers (the ``flash_*`` Mosaic kernels under the program's scope
``sparse_attention``, which take the indexer's selection), forward and
backward. Nothing from a program without the scope."""
from benchmarks import attention_kinds


def read(ctx):
    return attention_kinds.flash_ms(ctx, "sparse_attention")

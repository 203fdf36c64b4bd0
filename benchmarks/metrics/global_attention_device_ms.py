"""Device milliseconds a traced training step spends in the flash kernels of
the layers that see every earlier position (the ``flash_*`` Mosaic kernels
under the program's scope ``global_attention``), forward and backward.
Nothing from a program without the scope."""
from benchmarks import attention_kinds


def read(ctx):
    return attention_kinds.flash_ms(ctx, "global_attention")

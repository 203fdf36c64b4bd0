"""Device milliseconds a traced training step spends in the flash kernels of
the window layers (the ``flash_*`` Mosaic kernels under the program's scope
``window_attention``: the banded forward, dK/dV and dQ), forward and
backward. Nothing from a program without the scope."""
from benchmarks import attention_kinds


def read(ctx):
    return attention_kinds.flash_ms(ctx, "window_attention")

"""Device milliseconds of the flash forward kernel (``flash_fwd``) in a traced
training step: every Mosaic call of that name, cross- and self-attention."""
from benchmarks import scopes


def read(ctx):
    return scopes.class_ms(ctx, "flash_fwd")

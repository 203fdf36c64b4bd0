"""Device milliseconds of the flash backward kernel for dQ (``flash_bwd_dq``)
in a traced training step."""
from benchmarks import scopes


def read(ctx):
    return scopes.class_ms(ctx, "flash_bwd_dq")

"""Device milliseconds of the flash backward kernel for dK and dV
(``flash_bwd_dkv``) in a traced training step."""
from benchmarks import scopes


def read(ctx):
    return scopes.class_ms(ctx, "flash_bwd_dkv")

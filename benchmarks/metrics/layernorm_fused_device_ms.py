"""Device milliseconds a traced training step spends in operations that hold
some of a layer-norm module among other work (a norm fused into the matmul
or the residual add beside it), forward and backward: time that cannot be
split."""
from benchmarks import scopes


def read(ctx):
    return scopes.class_ms(ctx, "layernorm", "fused")

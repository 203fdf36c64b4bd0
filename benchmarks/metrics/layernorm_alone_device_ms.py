"""Device milliseconds a traced training step spends in operations that hold
nothing but a layer-norm module (``q_norm``, ``kv_norm``, ``norm``,
``out_norm``), forward and backward: the part XLA did not fuse into the
operation beside it (``layernorm_fused_device_ms``)."""
from benchmarks import scopes


def read(ctx):
    return scopes.class_ms(ctx, "layernorm", "alone")

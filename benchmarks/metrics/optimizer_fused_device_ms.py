"""Device milliseconds a traced training step spends in operations that hold
some of the ``optimizer`` or ``grad_clip`` scopes among other work: XLA fuses
a weight's AdamW update into the matmul that makes its gradient, and time
cannot split a fusion. With ``optimizer_alone_device_ms`` the most the
update can cost."""
from benchmarks import scopes


def read(ctx):
    return scopes.class_ms(ctx, "optimizer", "fused")

"""Device milliseconds a traced training step spends in operations that hold
nothing but the ``optimizer`` and ``grad_clip`` scopes of
``parallel/train_step.py``: the part of the AdamW update and the global-norm
clip that runs apart. XLA fuses most of the update into other operations:
``optimizer_fused_device_ms``."""
from benchmarks import scopes


def read(ctx):
    return scopes.class_ms(ctx, "optimizer", "alone")

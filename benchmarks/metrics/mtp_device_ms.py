"""Device milliseconds a traced training step spends under the
multi-token-prediction module's scope ``mtp``: the next tokens' embedding,
the two norms and the ``4096 -> 2048`` projection, the module's whole expert
layer, its norm, the head's product and the second loss term; forward,
recomputation and backward. One part of the module's layer is not in it:
XLA's own ``ragged-dot-*`` kernels for the routed experts' grouped products
lose the program's scope (``phases.py``) and count under ``experts`` alone,
about a fifth of ``expert_matmul_device_ms``. Nothing from a program without
the module."""
from benchmarks import phases


def read(ctx):
    return phases.phase_ms(ctx, ("mtp",))

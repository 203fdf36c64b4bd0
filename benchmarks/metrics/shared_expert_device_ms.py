"""Device milliseconds a traced training step spends in the shared experts
(the gated MLP ``shared_expert`` beside ``moe`` in an expert layer, which
every token takes: three dense products and the activation), in every expert
layer, the prediction module's too; forward, recomputation and backward. It
stands outside the ``experts`` scope: ``expert_matmul_roofline`` counts routed
pairs only. Nothing from a program without the module."""
from benchmarks import phases


def read(ctx):
    return phases.phase_ms(ctx, ("shared_expert",))

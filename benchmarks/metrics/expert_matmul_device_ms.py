"""Device milliseconds a traced training step spends in the expert layers'
grouped products and the activation between them (scope ``experts``),
forward, recomputation and backward."""
from benchmarks import phases


def read(ctx):
    return phases.phase_ms(ctx, ("experts",))

"""Device milliseconds a traced training step spends in the gated short
convolutions between their two projections (scope ``short_conv``: the two
gates and the shifted multiply-adds), forward, recomputation and backward."""
from benchmarks import phases


def read(ctx):
    return phases.phase_ms(ctx, ("short_conv",))

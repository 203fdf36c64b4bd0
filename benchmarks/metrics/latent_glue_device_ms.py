"""Device milliseconds a traced training step spends between latent
attention's projections and the attention kernels (scope ``latent_assemble``:
the query's scale, rotary on the rotary channels, the shared rotary key
broadcast to every head, the concatenation of a head's key, the head
transposes before the kernels and after them), forward, recomputation and
backward. Nothing from a program without the scope."""
from benchmarks import phases


def read(ctx):
    return phases.phase_ms(ctx, ("latent_assemble",))

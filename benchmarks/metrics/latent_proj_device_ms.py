"""Device milliseconds a traced training step spends on latent attention's
low-rank paths (scopes ``latent_q`` and ``latent_kv`` of ``LatentAttention``:
the q and kv down- and up-projections and the RMS norms on the two latents),
in every layer that has them, the prediction module's too; forward,
recomputation and backward. Nothing from a program that has neither scope."""
from benchmarks import phases


def read(ctx):
    return phases.phase_ms(ctx, ("latent_q", "latent_kv"))

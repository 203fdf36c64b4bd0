"""Seconds JAX spent lowering and compiling (cache hits included) before the
window opened. Source: ``jax.monitoring`` durations (program_span)."""


def read(ctx):
    return ctx["counters"].get("compile_s")

"""Shapes that ``impl='auto'`` left to the einsum path on a TPU because the
flash kernel refused them, counted while tracing (set-up and window).
Source: the program's ``attention_einsum_fallback_total``."""


def read(ctx):
    return ctx["counters"].get("attention_einsum_fallback_total")

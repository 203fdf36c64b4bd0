"""Device milliseconds a traced training step spends under a
``MultiHeadAttention`` module outside its four projections and outside the
Mosaic kernels: the layout copies, casts, masks and rotary around them,
forward and backward."""
from benchmarks import scopes


def read(ctx):
    return scopes.class_ms(ctx, "attention_glue")

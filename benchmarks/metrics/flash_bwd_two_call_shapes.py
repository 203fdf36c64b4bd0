"""Traced flash backwards that run as two kernels (``flash_bwd_dq`` beside
``flash_bwd_dkv``) because the float32 dQ of one (batch, key-value head) is
over the fused backward's VMEM budget, counted while tracing (set-up and
window). Source: the program's ``flash_backward_two_call_total``, which it
declares when it first traces a flash backward; nothing from a program that
has no such counter (its backward is always two kernels), which is what
``benchmarks.adapters.common.registry_counter`` cannot tell from a 0."""

COUNTER = "flash_backward_two_call_total"


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().counters().get(COUNTER)
    return None if value is None else float(value)

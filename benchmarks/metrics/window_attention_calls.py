"""Flash forward calls that carried a sliding window, counted while tracing
(set-up and window): the window layers of a step, once for every time the
step is traced. 0 says the window layers ran as something else (the einsum
path, or a causal call that ignored the window). A run with ``--trace 1``
traces the step a second time, for the program's scope tables, so it reads
twice a step's number, as ``flash_bwd_two_call_shapes`` does. Source: the
program's ``flash_window_call_total``, which it declares at its first flash
call; nothing from a program that has no such counter."""

COUNTER = "flash_window_call_total"


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().counters().get(COUNTER)
    return None if value is None else float(value)

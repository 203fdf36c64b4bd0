"""Dispatches of the train step, after its first, during which JAX compiled
or loaded a program: a step that was traced again (a batch of another
shape), set-up and window alike. 0 unless the run's batches change shape.
Source: the program's ``trainer_step_recompiles_total``, which it declares
when a ``fit`` begins; nothing from a program that has no such counter."""

COUNTER = "trainer_step_recompiles_total"


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().counters().get(COUNTER)
    return None if value is None else float(value)

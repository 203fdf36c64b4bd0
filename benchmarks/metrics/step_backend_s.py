"""Seconds the backend took inside the train step's first dispatch (the
trainer's phase ``trainer.first_step``): XLA's and Mosaic's compile where
the step is cold, the persistent cache's load where it is warm (JAX's own
``backend_compile_duration``, heard by the program's ledger). Source: the
program's ``trainer_first_step_backend_seconds_total``, which it declares
when a ``fit`` begins; nothing from a program that has no such counter."""

COUNTER = "trainer_first_step_backend_seconds_total"


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().counters().get(COUNTER)
    return None if value is None else float(value)

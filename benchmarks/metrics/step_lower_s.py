"""Seconds JAX spent lowering the train step to StableHLO inside its first
dispatch (the trainer's phase ``trainer.first_step``; JAX's own
``jaxpr_to_mlir_module_duration``, heard by the program's ledger). Source:
the program's ``trainer_first_step_lower_seconds_total``, which it declares
when a ``fit`` begins; nothing from a program that has no such counter."""

COUNTER = "trainer_first_step_lower_seconds_total"


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().counters().get(COUNTER)
    return None if value is None else float(value)

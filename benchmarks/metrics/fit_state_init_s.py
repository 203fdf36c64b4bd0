"""Seconds ``fit`` spent making its state (``Trainer.setup_state``: the
state's program traced, compiled or loaded, and dispatched), as the trainer
counts them under its phase ``trainer.setup_state``. Source: the program's
``trainer_setup_state_seconds_total``, which it declares when a ``fit``
begins; nothing from a program that has no such counter."""

COUNTER = "trainer_setup_state_seconds_total"


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().counters().get(COUNTER)
    return None if value is None else float(value)

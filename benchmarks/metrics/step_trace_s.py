"""Seconds of the train step's first dispatch (the trainer's phase
``trainer.first_step``) that were neither lowering nor the backend: Python
tracing the step to a jaxpr, the compile cache's key, the hand-off. A
remainder and not a sum of JAX's trace events, which nest (an inner ``jit``
reports its own inside the outer one's). Source: the program's
``trainer_first_step_seconds_total`` less
``trainer_first_step_lower_seconds_total`` and
``trainer_first_step_backend_seconds_total``; nothing from a program that
lacks any of the three."""

WHOLE = "trainer_first_step_seconds_total"
PARTS = ("trainer_first_step_lower_seconds_total", "trainer_first_step_backend_seconds_total")


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    counters = default_registry().counters()
    if WHOLE not in counters or any(name not in counters for name in PARTS):
        return None
    return float(counters[WHOLE] - sum(counters[name] for name in PARTS))

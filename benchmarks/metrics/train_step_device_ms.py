"""Median device milliseconds of the train step's program in the traced
steps. Source: the ``XLA Modules`` events the mix names (``trace.step_module``)."""
import statistics


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    durations = trace.module_durations(ctx["mix"]["trace"]["step_module"])
    return 1e3 * statistics.median(durations) if durations else None

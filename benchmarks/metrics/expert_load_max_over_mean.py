"""Fullest held expert over the mean held expert, the worst expert layer of a
step, mean of the trainer's last log window (the steps since its last flush
when ``fit`` ended, the traced ones last). Source: the program's gauge
``trainer_moe_expert_load_max_over_mean``."""
from benchmarks import phases


def read(ctx):
    return phases.program_gauge("trainer_moe_expert_load_max_over_mean")

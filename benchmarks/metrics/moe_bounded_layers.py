"""Expert layers of a training step that ran dispatch, experts and combine on
the row bound chosen from the held pairs (``models/core/hybrid.py``), not on
the worst-case buffer: summed over the expert layers, mean of the trainer's
last log window (the traced steps last). The number of expert layers when
routing stays near uniform; less when a layer's held pairs passed the bound
and it paid the worst case. Source: the program's gauge
``trainer_moe_layers_bounded``; nothing from a program without it."""
from benchmarks import phases


def read(ctx):
    return phases.program_gauge("trainer_moe_layers_bounded")

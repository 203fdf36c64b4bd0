"""The experts' grouped products' share of their roofline in a training
step, in percent: the least time the chip could take for the three grouped
products of every expert layer, forward and backward (the larger of
operations over peak FLOP/s and bytes over peak bytes/s,
``rooflines/grouped.py``), for the token-expert pairs the held experts were
given, over the device time under the ``experts`` scope, which holds the
recomputed forward too. How many expert layers a configuration holds is its
own roofline module's to say (``rooflines/<reference>.py::expert_layers``).

The pairs are the program's gauge ``trainer_moe_assignments_held``: the mean
step of the trainer's last log window. The trainer flushes the steps it still
holds when ``fit`` ends and the traced steps are the run's last, so that
window ends with the traced steps: it is the traced steps and, at most, the
49 before them, never an earlier window. The pairs of the traced steps alone
would need a flush where the trace starts, which only the driver can ask for
(PERF.md section 7)."""
import importlib

from benchmarks import phases
from benchmarks.rooflines import grouped


def read(ctx):
    peak, config = ctx["peak"], ctx["config"]
    device_ms = phases.phase_ms(ctx, ("experts",))
    pairs = phases.program_gauge("trainer_moe_assignments_held")
    if not device_ms or pairs is None or peak is None:
        return None
    arch = importlib.import_module(f"benchmarks.rooflines.{config['reference']}")
    layers = arch.expert_layers(config)
    least = layers * grouped.grouped_least_time(
        grouped.expert_products(config, pairs / layers), config["num_experts"], peak)
    return 100.0 * least / (1e-3 * device_ms)

"""Device time of a traced training step under a phase scope, as
``benchmarks/phases.py`` reads it, for a phase that holds loops: the profiler
gives a ``while`` (and a ``conditional``) an event of its own over its body's
operations, which is their time again, so those events are left out here.
Mean over the devices, a step; ``None`` without a trace, a step, the tables or
any operation under the phase."""
from __future__ import annotations

from . import phases, scopes

#: events that stand over other events of the trace: a loop's, a branch's
CONTAINERS = ("while", "conditional")


def phase_ms(ctx: dict, phase: str) -> float | None:
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    tables = scopes.tables(ctx["cell"]["name"])
    if not tables:
        return None
    steps = len(trace.module_durations(ctx["mix"]["trace"]["step_module"])) / len(trace.devices)
    if not steps:
        return None
    wanted = frozenset({phase})
    seconds = sum(
        duration for device in trace.devices for name, _, duration in device.ops
        if not scopes.instruction_of(name).startswith(CONTAINERS)
        and phases._phase_of(scopes.instruction_of(name), tables, wanted)
    ) / len(trace.devices)
    return 1e3 * seconds / steps or None

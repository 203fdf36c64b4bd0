"""Device time of a traced training step by the phase scopes the program's
hybrid-decoder blocks carry (``jax.named_scope``s ``router``, ``dispatch``,
``experts``, ``combine``, ``short_conv``: ``models/core/hybrid.py``), from the
same tables as ``scopes.py``: an operation counts under a phase if that scope
is in the ``op_name`` XLA left on its instruction or, where XLA left none, in
the first scoped ``op_name`` of what is fused into it. One kind of operation
is known by its instruction's name instead: XLA compiles ``jax.lax.ragged_dot``
(the experts' grouped products, ``ops/grouped_matmul.py``) to Mosaic kernels
of its own, ``ragged-dot-*``, and writes its own ``op_name`` over the
program's; they are the ``experts`` phase (were XLA to rename them, that
phase would fall to the activation between the products and
``expert_matmul_roofline`` pass 100 %). Mean over the devices, a step.
``None`` without a trace or the tables (the parent of the PR that added the
scopes has neither)."""
from __future__ import annotations

from . import scopes


#: XLA's own kernels for ``ragged_dot`` (the product and its tile metadata)
RAGGED_DOT = "ragged-dot"


def _phase_of(instruction: str, tables, phases) -> str | None:
    if instruction.startswith(RAGGED_DOT):
        return "experts" if "experts" in phases else None
    op_scopes, fused = tables
    names = [op_scopes.get(instruction, "")] + list(fused.get(instruction, ()))
    for name in names:
        found = scopes.scopes_of(name)
        if found:
            return next((s for s in found if s in phases), None)
    return None


def phase_ms(ctx: dict, phases) -> float | None:
    """Device milliseconds a traced step spent under any of ``phases``."""
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    tables = scopes.tables(ctx["cell"]["name"])
    if not tables:
        return None
    steps = len(trace.module_durations(ctx["mix"]["trace"]["step_module"])) / len(trace.devices)
    if not steps:
        return None
    phases = frozenset(phases)
    seconds = sum(
        duration for device in trace.devices for name, _, duration in device.ops
        if _phase_of(scopes.instruction_of(name), tables, phases)
    ) / len(trace.devices)
    return 1e3 * seconds / steps


def program_gauge(name: str):
    """A gauge of the program's default registry, ``None`` if never set."""
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().snapshot().get("gauges", {}).get(name)
    return None if value is None else float(value)

"""Device time of the flash kernels of a traced training step by the kind of
attention layer that called them. The program's decoder-only family puts a
``jax.named_scope`` around each layer's attention by its kind
(``window_attention``, ``global_attention``: ``models/text/lm.py``), and a
Mosaic kernel keeps the scopes it was traced under in its instruction's
``op_name``, forward and backward alike, so one kernel name (``flash_fwd``,
``flash_bwd_dkv``, ``flash_bwd_dq``) is told apart by the scope beside it.
From the same tables as ``scopes.py``; mean over the devices, a step.
``None`` without a trace, a step, the tables or any flash kernel under the
scope (a program without the scopes has none)."""
from __future__ import annotations

import re

from . import scopes

#: the event of a Mosaic kernel the program named ``flash_<pass>``
FLASH_KERNEL = re.compile(r'^%?flash_\w+(\.\w+)* = .*custom_call_target="tpu_custom_call"')


def flash_ms(ctx: dict, scope: str) -> float | None:
    """Device milliseconds a traced step spent in ``flash_*`` kernels whose
    ``op_name`` carries ``scope``."""
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    tables = scopes.tables(ctx["cell"]["name"])
    if not tables:
        return None
    steps = len(trace.module_durations(ctx["mix"]["trace"]["step_module"])) / len(trace.devices)
    if not steps:
        return None
    op_scopes = tables[0]
    found = [
        duration for device in trace.devices for name, _, duration in device.ops
        if FLASH_KERNEL.search(name)
        and scope in scopes.scopes_of(op_scopes.get(scopes.instruction_of(name), ""))
    ]
    if not found:
        return None
    return 1e3 * sum(found) / len(trace.devices) / steps

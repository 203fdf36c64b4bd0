"""Device time by the model scope it came from.

The profiler names a device operation by its HLO instruction (``fusion.12``,
``flash_fwd.3``); the program's ledger gives the table from instruction to
``op_name``, the path of scopes JAX traced the operation under
(``default_ledger().op_scopes("trainer.step")``), and for a fusion the
``op_name``s of everything fused into it (``fused_scopes``). This joins them
with the trace by instruction name and sums the traced ``XLA Ops`` seconds by
a class decided from ``op_name``. A fusion counts under the one operation
whose metadata XLA kept on it; where XLA kept none, under what it holds.
Time cannot split a fusion, so a class whose work XLA fuses into other
operations (the optimizer's update into the matmul that makes the gradient,
a layer norm into the matmul beside it) is read twice: the operations that
are of that class alone, and those that hold some of it among other work.
Nothing is guessed: an operation with no metadata on it or in it is
unscoped. Against a program without the tables (the parent of the PR that
added this) every reader returns ``None``.
"""
from __future__ import annotations

import functools
import json
import os
import re

from . import trace_reduce

SITE = "trainer.step"
KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
PROJECTIONS = frozenset({"q_proj", "k_proj", "v_proj", "o_proj"})
LAYER_NORMS = frozenset({"q_norm", "kv_norm", "norm", "out_norm"})  # modules._layer_norm's names
_TRANSFORMED = re.compile(r"^(?:\w+\()+([^()]*)\)+$")  # transpose(jvp(name)) -> name


def scopes_of(op_name: str) -> tuple:
    """The named scopes of ``jit(step)/jvp(Model)/encoder/attention/dot_general``:
    what lies between the program and the primitive, transformations taken
    off (``jvp(loss)`` is ``loss``, ``jvp()`` nothing)."""
    parts = op_name.split(";")[0].split("/")  # of operations XLA merged, the first
    if not parts[0].startswith(("jit(", "pjit(")):
        return ()  # a parameter is named by its argument, not by a scope
    out = []
    for part in parts[1:-1]:
        inner = _TRANSFORMED.match(part)
        part = inner.group(1) if inner else part
        if part:
            out.append(part)
    return tuple(out)


@functools.lru_cache(maxsize=None)
def classify(op_name: str, mosaic: bool) -> str:
    """The class of one device operation. ``mosaic`` says the event is a
    Mosaic custom call: a layout copy XLA put before a kernel may carry the
    kernel's ``op_name`` and is glue, not the kernel."""
    scopes = scopes_of(op_name)
    if not scopes:
        return "unscoped"
    kernel = next((k for k in KERNELS if k in scopes), None)
    if kernel and mosaic:
        return kernel
    if "optimizer" in scopes or "grad_clip" in scopes:
        return "optimizer"
    if "loss" in scopes:
        return "loss"
    if LAYER_NORMS.intersection(scopes):
        return "layernorm"
    if kernel or any(s == "attention" or s.startswith("attention.") for s in scopes):
        # a MultiHeadAttention module: its projections, or what it does
        # around the kernels (layout copies, casts, masks, rotary)
        return "attention_proj" if PROJECTIONS.intersection(scopes) else "attention_glue"
    return "mlp" if "mlp" in scopes else "other"


def instruction_of(event_name: str) -> str:
    """``fusion.12`` of the event ``%fusion.12 = bf16[...] fusion(...)``."""
    return event_name.split(" = ")[0].lstrip("%")


def classes_of(instruction: str, mosaic: bool, tables) -> tuple:
    """``(class, classes held)`` of one device operation: the class of the
    ``op_name`` XLA left on the instruction and the set of classes of that
    and of every operation fused into it, unscoped ones aside. An
    instruction XLA left no scope is of the class it holds, all of them
    joined by ``+`` if several, ``unscoped`` if none."""
    op_scopes, fused = tables
    own = classify(op_scopes.get(instruction, ""), mosaic)
    held = {classify(name, mosaic) for name in fused.get(instruction, ())}
    held = frozenset(held | {own}) - {"unscoped"}
    if own == "unscoped" and held:
        own = "+".join(sorted(held))
    return own, held


def tables(cell: str):
    """The program's scope tables ``(op_scopes, fused_scopes)``, ``None``
    where the program has none. Where the harness keeps the trace
    (``BENCH_KEEP_TRACE``) they are kept beside it, for
    ``tools/scope_breakdown.py``."""
    from perceiver_io_tpu.observability import default_ledger

    ledger = default_ledger()
    if not hasattr(ledger, "fused_scopes"):
        return None
    found = ledger.op_scopes(SITE), ledger.fused_scopes(SITE)
    if not found[0]:
        return None
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep:
        path = os.path.join(keep, f"{cell}.scopes.json")
        if not os.path.exists(path):
            os.makedirs(keep, exist_ok=True)
            with open(path, "w") as f:
                json.dump(found, f)
    return found


def device_seconds(trace, step_module: str, scope_tables) -> dict | None:
    """``{"steps", "total", "by_class", "alone", "holding"}``: the traced
    steps, the device seconds of every ``XLA Ops`` event, the same by the
    operation's class, the seconds of the operations that hold one class and
    nothing else, and by class the seconds of the operations that hold some
    of it; mean over the devices. ``None`` without a trace, a device plane, a
    step or a table."""
    if trace is None or not trace.devices or not scope_tables or not scope_tables[0]:
        return None
    steps = len(trace.module_durations(step_module))
    if not steps:
        return None
    sums = {"by_class": {}, "alone": {}, "holding": {}}
    for device in trace.devices:
        for name, _, duration in device.ops:
            mosaic = bool(trace_reduce.CUSTOM_CALL.search(name))
            own, held = classes_of(instruction_of(name), mosaic, scope_tables)
            keys = {"by_class": (own,), "alone": held if len(held) == 1 else (), "holding": held}
            for which, classes in keys.items():
                for cls in classes:
                    sums[which][cls] = sums[which].get(cls, 0.0) + duration
    k = len(trace.devices)
    out = {which: {cls: t / k for cls, t in by.items()} for which, by in sums.items()}
    return {"steps": steps, "total": sum(out["by_class"].values()), **out}


def _of_run(ctx: dict):
    return device_seconds(
        ctx["trace"], ctx["mix"]["trace"]["step_module"], tables(ctx["cell"]["name"])
    )


def class_ms(ctx: dict, cls: str, which: str = "by_class"):
    """Device milliseconds a traced step spent in the operations of class
    ``cls`` (``which`` = ``by_class``), in those that hold nothing but
    ``cls`` (``alone``), or in those that hold some of ``cls`` among other
    work (``fused``: time that cannot be split)."""
    found = _of_run(ctx)
    if found is None:
        return None
    if which == "fused":
        seconds = found["holding"].get(cls, 0.0) - found["alone"].get(cls, 0.0)
    else:
        seconds = found[which].get(cls, 0.0)
    return 1e3 * seconds / found["steps"]


def unscoped_pct(ctx: dict):
    """Share of the traced device-operation time that no scope places."""
    found = _of_run(ctx)
    if found is None or found["total"] <= 0.0:
        return None
    return 100.0 * found["by_class"].get("unscoped", 0.0) / found["total"]

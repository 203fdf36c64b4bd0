"""How many times the flash forward kernel runs in a training step, a
device: the ``flash_fwd`` Mosaic kernel's events in the traced span over the
step program's events there (both counted over every device). A step whose
layers are recomputed in the backward runs it twice an attention call unless
the recomputation keeps the kernel's output and log-sum-exp, and then once;
the backward's kernels and XLA's ``ragged-dot-*`` are not counted."""
import re

#: the event of the Mosaic kernel the program named ``flash_fwd``, under any
#: suffix XLA gives an instruction (``.3``, ``.remat``, ``.clone``)
FLASH_FWD = re.compile(r'^%?flash_fwd(\.\w+)* = .*custom_call_target="tpu_custom_call"')


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    steps = len(trace.module_durations(ctx["mix"]["trace"]["step_module"]))
    return len(trace.op_durations(FLASH_FWD)) / steps if steps else None

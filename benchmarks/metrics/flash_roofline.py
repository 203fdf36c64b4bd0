"""The flash kernels' share of their roofline in a training step, in
percent: the least time the chip could take for every flash call of a step
(forward and backward; the larger of operations over peak FLOP/s and bytes
over peak bytes/s, from the benchmark's own counts) over the summed device
time of the step's ``flash_*`` Mosaic kernels in the trace. Other Mosaic
kernels are other metrics' (XLA's ``ragged-dot-*`` are the experts' grouped
products, which ``expert_matmul_roofline`` counts)."""
import importlib
import re

from benchmarks.rooflines import work

#: the event of a Mosaic kernel the program named ``flash_<pass>``
FLASH_KERNEL = re.compile(r'^%?flash_\w+(\.\d+)* = .*custom_call_target="tpu_custom_call"')


def read(ctx):
    trace, peak, w = ctx["trace"], ctx["peak"], ctx["window"]
    if trace is None or peak is None:
        return None
    steps = len(trace.module_durations(ctx["mix"]["trace"]["step_module"]))
    if not steps or not trace.devices:
        return None
    kernel_s = sum(trace.op_durations(FLASH_KERNEL)) / len(trace.devices)
    if kernel_s <= 0.0:
        return None
    arch = importlib.import_module(f"benchmarks.rooflines.{ctx['config']['reference']}")
    calls = arch.train_step_work(ctx["config"], w["batch"], w["seq_len"])["attentions"]
    least = work.flash_least_time(calls, peak, itemsize=2, training=True)
    return 100.0 * least["seconds"] * steps / kernel_s

"""The flash kernels' share of their roofline in a training step, in
percent: the least time the chip could take for every flash call of a step
(forward and backward; the larger of operations over peak FLOP/s and bytes
over peak bytes/s, from the benchmark's own counts) over the summed device
time of the step's Mosaic custom-call operations in the trace."""
import importlib

from benchmarks.rooflines import work


def read(ctx):
    trace, peak, w = ctx["trace"], ctx["peak"], ctx["window"]
    if trace is None or peak is None:
        return None
    steps = len(trace.module_durations(ctx["mix"]["trace"]["step_module"]))
    kernel_s = trace.custom_call_s()
    if not steps or kernel_s <= 0.0:
        return None
    arch = importlib.import_module(f"benchmarks.rooflines.{ctx['config']['reference']}")
    calls = arch.train_step_work(ctx["config"], w["batch"], w["seq_len"])["attentions"]
    least = work.flash_least_time(calls, peak, itemsize=2, training=True)
    return 100.0 * least["seconds"] * steps / kernel_s

"""The sparse layers' flash kernels' share of their roofline in a training
step, in percent: the least time the chip could take for the attention over
the selected query-key pairs alone, forward and backward
(``rooflines/<reference>.py::sparse_calls``: a ``K``-window's pairs a layer;
the larger of operations over peak FLOP/s and bytes over peak bytes/s), over
the device time of the ``flash_*`` Mosaic kernels under the program's scope
``sparse_attention``. Nothing where the configuration's roofline module names
no sparse calls or the program has no such scope."""
import importlib

from benchmarks import attention_kinds
from benchmarks.rooflines import work


def read(ctx):
    peak, w = ctx["peak"], ctx["window"]
    device_ms = attention_kinds.flash_ms(ctx, "sparse_attention")
    if not device_ms or peak is None:
        return None
    arch = importlib.import_module(f"benchmarks.rooflines.{ctx['config']['reference']}")
    if not hasattr(arch, "sparse_calls"):
        return None
    calls = arch.sparse_calls(ctx["config"], w["batch"], w["seq_len"])
    least = work.flash_least_time(calls, peak, itemsize=2, training=True)
    return 100.0 * least["seconds"] / (1e-3 * device_ms)

"""Model FLOP/s utilisation of the whole training window, in percent: the
operations forward and backward require per step (the benchmark's count at
the cell's prefix dropout; recomputation not counted) times the steps the
window completed, over its seconds, the chips and the chip's bf16 peak."""
import importlib

from benchmarks.rooflines import work


def read(ctx):
    w, peak = ctx["window"], ctx["peak"]
    if not w.get("steps") or peak is None:
        return None
    arch = importlib.import_module(f"benchmarks.rooflines.{ctx['config']['reference']}")
    flops = work.train_step_flops(arch.train_step_work(ctx["config"], w["batch"], w["seq_len"]))
    return 100.0 * flops * w["steps"] / w["window_s"] / (ctx["chips"] * peak["flops_per_s_bf16"])

"""Device milliseconds a traced training step spends in the indexer loss's
Mosaic kernels, known by name (``indexer_kl``, ``indexer_kl_grad``, under any
suffix XLA gives an instruction): the summed events over the step program's
events, a device. They run in the forward pass alone (a recomputed layer
keeps their gradients), and ``indexer_device_ms`` counts them among the
indexer's work. Nothing from a program without such kernels."""
import re

#: the events of the Mosaic kernels the program named ``indexer_kl*``
INDEXER_KL_KERNEL = re.compile(r'^%?indexer_kl\w*(\.\w+)* = .*custom_call_target="tpu_custom_call"')


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    steps = len(trace.module_durations(ctx["mix"]["trace"]["step_module"])) / len(trace.devices)
    found = trace.op_durations(INDEXER_KL_KERNEL)
    if not steps or not found:
        return None
    return 1e3 * sum(found) / len(trace.devices) / steps

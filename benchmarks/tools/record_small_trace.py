"""Record the small trace that ``trace_reduce`` is checked on: three calls
of a jitted program (two matmuls around one flash-attention custom call)
under the benchmark's own span, on the chip. Writes
``chiprun_out/small_trace.xplane.pb`` and prints what the trace holds.
"""
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from benchmarks import harness, trace_reduce
    from perceiver_io_tpu.ops.flash_attention import flash_attention

    harness.require_chips(1)

    @jax.jit
    def small_step(x, w):
        q = (x @ w).reshape(2, 256, 4, 64).transpose(0, 2, 1, 3)
        o = flash_attention(q, q, q, causal=True)
        return o.transpose(0, 2, 1, 3).reshape(2, 256, 256) @ w

    x = jnp.ones((2, 256, 256), jnp.bfloat16)
    w = jnp.full((256, 256), 0.01, jnp.bfloat16)
    jax.block_until_ready(small_step(x, w))
    out_dir = os.path.join(ROOT, "chiprun_out")
    trace_dir = os.path.join(out_dir, "small_trace_dir")
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("small_span"):
            y = small_step(x, w)
        jax.block_until_ready(y)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    shutil.copy(path, os.path.join(out_dir, "small_trace.xplane.pb"))
    shutil.rmtree(trace_dir)
    describe(os.path.join(out_dir, "small_trace.xplane.pb"))
    return 0


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    print("bytes", os.path.getsize(path))
    for plane in data.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  line", repr(line.name), len(events))
            for ev in events[:6]:
                stats = {k: v for k, v in list(ev.stats)[:6]}
                print("     ", repr(ev.name)[:100], ev.start_ns, ev.duration_ns, str(stats)[:200])


if __name__ == "__main__":
    if len(sys.argv) > 1:
        describe(sys.argv[1])
    else:
        sys.exit(main())

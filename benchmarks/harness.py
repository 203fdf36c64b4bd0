"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
runs its driver once, reduces the trace, calls the per-layer metrics'
readers and prints the result line. Knows no cell, configuration or metric
by name."""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class BenchmarkError(Exception):
    """The benchmark's own files are at fault, or there is no chip."""


def load_cell(root: str, workload: str, files_dir: str = HERE) -> dict:
    """Everything the files say about cell ``workload``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(files_dir, "traffic", "mixes", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(files_dir, "peaks.json")) as f:
        peaks = json.load(f)

    def listed(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if listed(m)]
    names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"] if listed(m) and m["moves"] in names]
    return {
        "bench": bench, "cell": cell, "config": config, "mix": mix, "peaks": peaks,
        "end_to_end": end_to_end, "per_layer": per_layer, "files_dir": files_dir,
    }


def load_reader(files_dir: str, metric: str):
    """The ``read(ctx)`` of ``metrics/<metric>.py``."""
    path = os.path.join(files_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("benchmarks.metrics." + metric.replace(".", "_"), path)
    if spec is None or not os.path.exists(path):
        raise BenchmarkError(f"per-layer metric {metric!r} has no reader at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class CompileClock:
    """Totals of JAX's own lowering and compile events (a persistent-cache
    hit is a short compile event), after chip_smoke.py's."""

    def __init__(self):
        import jax.monitoring

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event in COMPILE_EVENTS:
            self.seconds += seconds
        if event == COMPILE_EVENTS[-1]:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Run:
    """One run of one cell: what a driver needs from the harness."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool, root: str,
                 t_start: float):
        self.spec, self.seed, self.seconds, self.trace = spec, int(seed), float(seconds), bool(trace)
        self.cell, self.config, self.mix = spec["cell"], spec["config"], spec["mix"]
        self.root, self.t_start = root, t_start
        self.clock = CompileClock()
        self.spans: list = []
        self.stamps: list = []
        self.counters: dict = {}
        self.setup_s = None
        self.memory_peak_bytes = 0
        self._work = os.path.join(root, ".bench_work", self.cell["name"])
        self.clean_up()

    def clean_up(self):
        """Nothing of a run stays on disk but the compile cache."""
        shutil.rmtree(self._work, ignore_errors=True)
        with contextlib.suppress(OSError):  # the parent too, once no cell's run is using it
            os.rmdir(os.path.dirname(self._work))

    def work_dir(self, name: str) -> str:
        path = os.path.join(self._work, name)
        os.makedirs(path, exist_ok=True)
        return path

    @contextlib.contextmanager
    def span(self, name: str):
        """A span of the benchmark's own, kept in memory and written into the
        profiler's trace as an annotation of the same name."""
        import jax.profiler

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def stamp(self, label: str):
        """Seconds since the process started, under ``label``: where set-up
        goes, printed with the result."""
        self.stamps.append([label, time.perf_counter() - self.t_start])

    def open_window(self):
        self.setup_s = time.perf_counter() - self.t_start
        self.counters["compile_s"] = self.clock.seconds
        self.counters["compiles_setup"] = self.clock.compiles
        self.counters["compile_cache_hits"] = self.clock.cache_hits

    def close_window(self):
        self.counters["compiles_in_window"] = self.clock.compiles - self.counters["compiles_setup"]

    def note_memory_peak(self):
        import jax

        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            # the TPU runtime counts a running program's temporaries as
            # reserved, not in use: the peak is the live arrays beside the
            # largest reservation
            in_use, peak = int(stats.get("bytes_in_use", 0)), int(stats.get("peak_bytes_in_use", 0))
            reserved = int(stats.get("peak_bytes_reserved", 0))
            self.memory_peak_bytes = max(self.memory_peak_bytes, peak, in_use + reserved)

    def start_trace(self):
        import jax.profiler

        self._trace_dir = self.work_dir("trace")
        jax.profiler.start_trace(self._trace_dir)

    def stop_trace(self):
        import jax.profiler

        from . import trace_reduce

        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(self._trace_dir)
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, f"{self.cell['name']}.xplane.pb"))
        trace = trace_reduce.read(path, span_names={n for n, _, _ in self.spans})
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        return trace


def require_chips(chips: int):
    """The devices of the run; raises without a TPU or with too few chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(f"no TPU: JAX's default backend is {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchmarkError(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def configure_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), and every program in it, however
    short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
             files_dir: str = HERE, need_tpu: bool = True, t_start=None) -> dict:
    """Run the cell once and return the result object (the last line)."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_cell(root, workload, files_dir)
    chips = int(spec["cell"]["chips"])
    if need_tpu:
        require_chips(chips)
    device = jax.devices()[0]
    spec["peak"] = spec["peaks"]["by_device_kind"].get(device.device_kind)
    if need_tpu and spec["peak"] is None:
        raise BenchmarkError(f"device kind {device.device_kind!r} is not in peaks.json")
    run = Run(spec, seed, seconds, trace, root, t_start)
    run.stamp("chip_found")
    driver = importlib.import_module(f"benchmarks.drivers.{spec['mix']['driver']}")
    out = driver.run(run)
    run.clean_up()

    limits = spec["mix"]["limits"]
    compared, correct = {}, True
    for name, limit in limits.items():
        value = out["compared"].get(name)
        ok = _finite(value) and value <= limit
        correct = correct and ok
        compared[name] = {"value": value, "limit": limit}
    metrics = {}
    if not trace:
        values = {**out["end_to_end"], "setup_s": run.setup_s}
        for m in spec["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = {
            "cell": spec["cell"], "config": spec["config"], "mix": spec["mix"],
            "peak": spec["peak"], "chips": chips, "counters": run.counters, "spans": run.spans,
            "window": out["window"], "trace": out.get("traced"), "setup_s": run.setup_s,
        }
        for m in spec["per_layer"]:
            value = load_reader(files_dir, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {
        "platform": device.platform, "kind": device.device_kind, "count": chips,
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    result = {
        "correct": bool(correct), "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics, "device": dev,
    }
    traced = out.get("traced")
    if trace and traced is not None:
        dev["busy_s"], dev["window_s"] = traced.busy_s(), traced.window_s()
        result["breakdown"] = {"device_ops": traced.top_ops(10), "idle_gaps": traced.idle_gaps(10)}
    result["window"] = {k: v for k, v in out["window"].items() if _finite(v)}
    result["window"].update({k: v for k, v in run.counters.items() if _finite(v)})
    result["setup_stamps"] = run.stamps
    result["where"] = {k: v for k, v in out["compared"].items() if isinstance(v, str)}
    result["readings"] = {k: v for k, v in out["compared"].items() if _finite(v)}
    for key in ("optimizer", "leaf_table"):
        if key in out:
            result[key] = out[key]
    result["compared"] = compared
    return result


def report(result: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, the result object as the last line of standard output."""
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)

"""Device-cost ledger: compile / memory / retrace attribution per executor.

The telemetry spine (registry, spans, exporters) sees only the host side:
it can say a serve run spent 40 s before first traffic, but not *what* each
executor cost to build, how many bytes it holds resident, or *why* a
logically-same executor rebuilt. Both the Gemma-on-TPU serving comparison
and the pjit/TPUv4 scalable-training paper (PAPERS.md) treat exactly that
device-level attribution — compile time, HBM footprint, retrace cause — as
prerequisites for capacity planning, and the ROADMAP's paged-KV and
sharded-serving items are bounded by compile count and KV memory today.

:class:`CompileLedger` is that attribution layer. Every executor build site
(``inference/generate.py`` generation executors — which the bucket engine's
warmup drives — ``inference/beam.py``, and the slot engine's
prefill/decode/boundary/chunk executors in ``serving/slots.py``) routes
through :func:`~perceiver_io_tpu.inference.generate.cached_executor`, which
hands each fresh build to :meth:`CompileLedger.wrap`. The wrapper AOT-lowers
and compiles the program on its first call (``jit(f).lower().compile()`` —
the same trace+compile work the first jit dispatch would do, paid once) and
records, per cache key:

- **compile wall time** (trace + XLA compile, measured on the ledger clock);
- **cost analysis** — lowered FLOPs and bytes-accessed from XLA's
  ``compiled.cost_analysis()``;
- **memory analysis** — argument / output / temp / generated-code bytes
  from ``compiled.memory_analysis()`` (the executor's resident HBM claim);
- **retrace attribution** — when a logically-same executor (same site, same
  model fingerprint) rebuilds, the named cache-key components are diffed
  against the previous build and the rebuild is counted under every
  component that changed (``bucket_shape``, ``trace_env``,
  ``decode_strategy``, ``phase_plan``, ``config``, ...). The first build of
  an identity is a cold compile, not a retrace.

Registry families fed (docs/observability.md):

- ``compile_total`` counter and ``compile_ms`` histogram;
- ``retrace_total`` plus per-reason ``retrace_reason_<component>_total``;
- ``executor_resident_bytes`` gauge (sum of live executors' temp+output
  bytes — the analytic footprint XLA claims);
- ``hbm_bytes_in_use`` gauge via :meth:`update_device_gauges` — device
  ``memory_stats()`` where the backend provides it (TPU/GPU; CPU returns
  None and the gauge is skipped);
- ``kv_cache_resident_bytes`` gauge — the analytic slot-KV footprint the
  slot engine publishes at construction (everywhere, device stats or not).

**The scope tables** (``op_scopes``, ``fused_scopes``): the profiler names a
device operation by its HLO instruction (``fusion.12``, ``flash_fwd.3``) and
drops the instruction's metadata, where JAX put the path of scopes the
operation was traced under (``jit(step)/jvp(Model)/encoder/.../q_proj/dot_general``:
Flax modules name themselves, ``loss`` / ``grad_clip`` / ``optimizer`` are
named in ``training/tasks.py`` and ``parallel/train_step.py``). Only the
program that compiled the step can give the table from one to the other. A
jitted function that keeps its own dispatch (the trainer's step) is
announced with :meth:`CompileLedger.note_jit`; :meth:`CompileLedger.op_scopes`
lowers and compiles it from the kept argument shapes when first asked (the
lowering is done again; the compile is a persistent-cache hit after the
run's own), parses the optimized HLO with :func:`parse_op_scopes` and keeps
the tables. A fusion has one ``op_name`` and may hold operations of several
scopes: :meth:`CompileLedger.fused_scopes` lists them all.

**What ``jax.jit``'s own dispatch builds** (:meth:`CompileLedger.jax_totals`):
a function that keeps its own dispatch traces, lowers and compiles (or loads
from the persistent cache) inside its first call, where no wrapper of the
ledger's can time it. JAX says what it spent through ``jax.monitoring``, and
one pair of listeners a process, registered when first asked for, keeps the
totals: seconds lowering to StableHLO, seconds in the backend (a compile, or
the persistent cache's load), backend compiles and persistent-cache hits. A
caller reads the totals before and after a call and takes the difference
(the trainer around a step function's first dispatch, ``chip_smoke.py``
around a phase). Tracing is never summed from JAX's events: an inner ``jit``
traced inside an outer one reports its own ``jaxpr_trace_duration`` and the
outer one's holds it, so trace time is what is left of a call once lowering
and the backend are taken off. A listener runs only when JAX builds
something: a warm dispatch pays nothing.

Failure containment: observation must never change execution semantics. If
the wrapped callable cannot be lowered (it is not a jitted function) or the
compiled dispatch rejects the call signature (``TypeError`` — AOT
executables are shape/dtype/weak-type strict), the wrapper permanently falls
back to the plain callable for that executor and counts
``compile_ledger_fallback_total`` — the run proceeds exactly as before the
ledger existed, minus one row of attribution. A *compile* error raises: the
plain jitted callable would only compile the same program a second time.
Genuine *execution* errors (device OOM, XLA runtime failures) re-raise
untouched: retrying a dispatch that may already have consumed donated
buffers would mask the real failure.

Determinism: with an injected clock (``reliability.FakeClock``) the ledger's
records — ordering, sequence numbers, retrace reasons — are a pure function
of the build sequence, pinned by ``tests/test_ledger.py``.
"""
from __future__ import annotations

import hashlib
import re
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

from perceiver_io_tpu.observability.registry import MetricsRegistry


def _sanitize_reason(name: str) -> str:
    """Component name -> metric-name-safe reason token."""
    return "".join(c if (c.isalnum() or c == "_") else "_" for c in name)


class LedgeredExecutor:
    """A jitted executor whose first call is AOT-lowered, compiled, timed,
    and cost/memory-analyzed into the owning ledger; later calls dispatch
    the compiled executable directly. A callable with nothing to lower, or a
    call the compiled executable's strict signature rejects, permanently
    falls back to the plain callable and is counted; a compile error
    raises."""

    __slots__ = ("_fn", "_compiled", "_ledger", "_entry", "_fallback", "_lock")

    def __init__(self, fn: Callable, ledger: "CompileLedger", entry: dict):
        self._fn = fn
        self._compiled = None
        self._ledger = ledger
        self._entry = entry
        self._fallback = False
        self._lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        compiled = self._compiled
        if compiled is None and not self._fallback:
            with self._lock:  # one compiler, even under a scrape thread
                if self._compiled is None and not self._fallback:
                    self._aot_compile(*args, **kwargs)
                compiled = self._compiled
        if compiled is not None:  # local read: a concurrent demotion can't
            try:                  # null the reference mid-dispatch
                return compiled(*args, **kwargs)
            except TypeError:
                # strict AOT signature (no weak-type/shape promotion):
                # demote to the jitted path rather than fail a request over
                # telemetry. Anything else is a genuine execution error —
                # re-raise rather than retry against possibly-donated
                # buffers and mask the real failure. Demote under the lock,
                # exactly once even when several threads hit the drift
                # together, so AOT can't re-arm and the fallback counter
                # counts demotions, not racers.
                with self._lock:
                    first = not self._fallback
                    self._fallback = True
                    self._compiled = None
                if first:
                    self._ledger._count_fallback(self._entry)
        return self._fn(*args, **kwargs)

    def compiled_text(self) -> Optional[str]:
        """The compiled program's text (what ``chip_smoke.py`` searches for
        the Mosaic kernel); None before the first call or after a demotion."""
        compiled = self._compiled
        return None if compiled is None else compiled.as_text()

    def _aot_compile(self, *args, **kwargs) -> None:
        lower = getattr(self._fn, "lower", None)
        if lower is None:  # a plain callable: nothing to compile ahead of time
            self._fallback = True
            self._ledger._count_fallback(self._entry)
            return
        clock = self._ledger._clock
        t0 = clock()
        # a compile error is the computation's own failure: it raises here
        # rather than be retried (and hidden) by the plain jitted callable
        compiled = lower(*args, **kwargs).compile()
        compile_ms = (clock() - t0) * 1e3
        self._compiled = compiled
        cost = _cost_summary(compiled)
        memory = _memory_summary(compiled)
        self._ledger._record_compiled(self._entry, compile_ms, cost, memory)


def _cost_summary(compiled) -> Dict[str, Optional[float]]:
    """``cost_analysis()`` -> {flops, bytes_accessed} (None when the backend
    reports nothing)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {"flops": None, "bytes_accessed": None}
    if not isinstance(ca, dict):
        return {"flops": None, "bytes_accessed": None}
    flops = ca.get("flops")
    accessed = ca.get("bytes accessed")
    return {
        "flops": None if flops is None else float(flops),
        "bytes_accessed": None if accessed is None else float(accessed),
    }


def _memory_summary(compiled) -> Dict[str, Optional[int]]:
    """``memory_analysis()`` -> {argument,output,temp,generated_code}_bytes
    (all None on backends that don't implement it)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    fields = (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("generated_code_bytes", "generated_code_size_in_bytes"),
    )
    if ma is None:
        return {k: None for k, _ in fields}
    out = {}
    for key, attr in fields:
        value = getattr(ma, attr, None)
        out[key] = None if value is None else int(value)
    return out


_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s.*?\s([a-z][\w\-]*)\((.*)$")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
#: how an instruction names a computation whose instructions run as device
#: operations of their own (a fusion's or a reduction's callee does not)
_CALLED = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}"
)
_APPLIED = re.compile(r"\bto_apply=%?([\w.\-]+)")  # a ``call``'s; a ``reduce`` has one too


def parse_op_scopes(hlo_text: str) -> Tuple[Dict[str, str], Dict[str, List[str]]]:
    """``({instruction name: op_name}, {fusion name: [op_name, ...]})`` from a
    compiled program's text, for every instruction of the entry computation
    and of the computations that control flow calls from it (loop bodies,
    branches). The instruction name is what the profiler calls the device
    operation; ``op_name`` is the path of scopes JAX traced it under, as XLA
    left it in the instruction's metadata: on a fusion that of one of the
    operations fused, ``""`` where XLA left none (its own fusions, async
    copies, slices, bitcasts). The second table says what else a fusion
    holds: the distinct ``op_name``s of the instructions fused into it, in
    the program's order, through nested fusions. Nothing is guessed."""
    # computation -> instruction -> (op_name, fused computation)
    computations: Dict[str, Dict[str, Tuple[str, Optional[str]]]] = {}
    entry, called, current = None, set(), None
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header is not None:
            current = computations.setdefault(header.group(2), {})
            if header.group(1):
                entry = header.group(2)
            continue
        instruction = _INSTRUCTION.match(line)
        if current is None or instruction is None:
            continue
        name, opcode, rest = instruction.groups()
        # a Mosaic kernel's backend_config is kilobytes of its encoded body
        rest = rest.partition(", backend_config=")[0]
        op_name, fused = _OP_NAME.search(rest), _FUSED.search(rest)
        current[name] = (
            "" if op_name is None else op_name.group(1),
            None if fused is None else fused.group(1),
        )
        for one, many in _CALLED.findall(rest):
            called.update(n.strip().lstrip("%") for n in (one, *many.split(",")) if n.strip())
        if opcode == "call":
            called.update(_APPLIED.findall(rest))

    def held(computation: str) -> Dict[str, None]:
        names: Dict[str, None] = {}  # a dict keeps the order and drops repeats
        for op_name, fused in computations.get(computation, {}).values():
            if op_name:
                names[op_name] = None
            if fused:  # a fusion nested in the fused computation
                names.update(held(fused))
        return names

    scopes: Dict[str, str] = {}
    fusions: Dict[str, List[str]] = {}
    for name in (entry, *sorted(called)):
        for instruction, (op_name, fused) in computations.get(name, {}).items():
            scopes[instruction] = op_name
            if fused:
                fusions[instruction] = list(held(fused))
    return scopes, fusions


def _abstract(tree):
    """``tree`` with every array leaf replaced by its ``ShapeDtypeStruct``
    (with the sharding of a committed ``jax.Array``): what lowering the call
    again needs, and no buffer."""
    import jax

    def leaf(x):
        if isinstance(x, jax.Array):
            sharding = x.sharding if x.committed else None
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=sharding, weak_type=x.weak_type
            )
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(leaf, tree)


class _JaxCompileEvents:
    """Totals of what JAX reports, through ``jax.monitoring``, of the
    programs it builds in this process, whoever asked for them. Registered
    with JAX once, when first asked to listen (the package imports no
    ``jax`` at module level); JAX calls a listener in the thread that
    compiles, hence the lock."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    #: around the persistent cache's lookup too: a hit is a short one
    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self._lock = threading.Lock()
        self._listening = False
        self.lower_s = 0.0
        self.backend_s = 0.0
        #: read bare by the trainer, twice a step: an int, never torn
        self.backend_compiles = 0
        self.cache_hits = 0

    def listen(self) -> None:
        if self._listening:
            return
        with self._lock:
            if self._listening:
                return
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
            self._listening = True

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == self.LOWER:
            with self._lock:
                self.lower_s += seconds
        elif event == self.BACKEND:
            with self._lock:
                self.backend_s += seconds
                self.backend_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return {
                "lower_s": self.lower_s,
                "backend_s": self.backend_s,
                "backend_compiles": self.backend_compiles,
                "cache_hits": self.cache_hits,
            }


#: one a process, shared by every ledger: JAX's events are the process's
_JAX_EVENTS = _JaxCompileEvents()


class CompileLedger:
    """Per-executor compile/memory/retrace ledger over one metrics registry.

    :param registry: registry the canonical families land on; defaults to
        the process-wide :func:`~perceiver_io_tpu.observability.default_registry`
        (executor caches are process-global, so their ledger is too).
    :param clock: monotonic time source for compile timing —
        ``reliability.FakeClock`` makes records fully deterministic.
    :param keep: bound on retained per-key records (FIFO; the registry
        counters keep counting past it).
    """

    def __init__(self, *, registry: Optional[MetricsRegistry] = None,
                 clock: Callable[[], float] = time.monotonic,
                 keep: int = 512):
        if registry is None:
            from perceiver_io_tpu.observability.registry import default_registry

            registry = default_registry()
        self.registry = registry
        self._clock = clock
        self._keep = keep
        self._lock = threading.Lock()
        self._records: List[dict] = []
        #: identity -> components of that identity's most recent build
        self._last: Dict[tuple, Dict[str, str]] = {}
        #: (site, components) -> latest build's temp+output bytes; kept
        #: incrementally so the resident gauge costs O(1) per compile
        #: (independent of the ``keep`` record bound)
        self._resident: Dict[tuple, int] = {}
        #: lifetime totals — unlike ``_records`` these never FIFO out, so
        #: the rollup stays exact past the ``keep`` bound
        self._total_retraces = 0
        self._total_compile_ms = 0.0
        self._reason_totals: Dict[str, int] = {}
        self._seq = 0
        self._on_record: List[Callable[[dict], None]] = []
        #: site -> the jitted function last announced there and its abstract
        #: arguments or, once asked for, its scope tables (``note_jit``)
        self._jits: Dict[str, dict] = {}
        self._scopes_lock = threading.Lock()
        registry.declare_counters(
            "compile_total", "retrace_total", "compile_ledger_fallback_total"
        )

    # -- wiring ---------------------------------------------------------------
    def wrap(self, executor: Callable, *, site: str,
             components: Dict[str, Any]) -> Callable:
        """Wrap one freshly built jitted executor for ledger accounting.

        :param site: build-site name (``generate``, ``beam``,
            ``slot_prefill``, ``slot_decode``, ``slot_prefill_chunk``).
        :param components: the NAMED cache-key components — retrace
            attribution diffs these, so every key-relevant knob must appear
            (``model``, ``bucket_shape``, ``trace_env``, ...). Values are
            stringified; ``model`` (or the whole dict) defines the identity
            a rebuild is compared against.
        """
        comps = {k: str(v) for k, v in components.items()}
        entry = {"site": site, "components": comps}
        return LedgeredExecutor(executor, self, entry)

    def note_jit(self, site: str, fn: Callable, args: tuple = ()) -> None:
        """Announce a jitted function that keeps ``jax.jit``'s own dispatch
        (the trainer's step at site ``trainer.step``) with one call's
        arguments. The ledger keeps the function and the arguments' shapes,
        dtypes and shardings, no array, so :meth:`op_scopes` can lower it
        again after its caller is gone. A kept function keeps what
        ``jax.jit`` cached for it, its loaded executable too: a caller that
        is done with the function calls its ``clear_cache()`` (the trainer
        does when ``fit`` ends). A later announcement at ``site`` replaces
        this one; :meth:`reset` drops it."""
        noted = {"fn": fn, "args": _abstract(args), "tables": None}
        with self._lock:
            self._jits[site] = noted

    def op_scopes(self, site: str) -> Dict[str, str]:
        """``{instruction name: op_name}`` of the program announced at
        ``site`` (:func:`parse_op_scopes`). Lowered and compiled when first
        asked, which takes about what a warm start spends on the step (the
        run's persistent compile cache answers the compile, not the
        lowering), recorded like any executor's build, then kept. Empty when
        nothing was announced; a function the ledger cannot lower or compile
        gives an empty table too and counts ``compile_ledger_fallback_total``."""
        return dict(self._scope_tables(site)[0])

    def fused_scopes(self, site: str) -> Dict[str, List[str]]:
        """``{fusion's instruction name: [op_name, ...]}`` of the same
        program: what each fusion holds besides the one ``op_name``
        :meth:`op_scopes` gives it (XLA fuses an optimizer update into the
        matmul that makes its gradient, and the fusion is named by the
        matmul). From the same compile as :meth:`op_scopes`."""
        return dict(self._scope_tables(site)[1])

    def _scope_tables(self, site: str) -> Tuple[dict, dict]:
        with self._lock:
            noted = self._jits.get(site)
        if noted is None:
            return {}, {}
        with self._scopes_lock:
            if noted["tables"] is None:
                noted["tables"] = self._compile_and_parse(site, noted["fn"], noted["args"])
                # the tables are all that is kept: the function can go with
                # its caller
                noted["fn"] = noted["args"] = None
            return noted["tables"]

    def _compile_and_parse(self, site: str, fn: Callable, args: tuple) -> Tuple[dict, dict]:
        t0 = self._clock()
        try:
            compiled = fn.lower(*args).compile()
            text = compiled.as_text()
        except Exception as e:  # the run has its step; only the tables are lost
            warnings.warn(f"op_scopes({site!r}): cannot compile the announced function: {e!r}")
            self._count_fallback()
            return {}, {}
        entry = {"site": site, "components": {
            "function": getattr(fn, "__name__", type(fn).__name__),
            "arguments": hashlib.sha1(repr(args).encode()).hexdigest()[:12],
        }}
        self._record_compiled(
            entry, (self._clock() - t0) * 1e3, _cost_summary(compiled), _memory_summary(compiled)
        )
        return parse_op_scopes(text)

    def jax_totals(self) -> Dict[str, float]:
        """One reading of what JAX has built in this process since the
        ledger (any ledger) first listened: ``lower_s`` (seconds lowering to
        StableHLO), ``backend_s`` (seconds in the backend: compiles and the
        persistent cache's loads), ``backend_compiles`` (both kinds) and
        ``cache_hits`` (the loads among them). Process-wide and monotonic,
        whichever ledger is asked and whatever :meth:`reset` drops: take the
        difference of two readings. The first call registers the listeners,
        so what was built before it is not in any reading."""
        _JAX_EVENTS.listen()
        return _JAX_EVENTS.totals()

    def backend_compiles(self) -> int:
        """``jax_totals()["backend_compiles"]`` as one bare read: what a
        loop can afford around every dispatch. The same number from two
        reads says that nothing was compiled or loaded between them (by any
        thread of the process)."""
        _JAX_EVENTS.listen()
        return _JAX_EVENTS.backend_compiles

    def attach(self, callback: Callable[[dict], None]) -> Callable[[], None]:
        """Register a per-record callback (the serve CLI forwards records as
        ``ledger.compile`` span events into events.jsonl); returns a detach
        function. Callback exceptions are swallowed — the ledger must never
        fail the build it observes."""
        self._on_record.append(callback)

        def detach() -> None:
            try:
                self._on_record.remove(callback)
            except ValueError:
                pass

        return detach

    # -- recording -------------------------------------------------------------
    def _identity(self, site: str, components: Dict[str, str]) -> tuple:
        """A rebuild is "logically the same executor" when site + model
        match; everything else (bucket shape, phase plan, env fingerprint,
        decode strategy) is a variant axis a retrace is attributed to."""
        return (site, components.get("model", ""))

    def _record_compiled(self, entry: dict, compile_ms: float,
                         cost: Dict[str, Optional[float]],
                         memory: Dict[str, Optional[int]]) -> None:
        site, comps = entry["site"], entry["components"]
        identity = self._identity(site, comps)
        with self._lock:
            self._seq += 1
            prev = self._last.get(identity)
            reasons: tuple = ()
            if prev is not None:
                changed = sorted(
                    k for k in (set(prev) | set(comps))
                    if prev.get(k) != comps.get(k)
                )
                reasons = tuple(changed) if changed else ("duplicate_key",)
            self._last[identity] = comps
            record = {
                "seq": self._seq,
                "site": site,
                "components": dict(comps),
                "compile_ms": round(compile_ms, 3),
                "flops": cost["flops"],
                "bytes_accessed": cost["bytes_accessed"],
                **memory,
                "retrace": prev is not None,
                "retrace_reasons": list(reasons),
            }
            self._records.append(record)
            if len(self._records) > self._keep:
                self._records.pop(0)
            self._total_compile_ms += compile_ms
            if reasons:
                self._total_retraces += 1
                for reason in reasons:
                    self._reason_totals[reason] = (
                        self._reason_totals.get(reason, 0) + 1
                    )
            # one entry per distinct (site, components) executor — a
            # rebuild of the same program replaces its bytes rather than
            # accumulating (the ledger can't see cache evictions; evicted
            # executors stay counted until reset)
            self._resident[(site, tuple(sorted(comps.items())))] = (
                (memory["temp_bytes"] or 0) + (memory["output_bytes"] or 0)
            )
            resident = sum(self._resident.values())
        reg = self.registry
        reg.inc("compile_total")
        reg.observe("compile_ms", compile_ms)
        if reasons:
            reg.inc("retrace_total")
            for reason in reasons:
                reg.inc(f"retrace_reason_{_sanitize_reason(reason)}_total")
        reg.set_gauge("executor_resident_bytes", resident)
        for callback in list(self._on_record):
            try:
                callback(record)
            except Exception:
                pass

    def _count_fallback(self, entry: Optional[dict] = None) -> None:
        """Count a demotion; when the executor had recorded resident bytes
        (post-compile strict-signature demotion frees the AOT executable),
        drop them from the gauge — the plain-jit replacement is untracked."""
        if entry is not None:
            key = (entry["site"], tuple(sorted(entry["components"].items())))
            with self._lock:
                dropped = self._resident.pop(key, None)
                resident = sum(self._resident.values())
            if dropped is not None:
                self.registry.set_gauge("executor_resident_bytes", resident)
        self.registry.inc("compile_ledger_fallback_total")

    # -- device gauges -----------------------------------------------------------
    def update_device_gauges(self) -> Optional[int]:
        """Publish ``hbm_bytes_in_use`` from the backend's live
        ``memory_stats()`` (first device). Returns the bytes value, or None
        on backends (CPU) that report nothing — the analytic gauges
        (``kv_cache_resident_bytes``, ``executor_resident_bytes``) are the
        everywhere-available fallback."""
        try:
            import jax

            stats = jax.devices()[0].memory_stats()
        except Exception:
            stats = None
        if not stats or "bytes_in_use" not in stats:
            return None
        value = int(stats["bytes_in_use"])
        self.registry.set_gauge("hbm_bytes_in_use", value)
        return value

    def set_kv_cache_bytes(self, nbytes: int) -> None:
        """Analytic KV-cache footprint gauge (the slot engine publishes its
        persistent slot state's byte size — exact on every backend)."""
        self.registry.set_gauge("kv_cache_resident_bytes", int(nbytes))

    # -- introspection / export ---------------------------------------------------
    def records(self, site: Optional[str] = None) -> List[dict]:
        with self._lock:
            out = [dict(r) for r in self._records]
        if site is not None:
            out = [r for r in out if r["site"] == site]
        return out

    def rollup(self) -> dict:
        """Records-free summary — counts, reasons, compile-time total. This
        is what pollable surfaces (``ServingEngine.stats()``) embed: no
        per-record dict copies on the scrape path. All values are LIFETIME
        totals (matching the registry counters), not views over the
        ``keep``-bounded record list."""
        with self._lock:
            rollup = {
                "compiles": self._seq,
                "retraces": self._total_retraces,
                "retrace_reasons": dict(sorted(self._reason_totals.items())),
                "compile_ms_total": round(self._total_compile_ms, 3),
            }
        rollup["fallbacks"] = int(
            self.registry.counter("compile_ledger_fallback_total")
        )
        return rollup

    def snapshot(self) -> dict:
        """JSON-able ledger view: the lifetime rollup plus the per-key
        compile/memory table every durable consumer (``serve_stats``,
        snapshots, ``obs report``) embeds, and under ``jax`` the process's
        totals of what JAX itself built (:meth:`jax_totals`). The table is
        bounded by ``keep`` (oldest rows FIFO out); the rollup keeps
        counting past it."""
        return {**self.rollup(), "jax": self.jax_totals(), "records": self.records()}

    def reset(self) -> None:
        """Drop records and identity history (test isolation; registry
        counters are reset separately via ``registry.reset``)."""
        with self._lock:
            self._records.clear()
            self._last.clear()
            self._resident.clear()
            self._total_retraces = 0
            self._total_compile_ms = 0.0
            self._reason_totals.clear()
            self._seq = 0
            self._jits.clear()
        # the executors the gauge described are gone too
        self.registry.set_gauge("executor_resident_bytes", 0)


#: Process-wide default ledger, mirroring ``default_registry()``: the
#: executor caches it observes are process-global singletons.
_DEFAULT: Optional[CompileLedger] = None
_DEFAULT_LOCK = threading.Lock()


def default_ledger() -> CompileLedger:
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = CompileLedger()
        return _DEFAULT

"""Per-phase decode strategies: cached vs recompute, chosen by measurement.

The decode loop passes through three cache phases (``generate.py`` module
docstring): latent growth (cached step runs O(1) tokens of compute; its
speed-up on the chip is not measured), prefix
growth ("boundary" — the cache elides only the ``2·n·c²`` full-window
embedding + cross-k/v projections while the latent stack is recomputed
either way), and the sliding window (recompute is semantically forced by
the learned absolute position embedding). On a CPU the cached boundary
step has lost to full recompute (on the chip: not measured): whether the
elision beats its own bookkeeping is a platform and shape question —
exactly the portable-caching tradeoff of the compiler-first O(1)-caching
paper (PAPERS.md) — so it should be a *measured choice*, not a hardcoded
one.

This module is that choice:

- :class:`DecodeStrategy` — the per-phase table ``{latent, boundary,
  window} -> {cached, recompute}``. Both boundary implementations are
  exact (the cached step's gather+attend is bitwise identical to the
  uncached forward), so greedy output is token-identical across every
  strategy — pinned by ``tests/test_decode_strategy.py``.
- :func:`resolve` — strategy resolution for ``generate()`` and the
  serving engines: explicit argument > ``PERCEIVER_DECODE_STRATEGY`` env
  var > ``"auto"`` (registry lookup, falling back to ``cached`` when
  nothing has been measured — the status-quo default).
- :func:`autotune_boundary` — the warmup-time autotuner: microbenchmark
  cached vs recompute boundary-phase decoding at the bound shape, pick
  the winner, memoize it in a process registry keyed by
  ``(shape, platform, ragged_attention.trace_env())``. With optional
  JSON persistence (``persist=`` / ``PERCEIVER_DECODE_STRATEGY_FILE``) a
  deployment measures once and every later process loads the verdict.
- ``python -m perceiver_io_tpu.inference.decode_strategy`` — the
  standalone probe behind ``make decode-tune``;
  ``examples/perf/decode_scaling.py`` emits the same JSON artifact.

The registry key deliberately excludes batch size (the cached-vs-recompute
tradeoff is a per-row FLOP balance; both sides scale with batch) so one
warmup measurement covers every micro-batch shape an engine dispatches.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Optional, Union

MODES = ("auto", "cached", "recompute")
PHASE_CHOICES = ("cached", "recompute")

#: slot-engine cross-KV layouts (docs/serving.md "Block-paged KV"): the
#: dense-vs-paged choice is the SAME kind of measured platform/shape
#: property as cached-vs-recompute — the paged gather's bookkeeping
#: competes with the dense layout's footprint — so it lives in this
#: module's registry, resolved and autotuned the same way.
KV_LAYOUTS = ("auto", "dense", "paged", "paged_int8")
KV_LAYOUT_CHOICES = ("dense", "paged", "paged_int8")
#: the layouts that address KV through the block pool (``paged_int8`` is
#: ``paged`` plus int8 storage with per-(position, head) dequant scales,
#: docs/serving.md "Quantized KV") — everywhere the engine asks "is this
#: the paged machinery" it checks membership here, not ``== "paged"``
PAGED_KV_LAYOUTS = ("paged", "paged_int8")

#: slot-engine cross-request prefix-cache axis (docs/serving.md "Prefix
#: sharing"): whether paged admissions map hot prompt-prefix blocks by
#: reference instead of re-projecting them. Like the layouts it is a
#: deployment property (traffic skew decides whether the radix index pays
#: its bookkeeping), so it rides in the same persisted registry.
PREFIX_CACHE_MODES = ("auto", "on", "off")
PREFIX_CACHE_CHOICES = ("on", "off")

#: slot-engine self-draft speculative-decoding axis (docs/serving.md
#: "Speculative decoding"): ``k{K}d{D}`` proposes K tokens per round from a
#: D-layer truncated latent stack (full-model params, no second checkpoint)
#: and verifies all K+1 positions in one batched forward — greedy output
#: token-identical to the non-speculative step, so whether it PAYS is the
#: same measured platform/shape property as every other axis here:
#: acceptance rate × per-round cost vs K+1 plain steps. Draft depths past 2
#: approach full-model cost and stop being drafts, so the measured grid
#: stops there.
SPECULATION_CHOICES = ("off",) + tuple(
    f"k{k}d{d}" for d in (1, 2) for k in (2, 4, 8)
)
SPECULATION_MODES = ("auto",) + SPECULATION_CHOICES

#: env var overriding the boundary-phase strategy process-wide
ENV_VAR = "PERCEIVER_DECODE_STRATEGY"
#: env var overriding the slot engine's KV layout process-wide
ENV_KV_LAYOUT = "PERCEIVER_KV_LAYOUT"
#: env var overriding the slot engine's prefix-cache mode process-wide
ENV_PREFIX_CACHE = "PERCEIVER_PREFIX_CACHE"
#: env var overriding the slot engine's speculation mode process-wide
ENV_SPECULATION = "PERCEIVER_SPECULATION"
#: env var pointing at a persisted strategy-registry JSON file
ENV_FILE = "PERCEIVER_DECODE_STRATEGY_FILE"
#: env var overriding the int8 quality-gate budget (max greedy logit
#: delta vs the exact paged layout the autotuner will accept)
ENV_KV_QUANT_BUDGET = "PERCEIVER_KV_QUANT_BUDGET"
#: default quality-gate budget: max |logit delta| across every greedy
#: decode step of the probe workload. 0.05 is far below typical
#: top-1/top-2 logit gaps at the probe shapes yet generous to 8-bit
#: rounding noise; deployments tune it like any other strategy knob.
DEFAULT_KV_QUANT_BUDGET = 0.05


def kv_quant_budget() -> float:
    """The int8 quality-gate budget (:data:`ENV_KV_QUANT_BUDGET` >
    :data:`DEFAULT_KV_QUANT_BUDGET`; unparseable overrides fall back to
    the default, the registry-env-knob discipline)."""
    raw = os.environ.get(ENV_KV_QUANT_BUDGET)
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    return DEFAULT_KV_QUANT_BUDGET


@dataclasses.dataclass(frozen=True)
class DecodeStrategy:
    """Per-phase cache strategy table. ``window`` is pinned to recompute —
    with the reference's learned absolute position embedding an incremental
    sliding-window step is semantically impossible, not merely slow
    (``generate.py`` module docstring). ``latent == "recompute"`` forces
    the boundary phase to recompute too: the boundary cache is built by
    the prefill/latent steps, so skipping them leaves it stale."""

    latent: str = "cached"
    boundary: str = "cached"
    window: str = "recompute"

    def __post_init__(self):
        for phase in ("latent", "boundary"):
            value = getattr(self, phase)
            if value not in PHASE_CHOICES:
                raise ValueError(
                    f"{phase} strategy must be one of {PHASE_CHOICES}, got {value!r}"
                )
        if self.window != "recompute":
            raise ValueError(
                "window strategy is pinned to 'recompute': the learned "
                "absolute position embedding re-positions every surviving "
                "token each step, so no exact incremental form exists"
            )

    @property
    def boundary_cached(self) -> bool:
        return self.latent == "cached" and self.boundary == "cached"


#: (shape_key, platform, trace_env) -> measurement entry dict
_REGISTRY: dict = {}
#: same key space -> {"kv_layout": "dense"|"paged", ...} measurement entry
#: (separate dict so a boundary-only artifact and a kv-only artifact can
#: merge without clobbering each other)
_KV_REGISTRY: dict = {}
#: same key space -> {"prefix_cache": "on"|"off", ...} measurement entry
_PREFIX_REGISTRY: dict = {}
#: same key space -> {"speculation": "off"|"k{K}d{D}", ...} measurement entry
_SPEC_REGISTRY: dict = {}
#: platform -> {"swap_gbps": float, ...} calibrated host-link rate for the
#: swap-preemption cost model (docs/serving.md "Host-swap preemption").
#: Keyed by PLATFORM ALONE: the device<->host link is a hardware property,
#: not a model-shape or trace-env one — one measured rate serves every
#: engine on the box.
_SWAP_REGISTRY: dict = {}
_FILE_LOADED: set = set()  # paths already merged into the registries


def shape_key(model) -> tuple:
    """The architecture coordinates the boundary tradeoff depends on —
    window size (the elided ``2·n·c²`` work), latent count and stack depth
    (the recomputed-in-both-paths work), and width/heads."""
    cfg = model.config
    return (
        int(cfg.max_seq_len),
        int(cfg.max_latents),
        int(cfg.num_channels),
        int(cfg.num_heads),
        int(cfg.num_self_attention_layers),
    )


def registry_key(model, platform: Optional[str] = None) -> tuple:
    from perceiver_io_tpu.ops.ragged_attention import trace_env

    if platform is None:
        import jax

        platform = jax.default_backend()
    return (shape_key(model), str(platform), trace_env())


def _maybe_load_env_file() -> None:
    path = os.environ.get(ENV_FILE)
    if path and path not in _FILE_LOADED and os.path.exists(path):
        load_registry(path)


def lookup(model, platform: Optional[str] = None) -> Optional[str]:
    """Measured boundary winner for this shape/platform/env, or None."""
    _maybe_load_env_file()
    entry = _REGISTRY.get(registry_key(model, platform))
    return None if entry is None else entry["boundary"]


def record(model, boundary: str, *, platform: Optional[str] = None,
           **extra) -> dict:
    """Store a boundary verdict (plus measurement metadata) for this
    shape/platform/env; returns the entry. Used by the autotuner and by
    ``examples/perf/decode_scaling.py`` so the scaling study feeds the same
    registry the serving warmup reads."""
    if boundary not in PHASE_CHOICES:
        raise ValueError(f"boundary must be one of {PHASE_CHOICES}, got {boundary!r}")
    entry = {"boundary": boundary, **extra}
    _REGISTRY[registry_key(model, platform)] = entry
    return entry


def lookup_kv_layout(model, platform: Optional[str] = None) -> Optional[str]:
    """Measured KV-layout winner for this shape/platform/env, or None."""
    _maybe_load_env_file()
    entry = _KV_REGISTRY.get(registry_key(model, platform))
    return None if entry is None else entry["kv_layout"]


def kv_entry(model, platform: Optional[str] = None) -> Optional[dict]:
    """The full KV-layout registry entry (verdict + measurement metadata,
    including the ``quant_gate`` dict the autotuner records), or None.
    Read-only view for observability (the engine's warmup reports the
    quality-gate outcome through ``kv_quant_fallback_total``)."""
    _maybe_load_env_file()
    entry = _KV_REGISTRY.get(registry_key(model, platform))
    return None if entry is None else dict(entry)


def record_kv_layout(model, kv_layout: str, *, platform: Optional[str] = None,
                     **extra) -> dict:
    """Store a KV-layout verdict (plus measurement metadata) for this
    shape/platform/env; returns the entry."""
    if kv_layout not in KV_LAYOUT_CHOICES:
        raise ValueError(
            f"kv_layout must be one of {KV_LAYOUT_CHOICES}, got {kv_layout!r}"
        )
    entry = {"kv_layout": kv_layout, **extra}
    _KV_REGISTRY[registry_key(model, platform)] = entry
    return entry


def lookup_prefix_cache(model, platform: Optional[str] = None) -> Optional[str]:
    """Recorded prefix-cache verdict for this shape/platform/env, or None."""
    _maybe_load_env_file()
    entry = _PREFIX_REGISTRY.get(registry_key(model, platform))
    return None if entry is None else entry["prefix_cache"]


def record_prefix_cache(model, prefix_cache: str, *,
                        platform: Optional[str] = None, **extra) -> dict:
    """Store a prefix-cache verdict (plus metadata — e.g. the measured hit
    ratio a deployment observed) for this shape/platform/env."""
    if prefix_cache not in PREFIX_CACHE_CHOICES:
        raise ValueError(
            f"prefix_cache must be one of {PREFIX_CACHE_CHOICES}, "
            f"got {prefix_cache!r}"
        )
    entry = {"prefix_cache": prefix_cache, **extra}
    _PREFIX_REGISTRY[registry_key(model, platform)] = entry
    return entry


def resolve_prefix_cache(
    mode: Optional[str],
    model=None,
    *,
    platform: Optional[str] = None,
) -> str:
    """Resolve a slot-engine prefix-cache request into ``"on"`` or
    ``"off"`` (docs/serving.md "Prefix sharing").

    Order mirrors :func:`resolve_kv_layout`: explicit mode >
    :data:`ENV_PREFIX_CACHE` > ``"auto"`` (registry lookup, falling back
    to ``"off"`` — the status-quo unshared path — when nothing has been
    recorded). Sharing only exists under ``kv_layout="paged"``; the
    engine enforces that pairing, not this resolver.
    """
    if mode is None:
        mode = os.environ.get(ENV_PREFIX_CACHE) or "auto"
    if mode not in PREFIX_CACHE_MODES:
        raise ValueError(
            f"prefix cache must be one of {PREFIX_CACHE_MODES}, got {mode!r}"
        )
    if mode == "auto":
        measured = (
            lookup_prefix_cache(model, platform) if model is not None else None
        )
        return measured or "off"
    return mode


def lookup_speculation(model, platform: Optional[str] = None) -> Optional[str]:
    """Measured speculation winner for this shape/platform/env, or None."""
    _maybe_load_env_file()
    entry = _SPEC_REGISTRY.get(registry_key(model, platform))
    return None if entry is None else entry["speculation"]


def spec_entry(model, platform: Optional[str] = None) -> Optional[dict]:
    """The full speculation registry entry (verdict + measurement metadata,
    including the acceptance rate the autotuner observed), or None.
    Read-only view for observability and the perf examples."""
    _maybe_load_env_file()
    entry = _SPEC_REGISTRY.get(registry_key(model, platform))
    return None if entry is None else dict(entry)


def record_speculation(model, speculation: str, *,
                       platform: Optional[str] = None, **extra) -> dict:
    """Store a speculation verdict (plus measurement metadata — acceptance
    rate, per-token timings) for this shape/platform/env."""
    if speculation not in SPECULATION_CHOICES:
        raise ValueError(
            f"speculation must be one of {SPECULATION_CHOICES}, "
            f"got {speculation!r}"
        )
    entry = {"speculation": speculation, **extra}
    _SPEC_REGISTRY[registry_key(model, platform)] = entry
    return entry


def resolve_speculation(
    mode: Optional[str],
    model=None,
    *,
    platform: Optional[str] = None,
) -> str:
    """Resolve a slot-engine speculation request into one of
    :data:`SPECULATION_CHOICES` (docs/serving.md "Speculative decoding").

    Order mirrors :func:`resolve_kv_layout`: explicit mode >
    :data:`ENV_SPECULATION` > ``"auto"`` (registry lookup, falling back to
    ``"off"`` — the status-quo one-token step — when nothing has been
    measured). Speculation is greedy-only; the engine enforces that
    pairing, not this resolver.
    """
    if mode is None:
        mode = os.environ.get(ENV_SPECULATION) or "auto"
    if mode not in SPECULATION_MODES:
        raise ValueError(
            f"speculation must be one of {SPECULATION_MODES}, got {mode!r}"
        )
    if mode == "auto":
        measured = (
            lookup_speculation(model, platform) if model is not None else None
        )
        return measured or "off"
    return mode


def lookup_swap_gbps(platform: Optional[str] = None) -> Optional[float]:
    """Calibrated host-link rate (decimal GB/s) for this platform, or
    None when no swap has ever been measured here — the slot engine's
    ``swap_link_gbps=None`` resolution falls back to its prior then."""
    _maybe_load_env_file()
    if platform is None:
        import jax

        platform = jax.default_backend()
    entry = _SWAP_REGISTRY.get(str(platform))
    return None if entry is None else float(entry["swap_gbps"])


def swap_entry(platform: Optional[str] = None) -> Optional[dict]:
    """The full calibrated-swap registry entry (rate + measurement
    metadata), or None. Read-only view for observability."""
    _maybe_load_env_file()
    if platform is None:
        import jax

        platform = jax.default_backend()
    entry = _SWAP_REGISTRY.get(str(platform))
    return None if entry is None else dict(entry)


def record_swap_gbps(gbps: float, *, platform: Optional[str] = None,
                     **extra) -> dict:
    """Store a measured host-link rate for this platform (plus
    measurement metadata — bytes moved, transfer wall time); returns the
    entry. The slot engine calls this after every real swap transfer, so
    the persisted artifact carries a calibrated rate forward to the next
    process (``swap_entries``, beside ``spec_entries``)."""
    gbps = float(gbps)
    if not gbps > 0:
        raise ValueError(f"swap_gbps must be > 0, got {gbps!r}")
    if platform is None:
        import jax

        platform = jax.default_backend()
    entry = {"swap_gbps": gbps, **extra}
    _SWAP_REGISTRY[str(platform)] = entry
    return entry


def reset_registry() -> None:
    """Test isolation: drop every memoized verdict and forget loaded files."""
    _REGISTRY.clear()
    _KV_REGISTRY.clear()
    _PREFIX_REGISTRY.clear()
    _SPEC_REGISTRY.clear()
    _SWAP_REGISTRY.clear()
    _FILE_LOADED.clear()


def _key_to_json(key: tuple) -> dict:
    shape, platform, env = key
    return {"shape": list(shape), "platform": platform, "env": repr(env)}


def _key_from_json(obj: dict) -> tuple:
    # env fingerprints are tuples of primitives; repr round-trips via eval-free
    # literal parsing
    import ast

    return (tuple(obj["shape"]), obj["platform"], ast.literal_eval(obj["env"]))


def save_registry(path: str) -> None:
    """Persist every memoized verdict as the deployment JSON artifact
    (atomic write; ``load_registry`` and ``PERCEIVER_DECODE_STRATEGY_FILE``
    consume it)."""
    entries = [
        {"key": _key_to_json(key), **entry} for key, entry in sorted(
            _REGISTRY.items(), key=lambda kv: repr(kv[0])
        )
    ]
    kv_entries = [
        {"key": _key_to_json(key), **entry} for key, entry in sorted(
            _KV_REGISTRY.items(), key=lambda kv: repr(kv[0])
        )
    ]
    prefix_entries = [
        {"key": _key_to_json(key), **entry} for key, entry in sorted(
            _PREFIX_REGISTRY.items(), key=lambda kv: repr(kv[0])
        )
    ]
    spec_entries = [
        {"key": _key_to_json(key), **entry} for key, entry in sorted(
            _SPEC_REGISTRY.items(), key=lambda kv: repr(kv[0])
        )
    ]
    # platform-keyed (not shape/env-keyed): the host link is hardware
    swap_entries = [
        {"platform": platform, **entry}
        for platform, entry in sorted(_SWAP_REGISTRY.items())
    ]
    tmp = path + ".tmp"
    dirpath = os.path.dirname(path)
    if dirpath:
        os.makedirs(dirpath, exist_ok=True)
    with open(tmp, "w") as fh:
        # version stays 1: kv_entries / prefix_entries / spec_entries /
        # swap_entries are additive and readers written before them simply
        # ignore the keys
        json.dump(
            {"version": 1, "entries": entries, "kv_entries": kv_entries,
             "prefix_entries": prefix_entries, "spec_entries": spec_entries,
             "swap_entries": swap_entries},
            fh, indent=2,
        )
    os.replace(tmp, path)


def load_registry(path: str) -> int:
    """Merge a persisted artifact into the process registry; returns the
    number of entries loaded. Unparseable files load zero entries rather
    than raising (a corrupt cache must degrade to re-measurement, not take
    serving down)."""
    _FILE_LOADED.add(path)
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return 0
    if not isinstance(data, dict):
        return 0
    loaded = 0
    for field, dest, value_key, choices in (
        ("entries", _REGISTRY, "boundary", PHASE_CHOICES),
        ("kv_entries", _KV_REGISTRY, "kv_layout", KV_LAYOUT_CHOICES),
        ("prefix_entries", _PREFIX_REGISTRY, "prefix_cache", PREFIX_CACHE_CHOICES),
        ("spec_entries", _SPEC_REGISTRY, "speculation", SPECULATION_CHOICES),
    ):
        entries = data.get(field)
        if not isinstance(entries, list):
            continue
        for item in entries:
            if not isinstance(item, dict):
                continue
            try:
                key = _key_from_json(item["key"])
                entry = {k: v for k, v in item.items() if k != "key"}
                if entry.get(value_key) not in choices:
                    continue
            except (KeyError, ValueError, SyntaxError, TypeError):
                continue
            dest[key] = entry
            loaded += 1
    swap_items = data.get("swap_entries")
    if isinstance(swap_items, list):
        for item in swap_items:
            if not isinstance(item, dict):
                continue
            platform = item.get("platform")
            gbps = item.get("swap_gbps")
            if not isinstance(platform, str) or \
                    not isinstance(gbps, (int, float)) or not gbps > 0:
                continue
            _SWAP_REGISTRY[platform] = {
                k: v for k, v in item.items() if k != "platform"
            }
            loaded += 1
    return loaded


def resolve(
    mode: Union[None, str, DecodeStrategy],
    model=None,
    *,
    platform: Optional[str] = None,
) -> DecodeStrategy:
    """Resolve a strategy request into a concrete :class:`DecodeStrategy`.

    Order: an explicit :class:`DecodeStrategy` wins; an explicit mode
    string next; then :data:`ENV_VAR`; then ``"auto"``. ``"auto"`` means
    "use the measured winner for this shape/platform/env when one exists,
    else keep the cached default" — so an untuned process behaves exactly
    like the pre-strategy code.
    """
    if isinstance(mode, DecodeStrategy):
        return mode
    if mode is None:
        mode = os.environ.get(ENV_VAR) or "auto"
    if mode not in MODES:
        raise ValueError(
            f"decode strategy must be one of {MODES} (or a DecodeStrategy), "
            f"got {mode!r}"
        )
    if mode == "auto":
        measured = lookup(model, platform) if model is not None else None
        return DecodeStrategy(boundary=measured or "cached")
    return DecodeStrategy(boundary=mode)


#: package-level export name (``resolve`` is ambiguous outside this module)
resolve_decode_strategy = resolve


def autotune_boundary(
    model,
    params,
    *,
    batch: int = 1,
    new_tokens: int = 4,
    clock: Callable[[], float] = time.perf_counter,
    persist: Optional[str] = None,
    force: bool = False,
) -> str:
    """Measure cached vs recompute boundary-phase decoding at the bound
    shape and memoize the winner; returns ``"cached"`` or ``"recompute"``.

    The probe pins every generated token into the boundary phase (latents
    start maxed, the prompt fills the window minus ``new_tokens`` — the
    ``decode_scaling.py`` recipe), runs each implementation once to compile
    and once timed on ``clock``, and records both per-token times. Ties
    (including the all-zero durations an un-advanced
    :class:`~perceiver_io_tpu.reliability.FakeClock` produces) break toward
    ``cached`` — deterministically, so chaos-clock tests replay. A shape
    whose window equals its latent count has no boundary phase at all; the
    verdict is recorded as ``cached`` without measuring.

    :param persist: JSON path — merged before deciding (a persisted verdict
        short-circuits the measurement unless ``force``) and rewritten
        after, so one deployment measures once.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.inference.generate import GenerationConfig, generate

    if persist:
        load_registry(persist)
    _maybe_load_env_file()
    key = registry_key(model)
    if not force and key in _REGISTRY:
        return _REGISTRY[key]["boundary"]

    n = model.max_seq_len
    max_latents = model.max_latents
    boundary_room = n - max_latents  # == max_prefix_len for this family
    if boundary_room < 1:
        record(model, "cached", note="no boundary phase at this shape")
        if persist:
            save_registry(persist)
        return "cached"
    new_tokens = max(1, min(new_tokens, boundary_room))
    prompt_len = n - new_tokens
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(1, model.config.vocab_size, size=(batch, prompt_len),
                     dtype=np.int32)
    )
    # latents start maxed: every generated token migrates the boundary
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=max_latents)

    timings = {}
    for mode in PHASE_CHOICES:
        ids = generate(model, params, prompt, gcfg, decode_strategy=mode)
        int(np.asarray(jax.device_get(ids))[0, -1])  # compile + fence
        t0 = clock()
        ids = generate(model, params, prompt, gcfg, decode_strategy=mode)
        int(np.asarray(jax.device_get(ids))[0, -1])
        timings[mode] = (clock() - t0) / new_tokens * 1e3
    winner = "cached" if timings["cached"] <= timings["recompute"] else "recompute"
    record(
        model, winner,
        cached_ms_per_token=round(timings["cached"], 4),
        recompute_ms_per_token=round(timings["recompute"], 4),
        batch=batch, new_tokens=new_tokens,
    )
    if persist:
        save_registry(persist)
    return winner


def resolve_kv_layout(
    mode: Optional[str],
    model=None,
    *,
    platform: Optional[str] = None,
) -> str:
    """Resolve a slot-engine KV-layout request into one of
    :data:`KV_LAYOUT_CHOICES` (``"dense"``, ``"paged"``, ``"paged_int8"``).

    Order mirrors :func:`resolve`: explicit mode > :data:`ENV_KV_LAYOUT` >
    ``"auto"`` (registry lookup, falling back to ``dense`` — the
    status-quo layout — when nothing has been measured). ``paged_int8``
    only wins a lookup when the autotuner's quality gate passed at record
    time (:func:`autotune_kv_layout`); an explicit request is taken at
    face value — the operator owns the quality tradeoff then.
    """
    if mode is None:
        mode = os.environ.get(ENV_KV_LAYOUT) or "auto"
    if mode not in KV_LAYOUTS:
        raise ValueError(
            f"kv layout must be one of {KV_LAYOUTS}, got {mode!r}"
        )
    if mode == "auto":
        measured = lookup_kv_layout(model, platform) if model is not None else None
        return measured or "dense"
    return mode


def _kv_probe_workload(model, slots: int, new_tokens: int):
    """The shared KV-probe geometry (autotune + quality gate): mid-context
    prompts — the paged gather's cost scales with the context, so probing
    at a trivial length would flatter the paged arm — and an EOS-free
    greedy config, so retirement is purely by count and every arm runs
    the identical schedule regardless of token divergence."""
    import numpy as np

    from perceiver_io_tpu.inference.generate import GenerationConfig
    from perceiver_io_tpu.serving import BucketTable

    n = model.max_seq_len
    num_latents = min(2, model.max_latents)
    prompt_len = max(num_latents, min(n // 2, model.max_prefix_len + num_latents))
    new_tokens = max(1, min(new_tokens, n - prompt_len))
    table = BucketTable(prompt_lens=(prompt_len,), batch_sizes=(1,))
    gcfg = GenerationConfig(max_new_tokens=new_tokens, num_latents=num_latents)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, model.config.vocab_size, size=prompt_len, dtype=np.int32)
        for _ in range(slots)
    ]
    return table, gcfg, prompts, new_tokens


def quant_quality_probe(
    model,
    params,
    *,
    slots: int = 2,
    block_size: int = 16,
    new_tokens: int = 8,
    budget: Optional[float] = None,
) -> dict:
    """Measure the int8 layout's greedy fidelity against the exact paged
    layout at the bound shape — the *quality gate* the autotuner applies
    before it will select ``paged_int8``.

    Drives one exact-paged and one int8-paged engine in LOCKSTEP over the
    shared probe workload (EOS-free, so both schedules are identical by
    construction) and after every step compares the per-slot logits of
    slots active in BOTH engines (idle-slot logits are garbage and
    excluded). Returns::

        {"max_logit_delta": float,   # worst |exact - int8| logit, any step
         "token_match_rate": float,  # greedy tokens identical across arms
         "budget": float,            # the gate threshold applied
         "passed": bool}             # max_logit_delta <= budget

    The verdict rides in the registry entry (``quant_gate``) so serving
    warmup can report a failed gate through ``kv_quant_fallback_total``.
    """
    import numpy as np

    from perceiver_io_tpu.serving.slots import SlotServingEngine

    budget = kv_quant_budget() if budget is None else float(budget)
    table, gcfg, prompts, _ = _kv_probe_workload(model, slots, new_tokens)

    engines, reqs = {}, {}
    for layout in PAGED_KV_LAYOUTS:
        eng = SlotServingEngine(
            model, params, gcfg, table, slots=slots, kv_layout=layout,
            kv_block_size=block_size,
        )
        engines[layout] = eng
        reqs[layout] = [eng.submit(p) for p in prompts]
    exact, quant = engines["paged"], engines["paged_int8"]
    max_delta = 0.0
    while exact.pending() or quant.pending():
        if exact.pending():
            exact.step()
        if quant.pending():
            quant.step()
        live = [
            i for i, (se, sq) in enumerate(zip(exact._slots, quant._slots))
            if se is not None and sq is not None
        ]
        if live:
            le = np.asarray(exact._state["logits"])[live]
            lq = np.asarray(quant._state["logits"])[live]
            max_delta = max(max_delta, float(np.max(np.abs(le - lq))))
    matched = total = 0
    for r_exact, r_quant in zip(reqs["paged"], reqs["paged_int8"]):
        te, tq = list(r_exact.result), list(r_quant.result)
        total += max(len(te), len(tq))
        matched += sum(1 for a, b in zip(te, tq) if a == b)
    return {
        "max_logit_delta": round(max_delta, 6),
        "token_match_rate": round(matched / max(total, 1), 4),
        "budget": budget,
        "passed": bool(max_delta <= budget),
    }


def autotune_kv_layout(
    model,
    params,
    *,
    slots: int = 2,
    block_size: int = 16,
    new_tokens: int = 8,
    clock: Callable[[], float] = time.perf_counter,
    persist: Optional[str] = None,
    force: bool = False,
) -> str:
    """Measure dense vs block-paged vs int8-paged slot decoding at the
    bound shape and memoize the winner; returns one of
    :data:`KV_LAYOUT_CHOICES`.

    The probe drives a tiny :class:`~perceiver_io_tpu.serving.slots.
    SlotServingEngine` per layout (same prompts, same schedule, greedy):
    one pass to compile, one timed pass, per-token ms on ``clock``. Ties —
    including the all-zero durations an un-advanced FakeClock produces —
    break toward ``dense`` (the status-quo layout), deterministically,
    and toward exact ``paged`` over ``paged_int8``. The int8 arm is
    additionally **quality-gated**: :func:`quant_quality_probe` must
    measure a greedy logit delta within :func:`kv_quant_budget`, else the
    autotuner falls back to exact ``paged`` no matter the timing (the
    gate verdict is recorded either way, as ``quant_gate``).
    Note the tradeoff being measured is TIME at equal capacity; the paged
    layouts' admission win (more residents per HBM byte, and more again
    for int8's quarter-size entries) is a capacity property, a count this
    measurement does not make — an operator who
    sizes ``kv_blocks`` below dense capacity has already chosen paged and
    should pass it explicitly.

    :param persist: JSON path — merged before deciding (a persisted verdict
        short-circuits the measurement unless ``force``) and rewritten
        after, sharing the boundary registry's artifact file.
    """
    import jax
    import numpy as np

    from perceiver_io_tpu.serving.slots import SlotServingEngine

    if persist:
        load_registry(persist)
    _maybe_load_env_file()
    key = registry_key(model)
    if not force and key in _KV_REGISTRY:
        return _KV_REGISTRY[key]["kv_layout"]

    table, gcfg, prompts, new_tokens = _kv_probe_workload(model, slots, new_tokens)

    timings = {}
    for layout in KV_LAYOUT_CHOICES:
        # explicit pool sizing implies a paged layout (the engine rejects
        # sizing a dense pool), so only those arms get block_size
        kv_kwargs = (
            {"kv_block_size": block_size} if layout in PAGED_KV_LAYOUTS else {}
        )

        def make():
            return SlotServingEngine(
                model, params, gcfg, table, slots=slots, kv_layout=layout,
                **kv_kwargs,
            )

        compile_engine = make()
        compile_engine.serve(prompts)  # pays the per-layout executor builds
        engine = make()
        for p in prompts:
            engine.submit(p)
        t0 = clock()
        engine.run_until_idle()
        timings[layout] = (clock() - t0) / (slots * new_tokens) * 1e3
    quality = quant_quality_probe(
        model, params, slots=slots, block_size=block_size,
        new_tokens=new_tokens,
    )
    winner = "dense" if timings["dense"] <= timings["paged"] else "paged"
    if (
        winner == "paged"
        and quality["passed"]
        and timings["paged_int8"] < timings["paged"]
    ):
        winner = "paged_int8"
    record_kv_layout(
        model, winner,
        dense_ms_per_token=round(timings["dense"], 4),
        paged_ms_per_token=round(timings["paged"], 4),
        paged_int8_ms_per_token=round(timings["paged_int8"], 4),
        quant_gate=quality,
        slots=slots, block_size=block_size, new_tokens=new_tokens,
    )
    if persist:
        save_registry(persist)
    return winner


#: acceptance-rate floor below which the speculation autotuner declines no
#: matter the timing: at acceptance a, a k-token round emits ~1 + a·k
#: tokens, so below ~0.5 the verify work is mostly thrown away and the
#: measured "win" is noise at probe scale. Deterministic gate (a rate, not
#: a clock), so FakeClock runs decline reproducibly.
DEFAULT_SPEC_ACCEPT_FLOOR = 0.5


def autotune_speculation(
    model,
    params,
    *,
    slots: int = 2,
    new_tokens: int = 8,
    candidates: tuple = ("k4d1",),
    accept_floor: float = DEFAULT_SPEC_ACCEPT_FLOOR,
    clock: Callable[[], float] = time.perf_counter,
    persist: Optional[str] = None,
    force: bool = False,
) -> str:
    """Measure self-draft speculation against the plain one-token step at
    the bound shape and memoize the winner; returns one of
    :data:`SPECULATION_CHOICES`.

    The probe drives a tiny :class:`~perceiver_io_tpu.serving.slots.
    SlotServingEngine` per arm over the shared KV-probe workload (same
    prompts, greedy, EOS-free — and speculation is token-identical by
    construction, so every arm emits the identical schedule): one pass to
    compile, one timed pass, per-token ms on ``clock``. A speculative arm
    must clear TWO gates to win: its measured acceptance rate must reach
    ``accept_floor`` (the deterministic decline — drafts the model keeps
    rejecting can never pay), and its per-token time must beat ``"off"``
    strictly. Ties — including the all-zero durations an un-advanced
    FakeClock produces — break toward ``"off"``, the status-quo step.
    Candidates whose draft depth is not a strict truncation of the bound
    model's stack are skipped (a full-depth "draft" is just the model).

    :param persist: JSON path — merged before deciding (a persisted verdict
        short-circuits the measurement unless ``force``) and rewritten
        after, sharing the boundary registry's artifact file.
    """
    from perceiver_io_tpu.serving.slots import SlotServingEngine

    if persist:
        load_registry(persist)
    _maybe_load_env_file()
    key = registry_key(model)
    if not force and key in _SPEC_REGISTRY:
        return _SPEC_REGISTRY[key]["speculation"]

    num_layers = int(model.config.num_self_attention_layers)
    arms = ["off"]
    skipped = []
    for cand in candidates:
        if cand not in SPECULATION_CHOICES or cand == "off":
            raise ValueError(
                f"candidates must come from {SPECULATION_CHOICES[1:]}, "
                f"got {cand!r}"
            )
        draft_layers = int(cand.split("d")[1])
        (arms if draft_layers < num_layers else skipped).append(cand)

    table, gcfg, prompts, new_tokens = _kv_probe_workload(model, slots, new_tokens)

    timings, acceptance = {}, {}
    for arm in arms:
        def make():
            return SlotServingEngine(
                model, params, gcfg, table, slots=slots, speculation=arm,
            )

        compile_engine = make()
        compile_engine.serve(prompts)  # pays the per-arm executor builds
        engine = make()
        for p in prompts:
            engine.submit(p)
        t0 = clock()
        engine.run_until_idle()
        timings[arm] = (clock() - t0) / (slots * new_tokens) * 1e3
        if arm != "off":
            acceptance[arm] = engine.stats()["speculation"]["acceptance_rate"]

    winner = "off"
    for arm in arms[1:]:
        if acceptance[arm] < accept_floor:
            continue  # the deterministic decline: drafting isn't landing
        if timings[arm] >= timings[winner if winner != "off" else "off"]:
            continue
        winner = arm
    record_speculation(
        model, winner,
        timings_ms_per_token={a: round(t, 4) for a, t in timings.items()},
        acceptance={a: round(r, 4) for a, r in acceptance.items()},
        accept_floor=accept_floor, skipped=skipped,
        slots=slots, new_tokens=new_tokens,
    )
    if persist:
        save_registry(persist)
    return winner


def main(argv=None) -> dict:
    """``make decode-tune``: run the autotune probe on a CLM shape, on the
    backend ``JAX_PLATFORMS`` selects, and print the verdict + measurements
    (with the platform they were taken on) as one JSON line."""
    import argparse

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--ctx", type=int, default=512)
    p.add_argument("--num-latents", type=int, default=64)
    p.add_argument("--num-channels", type=int, default=64)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--new-tokens", type=int, default=4)
    p.add_argument("--out", default=None,
                   help="persist the registry JSON artifact here")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.models.text.clm import (
        CausalLanguageModel,
        CausalLanguageModelConfig,
    )

    cfg = CausalLanguageModelConfig(
        vocab_size=262,
        max_seq_len=args.ctx,
        max_latents=args.num_latents,
        num_channels=args.num_channels,
        num_heads=args.num_heads,
        num_self_attention_layers=args.num_layers,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, args.ctx), jnp.int32),
        args.ctx - args.num_latents,
    )["params"]
    winner = autotune_boundary(
        model, params, batch=args.batch, new_tokens=args.new_tokens,
        persist=args.out, force=True,
    )
    entry = dict(_REGISTRY[registry_key(model)])
    out = {
        "boundary": winner,
        "platform": jax.default_backend(),
        "shape": list(shape_key(model)),
        **entry,
    }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()

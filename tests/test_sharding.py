"""Sharded serving runtime: the slot engine compiled over the parallelism
mesh (docs/serving.md "Sharded serving"; ``serving/sharding.py``,
``parallel/mesh.py``, ``parallel/partition.py``, ``serving/slots.py``).

The load-bearing assertions:

- a degenerate **1-device mesh reproduces the unsharded engine exactly**:
  token streams equal AND the final persistent slot state byte-identical
  (the standing exactness discipline — opting into the mesh layer must
  cost nothing when the mesh is trivial);
- greedy output on a **multi-device CPU mesh** (the 8-virtual-device
  backend ``conftest.py`` forces via ``XLA_FLAGS``) is **token-identical**
  to the unsharded engine across dense, paged, chunked-prefill, and
  prefix-shared admission geometries — GSPMD partitions the computation,
  it must not change it;
- mesh geometry is **executor identity**: a mesh flip rebuilds (cache
  miss) and the compile ledger attributes the retrace to ``mesh``; the
  same geometry re-resolves to a cache HIT, the compile-count bound is
  the unsharded engine's, and steady-state sharded traffic retraces
  nothing;
- the pool stays **zero-leak** under sharded cancellation and evacuation
  (mid-admission, resident, queued), same bar as the unsharded drills;
- replicas claim **disjoint device subsets** (``device_slice`` /
  ``fleet_mesh_specs``) and an over-subscribed fleet fails at
  construction, not by aliasing devices silently;
- the ``serving_mesh_*`` gauges, per-shard resident bytes, stats/health
  surfaces, and the ``obs report`` "sharded serving" section (fixture-
  pinned) expose the geometry.

All pure-CPU, tiny shapes — tier-1 (marker ``sharded``).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    executor_cache_stats,
    reset_executor_caches,
)
from perceiver_io_tpu.inference.samplers import SamplingConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.observability import report as report_mod
from perceiver_io_tpu.observability.ledger import default_ledger
from perceiver_io_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_MODEL,
    MeshConfig,
    device_slice,
    make_mesh,
    single_device_mesh,
)
from perceiver_io_tpu.parallel.partition import serving_state_spec
from perceiver_io_tpu.serving import (
    BucketTable,
    MeshGroupAllocator,
    ServingMeshSpec,
    ServingSharding,
    SlotServingEngine,
    fleet_mesh_specs,
)
from perceiver_io_tpu.serving.sharding import as_serving_sharding

pytestmark = [pytest.mark.sharded, pytest.mark.timeout(600)]

KEY = jax.random.PRNGKey(0)

# Deliberately NOT a shape another test module uses (executor cache keys
# include the model fingerprint; an identically-configured model elsewhere
# would pre-populate the caches this file's engines build and count).
TINY = dict(
    vocab_size=89, max_seq_len=32, max_latents=8, num_channels=16,
    num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
)
GREEDY = SamplingConfig(temperature=0.0)
TABLE = BucketTable(prompt_lens=(8, 16), batch_sizes=(1,))

#: 2 data x 2 model = 4 of the 8 virtual CPU devices; slots=2 divides
#: data, heads=2 divides model
MESH = ServingMeshSpec(data=2, model=2)


def _gcfg(max_new=6, num_latents=2):
    return GenerationConfig(
        max_new_tokens=max_new, num_latents=num_latents, sampling=GREEDY
    )


@pytest.fixture(scope="module")
def tiny_model():
    cfg = CausalLanguageModelConfig(**TINY)
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    return model, params


def _prompts(seed, lengths, vocab=89):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lengths]


def _state_bytes(state):
    """{leaf path: raw bytes} for a slot-state tree — the byte-identity pin."""
    return {
        jax.tree_util.keystr(path): np.asarray(leaf).tobytes()
        for path, leaf in jax.tree_util.tree_leaves_with_path(state)
    }


# -- device-subset plumbing (parallel/mesh.py) ------------------------------
def test_device_slice_and_single_device_mesh_subsets(devices):
    """Replicas claim disjoint contiguous subsets; the slice validates its
    bounds so an over-subscribed fleet fails at construction."""
    assert device_slice(4) == devices[:4]
    assert device_slice(2, offset=4) == devices[4:6]
    assert device_slice(2, offset=1, devices=devices[:4]) == devices[1:3]
    with pytest.raises(ValueError, match="overruns"):
        device_slice(4, offset=6)
    with pytest.raises(ValueError, match="count must be >= 1"):
        device_slice(0)
    with pytest.raises(ValueError, match="offset must be >= 0"):
        device_slice(1, offset=-1)
    # single_device_mesh(index=): the size-1 form of "use this subset"
    m0, m3 = single_device_mesh(), single_device_mesh(index=3)
    assert list(m0.devices.flat) == [devices[0]]
    assert list(m3.devices.flat) == [devices[3]]
    # explicit device argument still wins
    assert list(single_device_mesh(devices[5]).devices.flat) == [devices[5]]


def test_fleet_mesh_specs_disjoint_and_budget(devices):
    """fleet_mesh_specs hands replica i the offset i*M group and rejects a
    fleet that cannot fit; the MeshGroupAllocator reclaims a released
    replica's group before wrapping."""
    specs = fleet_mesh_specs(MESH, 2)
    assert [s.device_offset for s in specs] == [0, 4]
    resolved = [s.resolve() for s in specs]
    claimed = [list(r.mesh.devices.flat) for r in resolved]
    assert claimed[0] == devices[:4] and claimed[1] == devices[4:8]
    with pytest.raises(ValueError, match="overruns"):
        fleet_mesh_specs(MESH, 3)
    with pytest.raises(ValueError, match="replicas must be >= 1"):
        fleet_mesh_specs(MESH, 0)
    # the allocator form: two live claims fill the 8-device budget...
    alloc = MeshGroupAllocator(MESH)
    a, b = alloc.acquire(), alloc.acquire()
    assert [s.spec.device_offset for s in (a, b)] == [0, 4]
    # ...a crash rebuild RECLAIMS the crashed group (the fleet releases the
    # dead engine — and with it the ServingSharding claim — before the
    # factory re-runs), instead of aliasing the live replica's devices
    del b
    c = alloc.acquire()
    assert c.spec.device_offset == 4
    # only a genuinely over-subscribed fleet wraps (documented: CPU-virtual
    # devices alias harmlessly; size real pods to max_replicas x devices)
    d = alloc.acquire()
    assert d.spec.device_offset in (0, 4)
    # explicit release (what Replica.restart calls): deterministic, no gc
    alloc2 = MeshGroupAllocator(MESH)
    a2, b2 = alloc2.acquire(), alloc2.acquire()
    a2.release()
    a2.release()  # idempotent
    assert alloc2.acquire().spec.device_offset == 0
    assert b2.spec.device_offset == 4  # the live claim was untouched
    # spec validation
    with pytest.raises(ValueError, match="axis sizes must be >= 1"):
        ServingMeshSpec(data=0, model=2)
    with pytest.raises(ValueError, match="device_offset must be >= 0"):
        ServingMeshSpec(device_offset=-1)


def test_serving_state_rules(devices):
    """The serving rule set (parallel/partition.py): heads along model,
    slots along data, the pool's token dimension deliberately UNsharded
    (block tables address one shared pool); non-divisible dims and unknown
    names fall back to replication."""
    mesh = make_mesh(
        MeshConfig(data=2, fsdp=1, model=2, seq=1), devices=devices[:4]
    )
    # flat pool: shared across slots, heads sharded
    assert serving_state_spec("pool_k", (64, 2, 8), mesh) == P(None, AXIS_MODEL, None)
    assert serving_state_spec("pool_v", (64, 2, 8), mesh) == P(None, AXIS_MODEL, None)
    # dense per-slot caches: slots x heads
    assert serving_state_spec("cross_k", (2, 2, 32, 8), mesh) == P(
        AXIS_DATA, AXIS_MODEL, None, None
    )
    # latent-stack tuple entries match through their path suffix
    assert serving_state_spec("stack_k/0", (2, 2, 8, 8), mesh) == P(
        AXIS_DATA, AXIS_MODEL, None, None
    )
    # batch-1 staging caches: heads only (batch dim of 1 cannot shard)
    assert serving_state_spec("stage_k", (1, 2, 32, 8), mesh) == P(
        None, AXIS_MODEL, None, None
    )
    # per-slot rows and vectors
    assert serving_state_spec("window", (2, 32), mesh) == P(AXIS_DATA, None)
    assert serving_state_spec("table", (2, 9), mesh) == P(AXIS_DATA, None)
    assert serving_state_spec("length", (2,), mesh) == P(AXIS_DATA)
    # non-divisible dims replicate (3 slots over data=2; 3 heads over model=2)
    assert serving_state_spec("cross_k", (3, 2, 32, 8), mesh) == P(
        None, AXIS_MODEL, None, None
    )
    assert serving_state_spec("pool_k", (64, 3, 8), mesh) == P(None, None, None)
    # unknown leaves replicate — the safe default
    assert serving_state_spec("mystery", (4, 4), mesh) == P()


def test_as_serving_sharding_coercion(devices):
    """The engine's mesh= argument: None/resolved pass through, a 4-axis
    training mesh is accepted only with fsdp/seq at 1, junk is rejected."""
    assert as_serving_sharding(None) is None
    resolved = MESH.resolve()
    assert as_serving_sharding(resolved) is resolved
    assert isinstance(resolved, ServingSharding)
    assert resolved.fingerprint()[0] == "mesh"
    # training-mesh reuse: data x model with fsdp/seq at 1
    train_mesh = make_mesh(
        MeshConfig(data=2, fsdp=1, model=2, seq=1), devices=devices[:4]
    )
    coerced = as_serving_sharding(train_mesh)
    assert (coerced.data_size, coerced.model_size) == (2, 2)
    fsdp_mesh = make_mesh(
        MeshConfig(data=1, fsdp=2, model=2, seq=1), devices=devices[:4]
    )
    with pytest.raises(ValueError, match="no optimizer state"):
        as_serving_sharding(fsdp_mesh)
    with pytest.raises(TypeError, match="mesh must be"):
        as_serving_sharding("2x2")
    # same geometry on DISJOINT device groups -> different executor identity
    a, b = (s.resolve() for s in fleet_mesh_specs(MESH, 2))
    assert a.fingerprint() != b.fingerprint()
    assert a.describe() != b.describe()


# -- divisibility validation ------------------------------------------------
def test_divisibility_validation(tiny_model):
    """slots must divide the data axis and heads the model axis — loudly at
    construction (and resize), not as a silent replication downgrade of
    the dimension the mesh exists to shard."""
    model, params = tiny_model
    with pytest.raises(ValueError, match="slots .3. must divide"):
        SlotServingEngine(
            model, params, _gcfg(), TABLE, slots=3, mesh=MESH
        )
    with pytest.raises(ValueError, match="heads .2. must divide"):
        SlotServingEngine(
            model, params, _gcfg(), TABLE, slots=4,
            mesh=ServingMeshSpec(data=1, model=4),
        )
    engine = SlotServingEngine(model, params, _gcfg(), TABLE, slots=2, mesh=MESH)
    with pytest.raises(ValueError, match="must divide evenly"):
        engine.resize_slots(3)


# -- exactness: 1-device mesh byte identity ---------------------------------
def test_one_device_mesh_byte_identity(tiny_model):
    """A degenerate 1x1 mesh must reproduce the unsharded engine EXACTLY:
    same token streams and a byte-identical final slot state — the mesh
    layer's no-op case costs nothing and changes nothing."""
    model, params = tiny_model
    cfg = _gcfg()
    prompts = _prompts(0, [3, 11, 8, 5])
    ref = SlotServingEngine(model, params, cfg, TABLE, slots=2)
    one = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, mesh=ServingMeshSpec(data=1, model=1)
    )
    outs_ref, outs_one = ref.serve(prompts), one.serve(prompts)
    for a, b in zip(outs_ref, outs_one):
        np.testing.assert_array_equal(a, b)
    ref_bytes, one_bytes = _state_bytes(ref._state), _state_bytes(one._state)
    assert ref_bytes.keys() == one_bytes.keys()
    mismatched = [k for k in ref_bytes if ref_bytes[k] != one_bytes[k]]
    assert not mismatched, f"state leaves diverged on the 1x1 mesh: {mismatched}"
    assert one.stats()["mesh"] == {
        "data": 1, "model": 1, "devices": 1, "spec": "1x1@1dev+0"
    }


# -- exactness: multi-device token identity ---------------------------------
@pytest.mark.parametrize("engine_kwargs", [
    {},
    {"kv_layout": "paged", "kv_block_size": 4},
    {"prefill_chunk": 8},
    {"kv_layout": "paged", "kv_block_size": 4, "prefill_chunk": 8},
], ids=["dense", "paged", "chunked", "paged_chunked"])
def test_sharded_parity_token_identity(tiny_model, engine_kwargs):
    """Greedy output on the 2x2 mesh is token-identical to the unsharded
    engine with mid-flight admits through recycled slots (5 ragged requests
    over 2 slots) across dense / paged / chunked-prefill geometries. GSPMD
    may reorder the o-projection partial sums but greedy argmax decisions
    must not move."""
    model, params = tiny_model
    cfg = _gcfg(max_new=8)
    prompts = _prompts(1, [3, 11, 8, 3, 11])
    ref = SlotServingEngine(model, params, cfg, TABLE, slots=2, **engine_kwargs)
    outs_ref = ref.serve(prompts)
    eng = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, mesh=MESH, **engine_kwargs
    )
    outs = eng.serve(prompts)
    for a, b in zip(outs_ref, outs):
        np.testing.assert_array_equal(a, b)
    stats = eng.stats()
    assert stats["completed"] == len(prompts)
    assert stats["mesh"]["devices"] == 4
    assert eng.health()["mesh"] == eng.sharding.describe()
    # geometry gauges (docs/observability.md): how `obs report` and the
    # Prometheus surface see the mesh
    assert eng.registry.gauge("serving_mesh_devices") == 4
    assert eng.registry.gauge("serving_mesh_data") == 2
    assert eng.registry.gauge("serving_mesh_model") == 2
    if "kv_layout" in engine_kwargs:
        assert eng._pool.in_use == 0 and eng._pool.leaked() == 0
        # per-model-shard slice of the live KV bytes
        resident = eng.registry.gauge("kv_cache_resident_bytes")
        assert (
            eng.registry.gauge("kv_cache_resident_bytes_per_shard")
            == resident // 2
        )


def test_sharded_parity_prefix_shared(tiny_model):
    """Prefix-shared admissions (hot prefix mapped by reference, COW on
    divergence) stay token-identical on the mesh — the shared-prefill
    executor's pool gather is head-sharded through gather_constraint and
    must not move any argmax."""
    model, params = tiny_model
    cfg = _gcfg(max_new=6)
    rng = np.random.default_rng(2)
    prefix = rng.integers(1, 89, size=8).astype(np.int32)
    prompts = [
        np.concatenate([prefix, rng.integers(1, 89, size=int(n)).astype(np.int32)])
        for n in (3, 5, 7, 3)
    ]
    kwargs = dict(
        kv_layout="paged", kv_block_size=4, prefill_chunk=8, prefix_cache="on",
    )
    ref = SlotServingEngine(model, params, cfg, TABLE, slots=2, **kwargs)
    outs_ref = ref.serve(prompts)
    assert ref.registry.counter("kv_prefix_hits_total") > 0  # sharing was live
    eng = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, mesh=MESH, **kwargs
    )
    outs = eng.serve(prompts)
    for a, b in zip(outs_ref, outs):
        np.testing.assert_array_equal(a, b)
    assert eng.registry.counter("kv_prefix_hits_total") == ref.registry.counter(
        "kv_prefix_hits_total"
    )
    # published prefix blocks stay mapped for future admissions (cached, not
    # leaked); the refcount-aware leak check is the zero-leak bar
    assert eng._pool.leaked() == 0
    assert eng._pool.in_use == eng.registry.gauge("kv_prefix_cached_blocks")


# -- executor identity: compile bound, cache keys, ledger attribution -------
def test_compile_bound_and_zero_steady_state_retrace(tiny_model):
    """The sharded engine's warmup compiles exactly the unsharded bound
    (one prefill per bucket + decode + boundary variant) and mixed traffic
    afterwards retraces NOTHING — sharding changes executor identity, not
    executor count."""
    model, params = tiny_model
    cfg = _gcfg(max_new=6)
    reset_executor_caches()
    engine = SlotServingEngine(model, params, cfg, TABLE, slots=2, mesh=MESH)
    compiled = engine.warmup()
    assert compiled == len(TABLE.prompt_lens) + 2
    before = executor_cache_stats()["misses"]
    prompts = _prompts(3, [3, 4, 5, 8, 12, 16, 9])
    for i, p in enumerate(prompts):
        engine.submit(p, config=dataclasses.replace(cfg, max_new_tokens=2 + i % 3))
    engine.run_until_idle()
    assert executor_cache_stats()["misses"] == before  # zero retraces
    assert engine.stats()["completed"] == len(prompts)


def test_mesh_in_cache_key_and_ledger_attribution(tiny_model):
    """Mesh geometry is part of executor identity: flipping the mesh on an
    otherwise-identical engine REBUILDS every executor and the compile
    ledger attributes the retrace to ``mesh``; resolving the SAME geometry
    again hits the cache (zero fresh builds)."""
    model, params = tiny_model
    cfg = _gcfg(max_new=4)
    reset_executor_caches()
    default_ledger().reset()

    unsharded = SlotServingEngine(model, params, cfg, TABLE, slots=2)
    base = unsharded.warmup()
    assert base == len(TABLE.prompt_lens) + 2
    # the mesh fingerprint reaches the cache key; the ledger component is
    # the human-readable geometry
    sharded = SlotServingEngine(model, params, cfg, TABLE, slots=2, mesh=MESH)
    key = sharded._cache_key("slot_decode")
    fp = sharded.sharding.fingerprint()
    assert all(part in key for part in fp)  # fingerprint splats into the key
    assert key != unsharded._cache_key("slot_decode")
    rebuilt = sharded.warmup()
    assert rebuilt == base  # full rebuild, same bound
    reasons = default_ledger().snapshot()["retrace_reasons"]
    assert reasons.get("mesh", 0) > 0
    assert (
        default_ledger().registry.counter("retrace_reason_mesh_total")
        == reasons["mesh"]
    )
    mesh_components = {
        rec["components"].get("mesh")
        for rec in default_ledger().records()
        if rec["components"].get("mesh")
    }
    assert mesh_components == {sharded.sharding.describe()}
    # same geometry -> same identity -> cache HIT on a fresh engine
    before = executor_cache_stats()["misses"]
    again = SlotServingEngine(model, params, cfg, TABLE, slots=2, mesh=MESH)
    assert again.warmup() == 0
    assert executor_cache_stats()["misses"] == before
    # disjoint device subset, same axis sizes -> different identity: the
    # other replica's executor (devices baked into its shardings) must not
    # be reused
    other = fleet_mesh_specs(MESH, 2)[1]
    assert (
        SlotServingEngine(
            model, params, cfg, TABLE, slots=2, mesh=other
        )._cache_key("slot_decode")
        != again._cache_key("slot_decode")
    )


# -- zero-leak under sharded cancellation/evacuation ------------------------
def test_sharded_cancel_and_evacuate_zero_leak(tiny_model):
    """Token-granular cancellation and scale-down evacuation on the mesh
    return every pool page at the instant (mapped + reserved, tagged by
    cause) — the unsharded zero-leak bar, unchanged by sharding."""
    model, params = tiny_model
    cfg = _gcfg(max_new=8)
    engine = SlotServingEngine(
        model, params, cfg, TABLE, slots=2, mesh=MESH,
        kv_layout="paged", kv_block_size=4,
    )
    reqs = [engine.submit(p) for p in _prompts(4, [5, 9, 7, 6])]
    for _ in range(3):
        engine.step()
    # a resident mid-generation cancel frees its slot and pages NOW
    resident = [entry.req for entry in engine._active()]
    assert resident
    assert engine.cancel(resident[0].request_id)
    assert resident[0].status == "cancelled"
    # evacuation retires everything else (residents + queued), cause-tagged
    engine.evacuate(cause="scale_down")
    pool = engine._pool
    assert pool.in_use == 0 and pool.reserved == 0 and pool.leaked() == 0
    causes = pool.stats()["frees_by_cause"]
    assert causes.get("cancelled", 0) > 0
    statuses = {r.status for r in reqs}
    assert statuses == {"cancelled"}
    # the engine still serves after the drill — fresh traffic, same mesh
    outs = engine.serve(_prompts(5, [4, 8]))
    assert all(len(np.asarray(o)) for o in outs)
    assert pool.in_use == 0 and pool.leaked() == 0


# -- observability: report section ------------------------------------------
def test_report_sharding_section_fixture_pinned():
    """The checked-in fixture snapshot renders the "sharded serving"
    section (mesh shape, per-shard bytes, mesh-attributed retraces) and a
    mesh-less run renders NO such section — pre-mesh artifacts unchanged."""
    text = report_mod.run(
        "tests/fixtures/events.jsonl", "tests/fixtures/metrics_snapshot.json"
    )
    assert "== sharded serving ==" in text
    assert "mesh: 2x2 over 4 devices" in text
    assert "1,536 B per model shard" in text
    assert re.search(r"mesh-attributed retraces: 1\b", text)
    assert "ledger meshes: 2x2@4dev+0" in text
    analysis = report_mod.analyze([], {
        "gauges": {
            "serving_mesh_devices": 4, "serving_mesh_data": 2,
            "serving_mesh_model": 2, "kv_cache_resident_bytes": 2048,
            "kv_cache_resident_bytes_per_shard": 1024,
        },
        "counters": {},
    })
    assert analysis["sharding"]["per_shard_resident_bytes"] == 1024
    assert analysis["sharding"]["mesh_retraces"] is None
    # unsharded artifacts: no gauges -> no section
    empty = report_mod.analyze([], {})
    assert empty["sharding"] is None
    assert "== sharded serving ==" not in report_mod.format_report(empty)


def test_fleet_crash_rebuild_reclaims_crashed_group(tiny_model):
    """A sharded 2-replica fleet through one MeshGroupAllocator-backed
    factory: a replica crash releases the dead engine BEFORE the factory
    re-runs, so the rebuild reclaims the CRASHED group — it must not alias
    the healthy replica's devices while the freed group sits idle."""
    from perceiver_io_tpu.reliability import ChaosRegistry
    from perceiver_io_tpu.serving import FleetRouter

    model, params = tiny_model
    cfg = _gcfg(max_new=6)
    alloc = MeshGroupAllocator(MESH)  # two 4-device groups over 8 devices

    def factory():
        return SlotServingEngine(
            model, params, cfg, TABLE, slots=2, mesh=alloc.acquire()
        )

    chaos = ChaosRegistry()
    chaos.crash_replica(0, 2)
    fleet = FleetRouter([factory, factory], chaos=chaos)
    assert [r.engine.sharding.spec.device_offset for r in fleet.replicas] == [0, 4]
    reqs = [fleet.submit(p) for p in _prompts(6, [5, 9, 7, 6])]
    fleet.run_until_idle()
    assert [r.status for r in reqs] == ["ok"] * len(reqs)
    assert fleet.stats()["replica_restarts"] == 1
    # the rebuilt replica 0 re-claimed the crashed group at offset 0 —
    # live replicas stay on disjoint device subsets
    groups = [
        {d.id for d in r.engine.sharding.mesh.devices.flat}
        for r in fleet.replicas
    ]
    assert [r.engine.sharding.spec.device_offset for r in fleet.replicas] == [0, 4]
    assert groups[0].isdisjoint(groups[1])


def test_mesh_metric_families_have_help(tiny_model):
    """Every serving_mesh_*/per-shard family published by a sharded engine
    carries a direct HELP entry and exports through the Prometheus text
    surface (docs/observability.md "Sharded-serving metric families")."""
    from perceiver_io_tpu.observability.exporters import HELP_TEXT, to_prometheus_text

    model, params = tiny_model
    engine = SlotServingEngine(
        model, params, _gcfg(), TABLE, slots=2, mesh=MESH,
        kv_layout="paged", kv_block_size=4,
    )
    snap = engine.registry.snapshot()
    published = [
        n for n in snap["gauges"]
        if n.startswith("serving_mesh_") or n.endswith("_per_shard")
    ]
    assert sorted(published) == [
        "kv_cache_resident_bytes_per_shard", "serving_mesh_data",
        "serving_mesh_devices", "serving_mesh_model",
    ]
    missing = [n for n in published if n not in HELP_TEXT]
    assert not missing, f"families without a direct HELP entry: {missing}"
    text = to_prometheus_text(engine.registry)
    for name in published:
        assert f"# HELP {name} " in text


# -- CLI flag group ---------------------------------------------------------
def test_serve_cli_mesh_flag_group(tmp_path):
    """`clm serve --serve.mesh.*` builds the sharded slot engine with
    completions identical to the unsharded run; the flag group rejects the
    bucket engine and an over-subscribed fleet loudly."""
    from perceiver_io_tpu.scripts.text import clm as clm_script
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=8, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(cfg)
    params = model.init(KEY, jnp.zeros((1, 32), jnp.int32), 8)["params"]
    save_pretrained(str(tmp_path / "ckpt"), params, cfg)
    (tmp_path / "prompts.txt").write_text("hello\nhi\n")

    common = [
        "serve", "--ckpt", str(tmp_path / "ckpt"),
        f"--serve.prompts={tmp_path}/prompts.txt",
        "--serve.max_new_tokens=3", "--serve.num_latents=2",
        "--serve.prompt_buckets=16", "--serve.warmup=false",
        "--serve.engine=slots", "--serve.slots=2",
    ]
    plain = clm_script.main(common)
    sharded = clm_script.main(
        common + ["--serve.mesh.data=2", "--serve.mesh.model=2"]
    )
    assert [r["completion"] for r in sharded] == [r["completion"] for r in plain]
    assert all(r["status"] == "ok" for r in sharded)
    with pytest.raises(SystemExit, match="applies to --serve.engine=slots"):
        clm_script.main([
            a for a in common if not a.startswith(("--serve.engine", "--serve.slots"))
        ] + ["--serve.engine=bucket", "--serve.mesh.model=2"])
    with pytest.raises(SystemExit, match="overruns"):
        clm_script.main(common + [
            "--serve.mesh.data=2", "--serve.mesh.model=2", "--serve.replicas=3",
        ])


# -- the module's own probe -------------------------------------------------
@pytest.mark.slow  # compiles its own probe model
def test_shard_probe_main_records(capsys):
    """The self-contained sharded-serving probe (``python -m
    perceiver_io_tpu.serving.sharding``) emits one JSON record with the
    A/B-able fields: mesh geometry, tokens/s, per-shard resident bytes,
    and the token streams (two runs of one workload can be compared)."""
    import json

    from perceiver_io_tpu.serving.sharding import _probe_main

    assert _probe_main([
        "--data", "2", "--model", "2", "--slots", "2",
        "--requests", "4", "--new-tokens", "4", "--kv-layout", "paged",
    ]) == 0
    record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert record["mesh"] == {"data": 2, "model": 2}
    assert record["kv_layout"] == "paged"
    assert record["tokens_per_s"] > 0
    assert record["compile_count"] > 0
    assert len(record["tokens"]) == 4 and all(record["tokens"])
    assert record["per_shard_resident_bytes"] * 2 <= record["resident_bytes"] + 1

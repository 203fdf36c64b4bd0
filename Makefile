# Task runner (parity with the reference's invoke tasks, reference tasks.py:1-101).
PY ?= python

.PHONY: test test-fast chaos fleet-chaos elasticity elasticity-bench obs obs-report incident timeline slo slo-bench gateway stream-bench decode-strategy decode-tune cov bench serve-bench paged-bench quant-kv quant-bench prefix-cache prefix-bench preemption preempt-bench swap swap-bench speculative spec-bench dryrun lint

test:
	$(PY) -m pytest tests/ -q

test-fast:
	$(PY) -m pytest tests/ -q -m "not slow"

# deterministic fault-injection suite (docs/reliability.md) — CPU-fast,
# also included in the tier-1 "not slow" run
chaos:
	$(PY) -m pytest tests/ -q -m chaos --continue-on-collection-errors

# supervised serving-fleet suite (docs/serving.md): replica failover,
# circuit breakers, exactly-once recovery drills — CPU-fast, also tier-1
fleet-chaos:
	$(PY) -m pytest tests/ -q -m fleet --continue-on-collection-errors

# fleet-elasticity suite (docs/serving.md "Elasticity"): burn-rate
# autoscaler ladder drills, zero-downtime scale-down with exactly-once
# replay, spike-arrival loadgen, healthz-stays-ready pins — CPU-fast,
# also tier-1, per-test timeout budget via the conftest SIGALRM guard
elasticity:
	$(PY) -m pytest tests/ -q -m elasticity --continue-on-collection-errors

# flash-crowd elasticity A/B at the reduced drill shape (docs/serving.md
# "Elasticity"): the same deterministic FakeClock spike offered to a
# static fleet and an autoscaled one — goodput-under-SLO both ways, the
# scale-event timeline, zero-drop / token-identity / pool zero-leak pins
elasticity-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	model = CausalLanguageModel(cfg); \
	params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params']; \
	print(json.dumps({'elasticity': bench._bench_elasticity(model, params, cfg)}, indent=2))"

# unified telemetry layer suite (docs/observability.md) — CPU-fast,
# also included in the tier-1 "not slow" run
obs:
	$(PY) -m pytest tests/ -q -m observability --continue-on-collection-errors

# offline `obs report` analyzer over the checked-in fixture artifacts
# (docs/observability.md): per-phase latency, worst-request waterfall,
# compile/memory ledger table, padding waste — no dashboard, no live run
obs-report:
	$(PY) -m perceiver_io_tpu.observability.report tests/fixtures/events.jsonl \
		--snapshot tests/fixtures/metrics_snapshot.json

# incident flight-recorder suite (docs/observability.md "Flight recorder
# & incident bundles"): trace-sampling determinism + tail-keep, triggered
# bundle drills (cooldown/budget), the FakeClock chaos acceptance drill,
# and the `obs incident` analyzer — then the analyzer over the checked-in
# fixture bundle. CPU-fast, also tier-1.
incident:
	$(PY) -m pytest tests/test_flight_recorder.py -q -m flight_recorder
	$(PY) -m perceiver_io_tpu.observability.report --incident tests/fixtures/incident

# scheduler flight-deck suite (docs/observability.md "Scheduler timeline &
# post-mortems"): timeline ring + JSONL export, timeline<->span join, the
# exact TTFT/ITL telescoping bar, Chrome-trace schema, preemption
# post-mortems, per-tenant/per-tier attribution — then the `obs timeline`
# analyzer over the checked-in fixture (regenerate it with
# tests/fixtures/timeline/generate.py). CPU-fast, also tier-1.
timeline:
	$(PY) -m pytest tests/test_timeline.py -q -m timeline
	$(PY) -m perceiver_io_tpu.observability.report \
		--timeline tests/fixtures/timeline/timeline.jsonl \
		tests/fixtures/timeline/events.jsonl

# SLO telemetry suite (docs/observability.md): burn-rate monitor drills,
# load-generator determinism, TTFT/ITL accounting, fleet admission
# tightening — CPU-fast, also tier-1
slo:
	$(PY) -m pytest tests/ -q -m slo --continue-on-collection-errors

# goodput-under-SLO sweep at the reduced drill shape (docs/observability.md):
# offered-load sweep through the slot engine via the Poisson load generator,
# printing p95 TTFT / p95 inter-token latency per point and the knee
slo-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	model = CausalLanguageModel(cfg); \
	params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params']; \
	print(json.dumps({'slo_goodput': bench._bench_slo_goodput(model, params, cfg)}, indent=2))"

# HTTP/SSE streaming-gateway suite (docs/serving.md "Streaming"): token
# streaming over real sockets, client-disconnect cancellation, zero
# slot/page leak, socket-anchored TTFT — CPU-fast, also tier-1
gateway:
	$(PY) -m pytest tests/ -q -m gateway --continue-on-collection-errors

# mid-stream mass-abandonment drill at the reduced drill shape
# (docs/serving.md "Streaming"): scripted client abandonment against the
# paged slot engine under FakeClock — cancelled-slot reclaim latency,
# pool-page zero-leak, survivor token-identity
stream-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	model = CausalLanguageModel(cfg); \
	params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params']; \
	print(json.dumps({'streaming': bench._bench_streaming(model, params, cfg)}, indent=2))"

# decode-strategy suite (per-phase cached-vs-recompute + chunked prefill;
# docs/serving.md, docs/benchmarks.md) — CPU-fast, also tier-1
decode-strategy:
	$(PY) -m pytest tests/ -q -m decode_strategy --continue-on-collection-errors

# boundary-phase autotune probe on CPU: measures cached vs recompute at a
# small shape and prints the chosen strategy (persist with --out; the serve
# CLI's --serve.decode_strategy=auto warmup runs the same probe at the
# deployed shape)
decode-tune:
	$(PY) -m perceiver_io_tpu.inference.decode_strategy --ctx 512 --num-latents 64 --num-channels 64 --num-layers 2

cov:
	$(PY) -m pytest tests/ -q --cov=perceiver_io_tpu --cov-report=term-missing

bench:
	$(PY) bench.py

# slots-vs-bucket serving A/B at the reduced drill shape (docs/serving.md):
# mixed prompt lengths + heterogeneous max_new_tokens through both engines,
# printing the tokens/s ratio, slot occupancy, and padding-waste split
serve-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	from perceiver_io_tpu.inference import cast_float_params; \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	model = CausalLanguageModel(cfg); \
	params = cast_float_params(model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params'], jnp.bfloat16); \
	print(json.dumps({'serve_ab': bench._bench_serve_ab(model, params, cfg)}, indent=2))"

# dense-vs-paged KV layout A/B at the reduced drill shape (docs/serving.md
# "Block-paged KV"): a long-tail mixed-context workload through both slot
# layouts at ONE simulated HBM budget, printing max concurrent residents,
# the ratio, tokens/s, and the pool's page-utilization stats
paged-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	model = CausalLanguageModel(cfg); \
	params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params']; \
	print(json.dumps({'paged_kv': bench._bench_paged_kv(model, params, cfg)}, indent=2))"

# quantized-KV suite (docs/serving.md "Quantized KV"): int8 pool + scale
# scatter/gather units, greedy parity vs the exact paged layout, quality-
# gated autotune/persistence, ragged-kernel interpreter parity — CPU-fast,
# also tier-1, per-test timeout budget via the conftest SIGALRM guard
quant-kv:
	$(PY) -m pytest tests/ -q -m quant_kv --continue-on-collection-errors

# exact-vs-int8 paged-KV A/B at the reduced drill shape (docs/serving.md
# "Quantized KV"): ONE simulated HBM budget, residents-per-HBM-byte
# ratio, tokens/s, greedy token-match rate, quality-gate verdict
quant-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	model = CausalLanguageModel(cfg); \
	params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params']; \
	print(json.dumps({'quant_kv': bench._bench_quant_kv(model, params, cfg)}, indent=2))"

# cross-request prefix-sharing suite (docs/serving.md "Prefix sharing"):
# COW/refcount allocator drills, radix-index units, greedy token-identity
# across hot/partial/divergent/chunked/cancel/failover geometries, LRU
# eviction under pool pressure — CPU-fast, also tier-1, per-test timeout
# budget via the conftest SIGALRM guard
prefix-cache:
	$(PY) -m pytest tests/ -q -m prefix_cache --continue-on-collection-errors

# prefix-sharing A/B at the reduced drill shape (docs/serving.md "Prefix
# sharing"): Zipf-distributed shared prefixes through the paged slot
# engine, unshared vs COW-shared at ONE simulated HBM budget — TTFT
# p50/p95 ratio, residents-per-HBM-byte, hit ratio, token identity
prefix-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	model = CausalLanguageModel(cfg); \
	params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params']; \
	print(json.dumps({'prefix_cache': bench._bench_prefix_cache(model, params, cfg)}, indent=2))"

# preemption suite (docs/serving.md "Preemption & priorities"): lazy-
# admission allocator units, token-identity through preempt/requeue/
# readmit cycles across dense/paged/int8/prefix-shared/chunked
# geometries, priority-tier + tenant victim selection, kv.exhaust chaos
# zero-leak storm, frees_by_cause completeness — CPU-fast, also tier-1,
# per-test timeout budget via the conftest SIGALRM guard
preemption:
	$(PY) -m pytest tests/ -q -m preemption --continue-on-collection-errors

# strict-vs-optimistic admission A/B at the reduced drill shape
# (docs/serving.md "Preemption & priorities"): long-tail declared-max_new
# workload at ONE simulated HBM budget — max-resident ratio, residents
# per HBM byte, goodput-under-SLO both ways, preemption/readmission
# counts, greedy token-identity pin
preempt-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	model = CausalLanguageModel(cfg); \
	params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params']; \
	print(json.dumps({'preemption': bench._bench_preemption(model, params, cfg)}, indent=2))"

# host-swap suite (docs/serving.md "Host-swap preemption"): extract/
# restore primitive units, token-identity through swap-out/restore across
# paged/int8/prefix-shared/chunked geometries, kv.exhaust zero-leak storm
# under preemption=swap, auto per-victim arbitration honesty, swap_gbps
# calibration + registry persistence — CPU-fast, also tier-1
swap:
	$(PY) -m pytest tests/ -q -m swap --continue-on-collection-errors

# recompute-vs-swap-vs-auto preemption A/B over a generated-length sweep
# at ONE fixed pool budget (docs/serving.md "Host-swap preemption"):
# wall-to-drain + goodput-under-SLO per arm per length, the measured
# crossover length where paying transfer beats paying recompute, greedy
# token-identity vs an unpressured baseline, and the model honesty bars
# (predicted vs realized advantage sign, auto never picks the worse arm).
# The CPU lane runs a REDUCED shape (512 ctx), not DRILL_SHAPE: the pool
# budget is denominated in full-context slots, so at 2048 ctx a sweep
# with genuine exhaustion pressure needs 200+-token decodes per request
# and the recompute arm's replay churn makes the lane hours-scale on
# CPU. At 512 ctx the 1-slot budget is 32 x 16-token blocks, 8
# residents cross it from the FIRST sweep point, and victim replays
# stay cheap — every point preempts for real instead of measuring
# compile noise. On real TPU run _bench_swap at the full shape with
# default kwargs to measure the uncapped crossover (ROADMAP item 2)
swap-bench:
	$(PY) -c "import json, jax, jax.numpy as jnp; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	from perceiver_io_tpu.models.text.clm import CausalLanguageModel; \
	cfg = bench._mk_config((1, 512, 64, 128, 4, 2)); \
	model = CausalLanguageModel(cfg); \
	params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), cfg.max_seq_len - cfg.max_latents)['params']; \
	print(json.dumps({'swap': bench._bench_swap(model, params, cfg, budget_slots=1, n_requests=12, lengths=(24, 64, 128))}, indent=2))"

# speculative-decoding suite (docs/serving.md "Speculative decoding"):
# truncated-stack self-draft + single batched verify — greedy token-
# identity across dense/paged/int8/prefix-shared/chunked/mesh geometries,
# compile-bound +2, burst TTFT/ITL telescoping, ensure_many atomicity,
# kv.exhaust zero-leak, autotune pays/declines pins — CPU-fast, also tier-1
speculative:
	$(PY) -m pytest tests/ -q -m speculative --continue-on-collection-errors

# speculative A/B at the dispatch-bound probe shape (docs/serving.md
# "Speculative decoding"): the same greedy workload with speculation off
# vs a self-draft geometry — tokens/s both ways, acceptance rate, tokens
# per round, token-identity pin, plus the autotune pays/declines verdicts
spec-bench:
	$(PY) -c "import json, jax; \
	jax.config.update('jax_platforms', 'cpu'); \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	cfg = bench._mk_config(bench.DRILL_SHAPE); \
	print(json.dumps({'speculative': bench._bench_speculative(None, None, cfg)}, indent=2))"

# sharded serving-runtime suite (docs/serving.md "Sharded serving"):
# 1-device byte parity, 8-virtual-device token parity across dense/paged/
# chunked/prefix-shared geometries, mesh-keyed executor identity + ledger
# attribution, zero-leak cancel/evacuate drills — CPU-fast, also tier-1
sharded:
	$(PY) -m pytest tests/ -q -m sharded --continue-on-collection-errors

# sharded serving A/B: the self-contained probe subprocessed at 1 device
# vs a 2x4 mesh over 8 virtual CPU devices (XLA_FLAGS-injected) — tokens/s,
# compile counts, per-model-shard resident KV bytes, token-identity pin
shard-bench:
	$(PY) -c "import json; \
	import importlib.util; \
	spec = importlib.util.spec_from_file_location('bench', 'bench.py'); \
	bench = importlib.util.module_from_spec(spec); spec.loader.exec_module(bench); \
	print(json.dumps({'sharded_serving': bench._bench_sharded_serving()}, indent=2))"

dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

lint:
	$(PY) -m compileall -q perceiver_io_tpu tests examples

# Task runner (parity with the reference's invoke tasks, reference tasks.py:1-101).
PY ?= python

.PHONY: test test-fast chaos fleet-chaos elasticity obs obs-report incident timeline slo gateway decode-strategy decode-tune cov quant-kv prefix-cache preemption swap speculative sharded dryrun lint

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

# HTTP/SSE streaming-gateway suite (docs/serving.md "Streaming"): token
# streaming over real sockets, client-disconnect cancellation, zero
# slot/page leak, socket-anchored TTFT — CPU-fast, also tier-1
gateway:
	$(PY) -m pytest tests/ -q -m gateway --continue-on-collection-errors

# decode-strategy suite (per-phase cached-vs-recompute + chunked prefill;
# docs/serving.md) — CPU-fast, also tier-1
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

# quantized-KV suite (docs/serving.md "Quantized KV"): int8 pool + scale
# scatter/gather units, greedy parity vs the exact paged layout, quality-
# gated autotune/persistence, ragged-kernel interpreter parity — CPU-fast,
# also tier-1, per-test timeout budget via the conftest SIGALRM guard
quant-kv:
	$(PY) -m pytest tests/ -q -m quant_kv --continue-on-collection-errors

# cross-request prefix-sharing suite (docs/serving.md "Prefix sharing"):
# COW/refcount allocator drills, radix-index units, greedy token-identity
# across hot/partial/divergent/chunked/cancel/failover geometries, LRU
# eviction under pool pressure — CPU-fast, also tier-1, per-test timeout
# budget via the conftest SIGALRM guard
prefix-cache:
	$(PY) -m pytest tests/ -q -m prefix_cache --continue-on-collection-errors

# preemption suite (docs/serving.md "Preemption & priorities"): lazy-
# admission allocator units, token-identity through preempt/requeue/
# readmit cycles across dense/paged/int8/prefix-shared/chunked
# geometries, priority-tier + tenant victim selection, kv.exhaust chaos
# zero-leak storm, frees_by_cause completeness — CPU-fast, also tier-1,
# per-test timeout budget via the conftest SIGALRM guard
preemption:
	$(PY) -m pytest tests/ -q -m preemption --continue-on-collection-errors

# host-swap suite (docs/serving.md "Host-swap preemption"): extract/
# restore primitive units, token-identity through swap-out/restore across
# paged/int8/prefix-shared/chunked geometries, kv.exhaust zero-leak storm
# under preemption=swap, auto per-victim arbitration honesty, swap_gbps
# calibration + registry persistence — CPU-fast, also tier-1
swap:
	$(PY) -m pytest tests/ -q -m swap --continue-on-collection-errors

# speculative-decoding suite (docs/serving.md "Speculative decoding"):
# truncated-stack self-draft + single batched verify — greedy token-
# identity across dense/paged/int8/prefix-shared/chunked/mesh geometries,
# compile-bound +2, burst TTFT/ITL telescoping, ensure_many atomicity,
# kv.exhaust zero-leak, autotune pays/declines pins — CPU-fast, also tier-1
speculative:
	$(PY) -m pytest tests/ -q -m speculative --continue-on-collection-errors

# sharded serving-runtime suite (docs/serving.md "Sharded serving"):
# 1-device byte parity, 8-virtual-device token parity across dense/paged/
# chunked/prefix-shared geometries, mesh-keyed executor identity + ledger
# attribution, zero-leak cancel/evacuate drills — CPU-fast, also tier-1
sharded:
	$(PY) -m pytest tests/ -q -m sharded --continue-on-collection-errors

dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

lint:
	$(PY) -m compileall -q perceiver_io_tpu tests examples

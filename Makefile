# kaito-tpu build & test surface (counterpart of the reference Makefile
# targets: unit-test, inference-api-e2e, rag-service-test; the
# benchmark is `python kbench/run.py`, see BENCHMARK.json and PERF.md).

PYTHON ?= python

.PHONY: all native unit-test unit-test-fast unit-test-slow rag-test chaos kvq wquant kvpool kvtier lora structured obs devprof slo itl fleet autoscale spec qos asyncloop prefill overlap chip-smoke serve manager epp clean

all: native

native:
	$(MAKE) -C kaito_tpu/native

unit-test:
	$(PYTHON) -m pytest tests/ -q

# tier 1, the selection the check runs: everything but the tests marked
# `slow`, and a test is `slow` only by a mark written beside it with
# its reason (its seconds, what the check's machine lacks, or the
# failure it keeps the mark for).  The engine's own tests are in it.
# About ten minutes on six workers (`-n 6 --dist loadfile`), several
# times that on one.
unit-test-fast:
	$(PYTHON) -m pytest tests/ -q -m "not slow"

unit-test-slow:
	$(PYTHON) -m pytest tests/ -q -m "slow"

rag-test:
	$(PYTHON) -m pytest tests/test_rag.py -q

# fault-injection suite (docs/failure-domains.md): registry/router
# chaos and the compile-heavy engine containment tests (all of it is in
# tier 1 too), then the flight recorder's fatal-path legs
chaos:
	$(PYTHON) -m pytest tests/test_failpoints.py -q
	$(PYTHON) -m pytest tests/test_itl_slo.py -q -m "not slow" \
	  -k "flight or fatal"

# int8 KV-cache suite (docs/kv-cache.md): quantization round trips,
# kernel dequant parity, P/D scale wire format, golden-pinned int8
# serving on the committed real checkpoints
kvq:
	$(PYTHON) -m pytest tests/test_kv_quant.py -q
	$(PYTHON) -m pytest tests/test_real_checkpoint.py -q -k "kv_int8"

# weight-quant suite (docs/quantization.md): int4 pack/unpack, fused
# kernel parity (interpreter mode), quantize-at-load invariants,
# annotation plumbing, compose leg, golden-pinned int8/int4 serving on
# the committed real checkpoints
wquant:
	$(PYTHON) -m pytest tests/test_weight_quant.py -q
	$(PYTHON) -m pytest tests/test_real_checkpoint.py -q \
	  -k "weight_int4 or int8"

# cluster KV pool suite (docs/kv-pool.md): hash parity, store LRU +
# export TTL GC, EPP index/scoring/headers, publish→fetch→import
# greedy parity, gating invisibility — fast tier; the warm-TTFT-
# survives-scale-out e2e is the slow leg
kvpool:
	$(PYTHON) -m pytest tests/test_kv_pool.py -q -m "not slow"

# KV pool tier-3 suite (docs/kv-pool.md "Tier 3: SSD"): disk slab
# store units (spill/scan/prune/corruption), break-even veto, capped
# advert + EPP merge, session pin routing, annotation plumbing, and
# the multi-turn replay-from-SSD + corrupt-slab-recompute live legs —
# fast tier; the session-pin TTFT e2e is the slow leg
kvtier:
	$(PYTHON) -m pytest tests/test_kv_tier.py -q -m "not slow"

# multi-LoRA suite (docs/multi-lora.md): adapter-cache refusals +
# LRU/pinning/host tier, heterogeneous-batch greedy equivalence,
# zero-retrace pin, int8-KV x spec compose, hash-chain isolation,
# /v1/adapters + tenant mapping, annotation render/plan validation,
# EPP affinity scoring — fast tier; the hot-load-then-affinity-routes
# e2e over two real engines is the slow leg
lora:
	$(PYTHON) -m pytest tests/test_multi_lora.py -q -m "not slow"

# grammar-constrained decoding suite (docs/structured-output.md):
# schema/regex -> token-mask compilation, cache/table, always-valid
# output across greedy/sampled x ngram/draft spec x async dispatch,
# all-ones-mask bit-equivalence, response_format + tools API surface,
# streaming tool_calls deltas, gated metrics + fleet fold, annotation
# render/plan validation
structured:
	$(PYTHON) -m pytest tests/test_grammar.py -q -m "not slow"

# observability suite (docs/observability.md): tracing, flight
# recorder, router metrics, exposition-format invariants, control-plane
# metrics/Events, and the SLO watchdog — fast tier only (the slow e2e
# legs run under unit-test / unit-test-slow)
obs:
	$(PYTHON) -m pytest tests/test_tracing.py tests/test_metrics_format.py \
	  tests/test_slo.py tests/test_itl_slo.py tests/test_controllers.py \
	  tests/test_fleet.py tests/test_devprof.py \
	  tests/test_comm_overlap.py tests/test_kv_tier.py -q -m "not slow"

# device-time attribution suite (docs/observability.md "Device-time
# attribution"): bucket classifier, XPlane wire + chrome-trace parsers,
# buckets+idle==100 invariant, cross-track overlap %, phase markers,
# gated-off exposition pin, fleet fold, annotation render/plan
# validation, AND the live CPU-smoke leg: a sampled window against a
# real engine process (buckets sum to 100, >90% phase attribution,
# /debug/device vs /metrics agreement, 403 when off)
devprof:
	$(PYTHON) -m pytest tests/test_devprof.py -q

# collective-compute overlap suite (docs/multichip.md): ring/reference
# parity, prefetch bitwise pin, annotation plumbing, the engine gate and
# the greedy A-B legs on the default 8-device virtual mesh, then the
# TP=2 A-B smoke once more on a 4-device one
overlap:
	$(PYTHON) -m pytest tests/test_comm_overlap.py -q
	XLA_FLAGS=--xla_force_host_platform_device_count=4 $(PYTHON) -m pytest \
	  "tests/test_comm_overlap.py::test_tp_greedy_bit_equivalent_on_vs_off[2]" \
	  tests/test_comm_overlap.py::test_gate_off_byte_identical_exposition -q

# SLO watchdog suite alone (docs/observability.md "Control plane")
slo:
	$(PYTHON) -m pytest tests/test_slo.py -q

# per-token ITL attribution + incident flight recorder
# (docs/observability.md "Per-token ITL attribution"): watchdog itl_p99
# burn/warn/page, engine emit-funnel stamps across decode modes, flight
# bundle schema/LRU/endpoints, fleet folds + FlightRecorded Event,
# annotation render/plan validation, live gated-on/off server legs —
# fast tier; the decode-stall page-and-record e2e is the slow leg
itl:
	$(PYTHON) -m pytest tests/test_itl_slo.py -q -m "not slow"

# fleet telemetry plane (docs/observability.md "Fleet telemetry"):
# evaluator hysteresis, discovery, fold/gauge round-trips, concurrent
# scraping — fast tier; the two-real-replica scrape e2e is the slow leg
fleet:
	$(PYTHON) -m pytest tests/test_fleet.py -q -m "not slow"

# closed-loop autoscaler (docs/autoscaling.md): policy surface,
# stabilization/cooldown/flap suppression, warm-pool render-ahead +
# GC, EPP drain-before-delete — fast tier; the real-engine
# idle→pressure→scale→zero→wake closed loop is the slow leg
autoscale:
	$(PYTHON) -m pytest tests/test_autoscaler.py -q -m "not slow"

# multi-tenant QoS suite (docs/qos.md): config parsing, weighted-fair
# DRR admission, priority-aware preemption, per-tenant budgets/metric
# slices, EPP scorers, 429-aware fail-over — fast tier; the two-tenant
# overload e2e over real engine processes is the slow leg
qos:
	$(PYTHON) -m pytest tests/test_qos.py -q -m "not slow"

# speculative-decoding suite (docs/speculative.md): n-gram + draft
# model paths — rejection sampler properties, adaptive-depth
# controller, real-checkpoint greedy equivalence, plumbing
spec:
	$(PYTHON) -m pytest tests/test_speculative.py tests/test_spec_draft.py -q

# two-deep decode dispatch loop (docs/decode-loop.md; the default on an
# accelerator, off on the CPU backend these tests run on): the
# sustained-admission suite and the older one, then the fused-decode
# engine tier once more with KAITO_ASYNC_DISPATCH=1 (engines built with
# the default config resolve the env gate), so the path a chip serves
# through is exercised here too
asyncloop:
	$(PYTHON) -m pytest tests/test_decode_pipeline.py \
	  tests/test_async_dispatch.py -q
	KAITO_ASYNC_DISPATCH=1 $(PYTHON) -m pytest \
	  tests/test_async_dispatch.py tests/test_decode_run_ahead.py -q

# prefill scheduling (docs/prefill.md): the serial turn, chunked
# prefill through the engine, the flash kernel's parity
prefill:
	$(PYTHON) -m pytest tests/test_scheduler.py \
	  tests/test_chunked_prefill.py tests/test_flash_prefill.py -q

# the real server at a real model's widths on the chip (one chip
# process at a time; needs a TPU).  Rehearse on the CPU first:
#   JAX_PLATFORMS=cpu python chip_smoke.py --model tiny-llama-test \
#     --expect-platform cpu
chip-smoke:
	$(PYTHON) chip_smoke.py

serve:
	$(PYTHON) -m kaito_tpu.engine.server --model $${MODEL:-tiny-llama-test}

manager:
	$(PYTHON) -m kaito_tpu.controllers.manager

# first-party endpoint picker (docs/routing.md): the scored routing
# front the InferencePool extensionRef resolves to. BACKENDS is a
# space-separated list of url[=role[/group]] replica specs.
BACKENDS ?= http://127.0.0.1:5001
epp:
	$(PYTHON) -m kaito_tpu.runtime.epp $(foreach b,$(BACKENDS),--backend $(b))

docker-engine:
	docker build -f docker/engine/Dockerfile -t ghcr.io/kaito-tpu/engine:latest .

docker-manager:
	docker build -f docker/manager/Dockerfile -t ghcr.io/kaito-tpu/manager:latest .

clean:
	$(MAKE) -C kaito_tpu/native clean
	rm -rf .jax_cache

# Repo-root convenience targets. The real build logic lives in
# ddt_tpu/native/Makefile (C++ kernels + sanitizer builds); these wrap the
# day-to-day workflows so they are one short command from the repo root.

PY ?= python

# Static analysis gate (docs/ANALYSIS.md): exit 1 on any finding not in
# the ratchet baseline. Same check tier-1 runs via tests/test_lint.py.
lint:
	$(PY) -m tools.ddtlint ddt_tpu/ tests/

# Regenerate the ratchet baseline. Only after confirming every new entry
# is a deliberate, documented exception — the baseline should only shrink.
lint-baseline:
	$(PY) -m tools.ddtlint ddt_tpu/ tests/ --write-baseline

# ddtlint v2 smoke (docs/ANALYSIS.md): seed every ISSUE-13 hazard
# (lock inversion, cross-role write, blocking-under-gate, leaked
# acquire, hand-built spec, literal axis, uncovered layout operand,
# stale annotation) into copies of the REAL serve/backends modules and
# drive the CLI end-to-end (--format json), asserting each fires.
lint-smoke:
	$(PY) scripts/lint_smoke.py

# Mechanized TSan suppression audit (ddt_tpu/native/Makefile tsan-audit):
# soak with process-wide suppressions dropped, shape-check the survivors.
tsan-audit:
	$(PY) -m tools.ddtlint.tsan_audit --run

# Tier-1 test suite (CPU backend; the ROADMAP.md verify command).
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# Telemetry smoke (docs/OBSERVABILITY.md): train 2 rounds on synthetic
# data with a run log in a tmpdir, then render it via `cli report` —
# the round trip the tier-1 suite also asserts (tests/test_telemetry.py).
report:
	JAX_PLATFORMS=cpu $(PY) scripts/telemetry_smoke.py

# Flight-recorder smoke (docs/OBSERVABILITY.md): 2-round 2-partition CPU
# mesh train -> per-host log merge -> Perfetto trace export -> parse.
trace-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/trace_smoke.py

# xprof capture-window smoke (docs/OBSERVABILITY.md): 2-round CPU train
# with a programmatic jax.profiler window over rounds 1:2; asserts the
# trace lands and the manifest carries the run-id cross-reference.
profile-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/profile_smoke.py

# Training-kernel smoke (docs/PERF.md "Training kernel"): 2 fused rounds
# through the VMEM-streaming Pallas histogram (interpret mode) with
# sibling subtraction on; asserts fused/granular parity and the
# ddt:fused_round / ddt:hist:{stream,flush,subtract} spans.
kernel-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/kernel_smoke.py

# Chaos smoke (docs/ROBUSTNESS.md): small CPU run under a multi-fault
# plan — torn checkpoint write (digest-detected, history fallback),
# injected stream-read IOErrors (retry seam), injected straggler
# (watchdog detection) — asserting the recovered ensemble is
# BIT-IDENTICAL to an undisturbed run and the run log tells the story.
# Arm 4 (ISSUE 15): a real `cli serve` subprocess SIGKILLed mid-storm
# and restarted on the same port, with every client recovering.
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/chaos_smoke.py

# Serving-tier smoke (docs/SERVING.md): tiny model behind the HTTP
# front end on CPU — 100 concurrent requests with a mid-flight hot
# swap (zero failures, old-or-new responses only), admission
# coalescing witnessed, serve_latency SLO event lands in the run log
# and renders through `cli report`. Fleet arm (ISSUE 15): 3 registry
# models of mixed tiers behind one engine, LRU eviction + reload
# mid-storm, 0 steady-state jit compiles, `report fleet` rollup, and
# saturated single-model p99 within 1.5x of the plain engine.
serve-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/serve_smoke.py

# Billion-row-shape smoke (docs/PERF.md "2D sharding"): host-sharded
# streamed training at a scaled-down out-of-core config — each "host"
# reads only its own chunk sub-shards, flat per-host peak RSS asserted
# against the run log's host_peak_rss_bytes counter, and streamed ==
# in-memory split agreement checked.
bigdata-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/bigdata_smoke.py

# Registry smoke (docs/REGISTRY.md): train -> CLI push -> COLD-process
# restore through the zero-retrace AOT loader -> serve -> bit-match vs
# the exporting process, with the jit_compiles counter witnessing zero
# compiles during serving; the run log's registry section renders the
# push/load provenance via `cli report`.
registry-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/registry_smoke.py

# Training-ops-plane smoke (docs/OBSERVABILITY.md "The training
# operations plane"): a real `cli train --status-port` subprocess is
# scraped twice MID-RUN over a live socket (strictly advancing round
# counter, /metrics round-tripped through telemetry/exposition.py),
# `report progress` renders its heartbeats, and the enabled/disabled
# overhead is measured and bounded at 1.05x.
train-ops-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/train_ops_smoke.py

# Compile-only check (no chip needed): every Pallas kernel and the
# rounds/scoring programs against a described v5e. Before chip time.
aot-check:
	JAX_PLATFORMS=cpu $(PY) scripts/tpu_aot_check.py

# The chip smoke's control flow on a CPU (the real thing, on the chip,
# is `python chip_smoke.py` through the chip tool).
chip-smoke-rehearse:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
		$(PY) chip_smoke.py --rehearse

native:
	$(MAKE) -C ddt_tpu/native

.PHONY: lint lint-baseline lint-smoke tsan-audit test report trace-smoke \
	profile-smoke kernel-smoke chaos-smoke serve-smoke registry-smoke \
	bigdata-smoke train-ops-smoke aot-check \
	chip-smoke-rehearse native

# Convenience targets.  The docker-* targets require docker + compose on
# the host (not available in the build image — run them on a docker-
# capable machine).

.PHONY: test check lint lint-fixtures lint-jaxpr-fixtures trace-smoke pipeline-smoke serve-smoke chaos-smoke online-smoke fleet-smoke mesh-smoke decompose-smoke tune-smoke elle-smoke kernels-smoke obs-fleet-smoke drift-smoke docker-smoke docker-up docker-down

test:
	python -m pytest tests/ -q

# the full local gate: static analysis + unit tests + the
# observability, pipeline, checker-service, slice-dispatch,
# decomposition, auto-tune, transactional-screen, closure/union
# kernel, and drift-sentinel smoke checks.  Performance is measured
# on the chip by perf/run.py (BENCHMARK.json, PERF.md), not here
check: lint test trace-smoke pipeline-smoke serve-smoke chaos-smoke online-smoke fleet-smoke mesh-smoke decompose-smoke tune-smoke elle-smoke kernels-smoke obs-fleet-smoke drift-smoke

# jtlint static analysis (doc/static-analysis.md): all eight passes —
# trace-safety, lock-discipline, concurrency (whole-program race
# inference), obs-hygiene, protocol conformance, seam contracts, and
# dispatch-budget discipline — plus the jaxpr audit, which traces
# every registered kernel across the knob cross-product and certifies
# budget/shape/cache-key contracts against the lowered program
# (incremental: content-hash cached, so a warm run never imports
# jax).  Fails on any finding not in the
# committed baseline (jepsen_tpu/lint/baseline.json — kept EMPTY);
# lint.json / lint.sarif are the machine-readable reports.  The run
# prints its wall-clock and fails if the whole-tree suite exceeds the
# 10 s interactive budget — slow lint stops getting run.
lint:
	@t0=$$(date +%s%N); \
	python -m jepsen_tpu.lint jepsen_tpu/ --json lint.json --sarif lint.sarif || exit $$?; \
	t1=$$(date +%s%N); ms=$$(( (t1 - t0) / 1000000 )); \
	echo "lint wall-clock: $${ms} ms (budget 10000 ms)"; \
	test $${ms} -le 10000 || { echo "lint exceeded the 10 s budget"; exit 1; }

# the lint suite's own fixture corpus (tests/test_lint.py): every rule's
# positive + suppressed snippets, the inference unit tests, and the
# framework/baseline/CLI contract — standalone, no device deps
lint-fixtures:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_lint.py -q -p no:cacheprovider

# the jaxpr-audit rule fixtures: every jaxpr-* rule demonstrably fires
# on a seeded violation (and stays quiet when suppressed), plus the
# incremental-cache round-trip pins (doc/static-analysis.md "jaxpr
# audit")
lint-jaxpr-fixtures:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_lint_jaxpr.py -q -p no:cacheprovider

# run the in-process CLI path with tracing on and fail unless the
# store dir holds a valid Chrome trace + Prometheus dump with phase/op
# spans and engine telemetry (doc/observability.md)
trace-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.obs.smoke

# mixed-length batch through the pipelined checker engine at window
# sizes 1 (serial-equivalent) and 4, both kernel routes; fails on
# verdict divergence or missing pipeline metrics
# (doc/checker-engines.md "engine pipeline")
pipeline-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.engine.smoke

# resident checker daemon (doc/checker-service.md): two concurrent
# client batches on both kernel routes through an in-process daemon;
# fails on verdict divergence vs the in-process engine, missing
# coalescing/warm-hit evidence, an invalid live /metrics exposition,
# or a shutdown that drops in-flight work
serve-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.serve.smoke

# self-chaos gate (doc/checker-service.md "Failure modes & recovery"):
# a daemon subprocess SIGKILLed mid-request and mid-WAL-write, then
# restarted — retried request ids replay the verdict WAL and
# re-dispatch only what the torn line lost, byte-identical to the
# in-process engine on both kernel routes; a stall/drop fault proxy on
# the local HTTP seam — every client call bounded by its deadline
# budget, the circuit breaker trips to in-process and recovers via a
# half-open /healthz probe, and a dropped response's retry is deduped
# by request id (no double counting).  Every injected fault must be
# accounted in client + daemon metrics.
chaos-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.serve.chaos

# fleet-tier gate (doc/checker-service.md "Fleet tier"): two real
# member daemon processes sharing one AOT executable cache, fronted by
# an in-process rendezvous router — routed verdicts byte-identical to
# the in-process engine on both kernel routes, same-shape concurrent
# clients coalesce on ONE member, a SIGKILLed member's in-flight
# request spills to the sibling losing no verdicts, and the revived
# member warms from the shared AOT cache to answer its first request
# with zero cold dispatches (request diag + journal cache=miss scan)
fleet-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.serve.fleet_smoke

# online-checking gate (doc/checker-service.md "Online checking"): a
# batch with injected violations fed incrementally through POST /feed
# against an in-process daemon, a concurrent GET /watch subscriber —
# the violation verdict must reach /watch BEFORE the feed closes, on
# both kernel routes and at op granularity (the interpreter shipper's
# wire shape), with close results byte-identical to the in-process
# batch check and feed/watch telemetry live on /metrics
online-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.serve.online_smoke

# slice-native dispatch gate (doc/checker-engines.md): the production
# check_batch path sharded over a forced 8-virtual-device host mesh on
# both kernel routes + escalation; fails on ANY divergence from the
# single-device result dicts, missing per-device metrics, or a
# per-chip budget breach.  The second line re-runs the untouched
# engine parity suite with the mesh forced on — the same tests that
# pin serial/pipelined equivalence now also pin sharded equivalence.
mesh-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.parallel.smoke
	env JAX_PLATFORMS=cpu JEPSEN_TPU_ENGINE_MESH=1 python -m pytest tests/test_engine.py tests/test_mesh.py -q -p no:cacheprovider

# P-compositionality gate (doc/checker-engines.md "Decomposition
# front-end"): partitionable corpora (multi-register / multi-mutex /
# unordered-queue) through check_batch with decomposition on vs off,
# dense + frontier + oracle-fallback routes, single-device and then
# sharded over the forced 8-virtual-device mesh; fails on any verdict
# divergence, a failing partition left unnamed, missing decomposition
# telemetry, or sub-histories not landing in the dense envelope
decompose-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.engine.decompose_smoke
	env JAX_PLATFORMS=cpu JEPSEN_TPU_ENGINE_MESH=1 python -m jepsen_tpu.engine.decompose_smoke

# auto-tuned dispatch gate (doc/tuning.md): a tiny bounded sweep on
# the CPU fallback, then: artifact round-trips byte-identically,
# corrupt/version-mismatched artifacts fall back to pinned defaults,
# no proposal exceeds the per-chip safe_dispatch budget, and tuned
# dispatch is verdict-byte-identical to untuned across the dense,
# frontier, escalation, decomposed, and service routes
tune-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.tune.smoke

# transactional-screen gate (doc/checker-engines.md "Transactional
# screens"): list-append + rw-register corpora (mixed graph sizes,
# cyclic + acyclic, plain + realtime models) through elle.check_batch
# with device screens forced on vs off, the boolean has-cycle (dense
# closure) route, and per-chip budget accounting through a capped
# resident executor; second line re-runs sharded over the forced
# 8-virtual-device mesh.  Fails on any verdict divergence vs the CPU
# path, missing screen evidence, or a budget breach.
elle-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.elle.smoke
	env JAX_PLATFORMS=cpu JEPSEN_TPU_ENGINE_MESH=1 python -m jepsen_tpu.elle.smoke

# peak-FLOP kernel gate (doc/checker-engines.md "Transactional
# screens"): the plane-packed one-closure screens vs the per-mask
# reference kernels vs the pure-numpy oracle on plain + suffixed
# filter profiles, early-exit vs fixed-round closures on both Elle
# kernel routes, and the matmul subset-union lowering vs gather/unroll
# on the register + queue dense kernels — all byte-identical — plus
# per-chip budget accounting for the packed shapes under a tiny
# dispatch cap; second line re-runs sharded over the forced
# 8-virtual-device mesh.
kernels-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.ops.smoke
	env JAX_PLATFORMS=cpu JEPSEN_TPU_ENGINE_MESH=1 python -m jepsen_tpu.ops.smoke

# fleet-telemetry gate (doc/observability.md "Fleet telemetry"): two
# concurrent service-routed runs through an in-process daemon with a
# dispatch journal; fails on an unstitched trace (missing cross-seam
# flow events or a dead /trace endpoint), a schema-invalid or
# coalescing-blind journal, missing *_rate1m gauges / queue-wait in
# the live exposition, or a broken `top --once` fleet view
obs-fleet-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.obs.fleet_smoke

# drift-sentinel gate (doc/observability.md "Drift sentinel"): a
# synthetic dispatch journal with one shape's execute_s inflated 3×,
# warm-scanned by a resident daemon — the sentinel must flag that
# shape and ONLY that shape (score ~3×, one latched crossing, a
# durable drift-retune marker row), with the drift block visible on
# /status, the status table, top --once, and the jepsen_drift_*
# gauges on a Prometheus-valid /metrics; plus a POST /profile
# round-trip producing a loadable capture manifest
drift-smoke:
	env JAX_PLATFORMS=cpu python -m jepsen_tpu.obs.drift_smoke

# BASELINE config 2: etcd register + partition nemesis over real SSH in
# the dockerized 5-node cluster; artifacts land in docker/smoke-store/.
docker-smoke:
	docker/bin/smoke

docker-up:
	docker/bin/up

docker-down:
	cd docker && docker compose down -v

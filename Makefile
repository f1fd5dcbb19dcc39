# Developer/CI entry points. `make lint` and tests/test_sdlint.py's
# whole-tree gate invoke the same command, so they cannot drift apart.

PY ?= python

.PHONY: lint lint-changed test tier1 trace-smoke slo-smoke profile-smoke \
	debug-bundle bench-devices bench-check bench-warm bench-autotune \
	bench-mesh bench-procs bench-serve bench-semantic bench-scale \
	bench-continuum search-smoke soak-smoke chaos chip-smoke

# set SDLINT_ANNOTATE=1 in CI for GitHub ::error annotations on the diff.
# The selftest proves every rule still fires on its own fixture corpus
# before the (cold, authoritative) whole-tree pass.
lint:
	$(PY) -m tools.sdlint --selftest
	$(PY) -m tools.sdlint spacedrive_tpu --format=json

# developer fast path: re-analyze only changed files + their dependency
# closure (cache under .sdlint_cache/); CI and tier-1 stay on `lint`
lint-changed:
	$(PY) -m tools.sdlint spacedrive_tpu --changed

test: tier1

tier1:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# multi-device leg: forced-8-device parity smoke (the same test tier-1
# runs) + the bench device-count sweep on the virtual host mesh. On a
# real TPU host, drop the XLA_FLAGS/JAX_PLATFORMS overrides to sweep
# the actual chips (docs/performance.md). `make chip-smoke` is the
# on-chip proof the index pass still starts (one process; fails on CPU).
bench-devices:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_sharded_ops.py -q \
		-p no:cacheprovider
	env XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		JAX_PLATFORMS=cpu SD_BENCH_SWEEP=1 SD_BENCH_FILES=512 \
		SD_BENCH_REPEATS=2 $(PY) bench.py

chip-smoke:
	$(PY) chip_smoke.py

# chaos soak: the full fault-injection matrix — the fast deterministic
# subset (also in tier-1) plus the multi-seed slow soak
# (docs/robustness.md). Deterministic per seed; `-m ''` lifts the
# default "not slow" filter so the matrix runs too.
chaos:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py \
		tests/test_resilience.py -q -m '' -p no:cacheprovider

# warm-pass bench: cold index → mutate 1% of files in place → warm
# index on the same node, recording warm files/s, journal hit rate, and
# bytes-hashed into BENCH_E2E (config_warm). CI-safe sizes on the CPU
# platform; on the TPU rig run `python bench_e2e.py` for the full set.
bench-warm:
	env JAX_PLATFORMS=cpu SD_E2E_CONFIGS=warm SD_E2E_FILES=800 \
		SD_E2E_REPEATS=2 $(PY) bench_e2e.py

# closed-loop autotuner A/B: the SAME identifier pass static
# (SD_AUTOTUNE=0) vs adaptive, on a clean link and on one throttled
# deterministically through the fault plane's feeder.fetch stall point.
# Records BENCH_AUTOTUNE.json; `make bench-check` gates it (adaptive
# ≥1.3x static throttled, ≥0.95x static clean). CI-safe sizes on the
# CPU platform; on the TPU rig run `python bench_e2e.py` for the full
# set (autotune rides the default config list).
bench-autotune:
	env JAX_PLATFORMS=cpu SD_E2E_CONFIGS=autotune SD_E2E_FILES=8000 \
		SD_E2E_REPEATS=2 $(PY) bench_e2e.py

# mesh-parallel scaling bench: the SAME corpus identify-distributed by
# the same engine on 1 node vs 2 in-process nodes (loopback duplex,
# real WORK wire + leases + HLC/LWW merge), recording files/s and
# scaling_efficiency into BENCH_E2E (config_mesh); `make bench-check`
# gates the series. In-process peers share a GIL — cross-host peers
# only scale better (note rides the artifact).
bench-mesh:
	env JAX_PLATFORMS=cpu SD_E2E_CONFIGS=mesh SD_E2E_FILES=800 \
		SD_E2E_REPEATS=2 $(PY) bench_e2e.py

# multi-process execution plane A/B: the SAME shard-plane identify
# window with SD_PROCS=0 (golden single-process path) vs a 2-worker
# pool, interleaved arms, recording files/s ratio, per-worker scaling
# efficiency, and the attrib unattributed-gap + profiler gil_wait
# shares before/after into BENCH_PROCS.json; `make bench-check` gates
# bit-identity everywhere and the scaling bars on ≥2-core rigs
# (1-core rigs record the honest floor, like config_mesh).
bench-procs:
	env JAX_PLATFORMS=cpu SD_E2E_CONFIGS=procs SD_E2E_FILES=4000 \
		SD_E2E_REPEATS=3 $(PY) bench_e2e.py

# stage-typed execution continuum A/B: the SAME image corpus runs its
# post-identify stages (thumbnail + embed) through the unified
# scheduler purely local vs across 2 loopback nodes, procpool live in
# BOTH arms, interleaved. Records per-stage files/s, scaling
# efficiency, gap + gil_wait shares, and the live controller outputs
# (per-stage rate EWMAs, lease targets, pool quantum) into
# BENCH_CONTINUUM.json; `make bench-check` gates bit-identity
# everywhere and the efficiency floor on ≥2-core rigs.
bench-continuum:
	env JAX_PLATFORMS=cpu SD_E2E_CONFIGS=continuum SD_E2E_IMAGES=64 \
		SD_E2E_REPEATS=2 $(PY) bench_e2e.py

# semantic-plane bench: cold embed files/s (per-stage clocks, so the
# rest of the media pass doesn't dilute it), the warm journal contract
# (second pass embeds ZERO unchanged files), planted near-duplicate
# rank-1, and top-k query p50/p99 at 10k/100k vectors into
# BENCH_SEMANTIC.json; `make bench-check` re-derives the correctness
# bars (docs/performance.md "Semantic search")
bench-semantic:
	env JAX_PLATFORMS=cpu SD_E2E_CONFIGS=semantic SD_E2E_IMAGES=96 \
		SD_E2E_REPEATS=2 $(PY) bench_e2e.py

# semantic-search smoke: boot the pipeline over a planted-near-dup
# corpus → embed → index → `search.semantic` returns the plant first
# among non-self hits, plus the GET /search route + serve-cache leg
search-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest \
		"tests/test_semantic_search.py::test_pipeline_embeds_searches_and_warm_skips" \
		"tests/test_semantic_search.py::test_get_search_route_and_rspc" \
		-q -p no:cacheprovider

# serving-capacity bench: N simulated HTTP/rspc clients vs one node,
# clean and with the DB throttled through the db.slow fault point,
# recording unloaded/capacity/4x-overload latency + goodput + shed
# stats into BENCH_SERVE.json; `make bench-check` re-derives the
# graceful-degradation bars from the recorded rates
# (docs/robustness.md "Serving under overload").
bench-serve:
	env JAX_PLATFORMS=cpu $(PY) bench_serve.py > /dev/null

# million-file churn soak: sparse corpus + seed-deterministic churn
# (touch/rename/reindex/reads/orphan storms) through the real planes
# while the resource sampler watches RSS/fd/journal growth; writes
# BENCH_SCALE.json, `make bench-check` re-derives the verdict. Full
# lane — budget SD_SOAK_SECONDS (default 120 s at 20k files; raise
# both for the overnight million-file run on a real rig; the trend
# SLOs then gate at the real 64 MB/h / 50 fd/h production bars).
bench-scale:
	env JAX_PLATFORMS=cpu $(PY) bench_scale.py

# soak smoke (tier-1): a compressed bench_scale lane — small corpus,
# accelerated sampler/history cadence, warmup-scaled trend bars — plus
# the planted-leak test proving a breach flips health and captures one
# profile, and the prune/backfill bounded-batch units. The smoke's RSS
# bar is generous by design: a 15 s run extrapolates absurd per-hour
# slopes from JAX/aiohttp warmup allocation; the full `bench-scale`
# lane owns the real bars.
soak-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_soak.py \
		tests/test_resources.py -q -m 'not slow' -p no:cacheprovider

# perf trajectory gate: diff the two most recent BENCH_r*.json rounds
# AND (when BENCH_E2E_prev.json exists) the previous → current
# BENCH_E2E per-config rates incl. the warm-pass metrics; fail on a
# >15% regression in any comparable throughput series. Rides the incremental
# lint path so the repeated local bench loop doesn't pay a cold lint
# every round; CI's `lint` target stays cold and authoritative.
bench-check: lint-changed
	$(PY) tools/bench_compare.py --dir .
	$(PY) tools/check_failures.py

# diff the tier-1 failure *set* (never the count) against
# tests/tier1_known_failures.txt using the log the verify command
# tees to /tmp/_t1.log; soft-skips when no log exists
check-failures:
	$(PY) tools/check_failures.py

# observability smoke: boot a node, index, assert /metrics + /trace +
# debug bundle are live and secret-free
trace-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_observability_smoke.py \
		tests/test_trace.py -q -p no:cacheprovider

# attribution + SLO smoke: boot a node, run a small pass, assert a
# well-formed critical-path report (buckets sum to the window,
# non-empty critical path) and a complete SLO burn-rate evaluation —
# plus the attribution/history/SLO unit tiers
# (docs/observability.md "Attribution, history, and SLOs")
slo-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest \
		"tests/test_observability_smoke.py::test_slo_smoke_attribution_and_slo_surfaces" \
		tests/test_attrib.py tests/test_slo_history.py \
		-q -p no:cacheprovider

# host-profiling smoke: boot a node → small identify pass → non-empty
# folded profile whose named frame groups cover ≥70% of sampled wall →
# gap-decomposed attribution report; plus the sampler/trigger/mesh-pull
# unit tiers (docs/observability.md "Host profiling")
profile-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_profile.py \
		-q -m 'not slow' -p no:cacheprovider

# offline redacted diagnostic bundle (add SDX_URL=http://... for a live
# node's bundle instead)
debug-bundle:
	env JAX_PLATFORMS=cpu $(PY) -m spacedrive_tpu debug-bundle \
		$(if $(SDX_URL),--url $(SDX_URL)) --out debug-bundle.json

# Developer/CI entry points. `make lint` and tests/test_sdlint.py's
# whole-tree gate invoke the same command, so they cannot drift apart.

PY ?= python

.PHONY: lint lint-changed test tier1 trace-smoke slo-smoke profile-smoke \
	debug-bundle bench-scale search-smoke soak-smoke chaos chip-smoke

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

# the on-chip proof the index pass still starts (one process; fails on
# CPU)
chip-smoke:
	$(PY) chip_smoke.py

# chaos soak: the full fault-injection matrix — the fast deterministic
# subset (also in tier-1) plus the multi-seed slow soak
# (docs/robustness.md). Deterministic per seed; `-m ''` lifts the
# default "not slow" filter so the matrix runs too.
chaos:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py \
		tests/test_resilience.py -q -m '' -p no:cacheprovider

# semantic-search smoke: boot the pipeline over a planted-near-dup
# corpus → embed → index → `search.semantic` returns the plant first
# among non-self hits, plus the GET /search route + serve-cache leg
search-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest \
		"tests/test_semantic_search.py::test_pipeline_embeds_searches_and_warm_skips" \
		"tests/test_semantic_search.py::test_get_search_route_and_rspc" \
		-q -p no:cacheprovider

# million-file churn soak: sparse corpus + seed-deterministic churn
# (touch/rename/reindex/reads/orphan storms) through the real planes
# while the resource sampler watches RSS/fd/journal growth; writes
# BENCH_SCALE.json (git-ignored: a run's output, not a record). Full
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

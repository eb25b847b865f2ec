# Single entry point for CI / pre-merge verification — the same target
# .github/workflows/ci.yml runs on every push/PR:
#   [![CI](../../actions/workflows/ci.yml/badge.svg)](../../actions/workflows/ci.yml)
#
#   make verify            — lint + tier-1 tests + bench regression gate
#                            + quick decode benchmark smoke
#   make lint              — ruff check (whole tree) + ruff format --check
#                            (ratchet: FMT_PATHS below grows as files are
#                            touched); skips with a notice when ruff is
#                            not installed (CI installs it)
#   make check-regression  — fresh --quick decode bench vs the committed
#                            BENCH_decode.json via $(REGRESSION_GATE):
#                            absolute wall-clock rows (committing
#                            machine) and/or machine-normalized mode
#                            ratios (CI).  Skips print a loud reason
#                            (::warning:: under GitHub Actions).
#                            Runs BEFORE bench-quick so the comparison
#                            sees the committed baseline (bench-quick
#                            rewrites BENCH_decode.json).
# (ROADMAP.md "Tier-1 verify" is the pytest line below, verbatim.)

PY := PYTHONPATH=src python

# which gates run: `both` locally (absolute wall-clock + mode ratios);
# CI sets `ratio` — the machine-normalized gate needs no cross-machine
# threshold fudge (benchmarks/check_regression.py)
REGRESSION_GATE ?= both
# absolute-gate headroom on the committing machine
REGRESSION_THRESHOLD ?= 1.3
# absolute backstop: all rows uniformly slower than this fails outright
REGRESSION_MAX_SCALE ?= 5.0
# ratio gate: max degradation of a mode-ratio pair vs the baseline
REGRESSION_RATIO_THRESHOLD ?= 2.0

# ruff-format ratchet: files written in ruff-format style since the
# gate landed; extend (after `ruff format <file>`) when touching others
FMT_PATHS := benchmarks/check_regression.py \
             tests/test_check_regression.py

.PHONY: verify test lint check-regression bench-quick bench chaos longctx quant sharded

# bench-quick rewrites BENCH_decode.json, so it must run after the
# regression gate has read the committed baseline — the recipe (not a
# prerequisite list, which `make -j` would parallelize) enforces that
verify: lint test check-regression
	$(MAKE) bench-quick

# the default CI leg (REPRO_ENGINE unset = paged): the serving tests on
# the engine's one path, the paged cache with chunked prefill
test:
	$(PY) -m pytest -x -q

# the paged-chaos CI leg, runnable locally: the whole suite against
# optimistic+swap+sharing with a seeded FaultInjector and per-step
# invariant auditing (tests/conftest.py maps REPRO_ENGINE)
chaos:
	REPRO_ENGINE=paged-chaos $(PY) -m pytest -x -q

# the paged-longctx CI leg, runnable locally: the whole suite against
# the paged stack with split-KV flash-decoding (decode_splits=3 —
# greedy outputs must match the splits=1 legs)
longctx:
	REPRO_ENGINE=paged-longctx $(PY) -m pytest -x -q

# the paged-quant CI leg, runnable locally: the whole suite against
# int8 scale-pool pages (ServeConfig.cache_quant, DESIGN.md
# §page-layouts) layered over the budget-leg stack — sharing, swap
# preemption, chaos, sampled audits, token budget — with per-step
# dynamic split derivation (decode_splits=0)
quant:
	REPRO_ENGINE=paged-quant $(PY) -m pytest -x -q

# the paged-sharded CI leg, runnable locally: the serving suite with
# ServeConfig.shards > 1 on a 4-way forced-host-device data mesh
# (DESIGN.md §sharded-engine) under the chaos stack — greedy outputs
# must match the 1-shard legs token-for-token.  Scoped to the tests
# that route through tests/conftest.py serve_config (the only ones the
# leg changes) plus the sharded router/isolation tests.
sharded:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	REPRO_ENGINE=paged-sharded $(PY) -m pytest -x -q \
		tests/test_serving.py tests/test_preemption.py \
		tests/test_sharded.py

lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check . && ruff format --check $(FMT_PATHS); \
	else \
		echo "lint: ruff not installed; skipping (CI runs it)"; \
	fi

check-regression:
	$(PY) -m benchmarks.check_regression \
		--gate $(REGRESSION_GATE) \
		--threshold $(REGRESSION_THRESHOLD) \
		--max-scale $(REGRESSION_MAX_SCALE) \
		--ratio-threshold $(REGRESSION_RATIO_THRESHOLD)

bench-quick:
	$(PY) -m benchmarks.run --quick

bench:
	$(PY) -m benchmarks.run

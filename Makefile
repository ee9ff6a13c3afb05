# One memorable invocation per tier-1 task (see README.md).
PY ?= python
# src for the repro package, . so `benchmarks` resolves as a package.
export PYTHONPATH := src:.$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-all bench bench-smoke lint

# Tier-1 verify: deterministic suite; hypothesis modules auto-skip if absent.
# Tests run on the CPU (JAX_PLATFORMS=cpu): kernels in interpret mode, plus
# compiles for a described TPU; `python chip_smoke.py` is the on-chip check.
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q

# Includes the property-based modules (pip install -r requirements-dev.txt).
test-all:
	JAX_PLATFORMS=cpu $(PY) -m pytest -q

# All paper-reproduction benchmarks as CSV (see EXPERIMENTS.md).
bench:
	$(PY) benchmarks/run.py

# Smoke of every benchmark section: real code paths, wall-clock-heavy
# sections shrunken (REPRO_BENCH_FAST); wired into CI so benchmark
# scripts cannot silently rot.  Per-section begin/end lines land on
# stderr (timeout attribution) and BENCH_smoke.json is written for
# benchmarks/compare.py / the CI artifact.
bench-smoke:
	$(PY) benchmarks/run.py --fast

# Syntax sweep (compileall), then pyflakes — whose findings FAIL the
# target (CI's lint job depends on that).  The one allowed skip is
# pyflakes being genuinely absent locally (pip install -r
# requirements-dev.txt); the skip is loud, never silent.
lint:
	$(PY) -m compileall -q src benchmarks examples tests
	@if $(PY) -c "import pyflakes" 2>/dev/null; then \
		$(PY) -m pyflakes src benchmarks examples tests; \
	else \
		echo "lint: pyflakes not installed; syntax sweep only" \
		     "(pip install -r requirements-dev.txt)"; \
	fi

# One-command entry points for the tier-1 suite and smoke benchmarks.
#
#   make test    — full tier-1 pytest run (hypothesis-based files skip
#                  cleanly when hypothesis isn't installed).  Every test runs
#                  under a timeout guard (pytest-timeout when installed, a
#                  faulthandler watchdog otherwise — see tests/conftest.py)
#                  so a deadlocked streaming-flush thread fails instead of
#                  hanging CI; tune with PYTEST_TIMEOUT=<seconds>
#   make bench   — smoke benchmark: extensibility LOC count (speed is
#                  measured on the chip: BENCHMARK.json, benchmarks/chip)
#   make bench-all — every registered benchmark (slow: full roofline sweep)
#   make docs-check — README/docs snippets compile, imports resolve, CLI
#                  flags and make targets referenced in docs exist

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test bench bench-all docs-check

test:
	$(PYTHON) -m pytest -x -q

bench:
	$(PYTHON) -m benchmarks.run --only extensibility

bench-all:
	$(PYTHON) -m benchmarks.run

docs-check:
	$(PYTHON) tools/docs_check.py

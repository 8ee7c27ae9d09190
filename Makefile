# Developer entry points (reference analog: the upstream Makefile).
# Tests force the CPU-simulated 8-device mesh via tests/conftest.py;
# smoke and bench need a TPU.

.PHONY: test test-quick lint docs docs-site smoke bench notebooks dryrun

docs:
	python scripts/gen_api_reference.py
	python scripts/build_docs_site.py

docs-site:
	python scripts/build_docs_site.py

test:
	python -m pytest tests/ -x -q

# the measured sub-minute spec-path modules (<5 min total on the
# simulated mesh) — the iteration/CI-sharding tier; `make test` remains
# the full matrix of record
test-quick:
	python -m pytest tests/ -m quick -q

lint:
	python scripts/lint_basics.py
	@if python -c "import ruff" 2>/dev/null; then \
		python -m ruff check unionml_tpu tests benchmarks scripts; \
	elif python -c "import flake8" 2>/dev/null; then \
		python -m flake8 --max-line-length 110 \
			--extend-ignore=E203,W503,E731,E741 \
			unionml_tpu tests benchmarks scripts; \
	else \
		echo "flake8/ruff not installed; lint_basics covered the correctness subset"; \
	fi

# the standing proof that train and serve still start on the chip
# (needs one TPU; `python chip_smoke.py --rehearse` walks it on the CPU)
smoke:
	python chip_smoke.py

bench:
	python bench.py

notebooks:
	python scripts/myst_to_ipynb.py docs/tutorials/*.md

dryrun:
	python __graft_entry__.py 8

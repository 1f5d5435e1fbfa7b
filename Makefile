PY ?= python

.PHONY: test test-fast smoke chip-smoke bench up init dryrun lint

# Static analysis gate: cordumlint always (stdlib-only), ruff + mypy-strict
# when installed (the CI lint job installs both; minimal TPU images may not).
lint:
	$(PY) -m tools.cordumlint cordum_tpu bench.py
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check cordum_tpu tools bench.py; \
	else echo "lint: ruff not installed; skipped (CI enforces it)"; fi
	@if command -v mypy >/dev/null 2>&1; then \
		mypy --strict cordum_tpu/protocol cordum_tpu/infra; \
	else echo "lint: mypy not installed; skipped (CI enforces it)"; fi

lint-baseline:
	$(PY) -m tools.cordumlint cordum_tpu bench.py --write-baseline \
		--justification "$(JUSTIFICATION)"

test:
	$(PY) -m pytest tests/ -q

test-fast:
	$(PY) -m pytest tests/ -q -x --ignore=tests/test_models.py --ignore=tests/test_moe_pipeline.py --ignore=tests/test_training.py

smoke:
	$(PY) tools/platform_smoke.py

# the main path on one TPU chip (exits non-zero without one); add
# ARGS=--rehearse for the CPU rehearsal or ARGS="--chips 4" for the TP path
chip-smoke:
	$(PY) chip_smoke.py $(ARGS)

bench:
	$(PY) bench.py

up:
	$(PY) -m cordum_tpu.cli up

init:
	$(PY) -m cordum_tpu.cli init

dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -c "import jax; jax.config.update('jax_platforms','cpu'); import __graft_entry__ as g; g.dryrun_multichip(8)"

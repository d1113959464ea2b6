# CI / local tooling for the Merge Path reproduction.
# All targets wrap the tier-1 command with PYTHONPATH=src (see ROADMAP.md).

PY ?= python

# perf-trajectory point written by `make ci`: derived automatically as
# highest existing BENCH_<n>.json + 1, so PRs can't forget the bump
BENCH_JSON ?= $(shell $(PY) tools/bench_diff.py --next)

.PHONY: test test-faults bench-smoke bench lint check ci docs-check train-smoke trace-smoke

# tier-1 verify (ROADMAP.md). The gating run of this suite is
# `pytest -n 6 --dist loadfile` under a 1,470 s limit: each test file goes
# whole to one worker, so the longest file's total, not the sum over
# files, sets the pace. Every test has a 300 s limit (tests/conftest.py).
test:
	PYTHONPATH=src $(PY) -m pytest -x -q

# fault-injection sweep: the fuzz tests in tests/test_faults.py run every
# guarded fallback edge, then the same suite re-runs under an env-driven
# plan (REPRO_FAULTS) so the degraded paths are exercised end to end the
# way production would hit them
test-faults:
	PYTHONPATH=src $(PY) -m pytest -x -q tests/test_faults.py
	PYTHONPATH=src REPRO_FAULTS="launch:merge:0;launch:sort:0;exchange:distributed_merge:0:window" \
		$(PY) -m pytest -x -q tests/test_faults.py -k env_plan

# docs coverage gate: every public repro.core / repro.kernels.ops /
# repro.runtime symbol must appear in docs/architecture.md or
# docs/robustness.md
docs-check:
	PYTHONPATH=src $(PY) tools/docs_check.py

# one real train step on the kernel path (fused SSM scan + Pallas MoE
# dispatch): finite loss, nonzero grad on every param leaf, params move
train-smoke:
	PYTHONPATH=src $(PY) -m repro.train.smoke

# telemetry gate: run a small serving workload + one distributed merge,
# write the Perfetto trace, and assert it is healthy — zero unclosed
# spans and Cor. 7 window balance ratio <= 1.05
trace-smoke:
	PYTHONPATH=src $(PY) -m repro.telemetry.smoke --out trace.json
	PYTHONPATH=src $(PY) -m repro.telemetry --check trace.json

# static analysis, run before anything launches: abstract kernel-contract
# checker (eval_shape only — zero device kernels), repo-specific AST lint,
# and the perf-regression gate over existing BENCH_*.json anchor rows
check:
	PYTHONPATH=src $(PY) -m repro.analysis
	$(PY) tools/lint_rules.py
	$(PY) tools/bench_diff.py --check

# full CI: static analysis first (contract violations fail fast, no
# kernels run), then tier-1 tests + fault-injection sweep + docs gate +
# kernel-path train step + smoke benchmarks recording the perf point
# (benchmarks/run.py fails if any fallback fired on the clean tree), then
# the bench-diff gate re-checks the fresh snapshot against the previous PR's
ci: check test test-faults docs-check train-smoke trace-smoke
	PYTHONPATH=src $(PY) benchmarks/run.py --smoke --json $(BENCH_JSON)
	$(PY) tools/bench_diff.py --check

# fast benchmark sweep (<60 s): small sizes of every paper benchmark
bench-smoke:
	PYTHONPATH=src $(PY) benchmarks/run.py --smoke

# full benchmark sweep (minutes)
bench:
	PYTHONPATH=src $(PY) benchmarks/run.py

# bytecode-compile everything (syntax/indentation/encoding errors) plus
# the repo-specific AST rules (stdlib ast only — the container is offline)
lint:
	$(PY) -m compileall -q src tests benchmarks examples tools
	$(PY) tools/lint_rules.py
	@echo "lint OK"

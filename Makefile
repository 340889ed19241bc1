# Test / check targets (reference parity: pydcop Makefile — unit,
# api, cli, doctests, and a static gate; the reference's mypy target
# maps to tools/static_check.py since mypy is not installable here).

PY ?= python

.PHONY: all test chaos chaos-soak chaos-soak-quick trace-demo serve-smoke shard-smoke unit api cli check doctest dryrun chip-smoke

# 0 = the full scenario matrix; `make test` runs the --quick
# device-side gate (chaos_soak.QUICK_GATE; fixed seed, ~20 s).
SOAK_SCENARIOS ?= 0

all: check test

# Executable docstring examples across the package (reference
# Makefile:6 `pytest --doctest-modules ./pydcop`).  Root conftest.py
# forces the CPU backend for the examples.
doctest:
	$(PY) -m pytest --doctest-modules pydcop_tpu -q

# Chaos gate: the resilience battery under a FIXED fault seed (the
# fault pattern is a pure function of seed + edge + message index, so
# a red run reproduces with the same command).  The battery lives in
# tests/, so the default `make test` below already runs it — chaos is
# a gate inside the default suite, and this target is the fast,
# seed-pinned way to run it alone.
chaos:
	PYDCOP_CHAOS_SEED=42 $(PY) -m pytest \
		tests/unit/test_resilience_battery.py -q

# Self-healing gate: the seeded chaos-soak scenario matrix
# (drop+dup+delay / partition-with-heal / silent kill / guard trip /
# checkpoint corruption / serve crash + journal replay / poison bin /
# shard trip + repartition), each asserting the global invariants:
# valid assignment, monotone cycle counter, no orphaned computations,
# and health verdicts consistent with the injected kill schedule.  A
# red scenario prints its seed + trace file for replay
# (tools/chaos_soak.py --only NAME).  Default = full matrix;
# `make test` runs the --quick device-side gate (~20 s).
chaos-soak:
	PYDCOP_CHAOS_SEED=42 $(PY) tools/chaos_soak.py \
		--scenarios $(SOAK_SCENARIOS)

chaos-soak-quick:
	PYDCOP_CHAOS_SEED=42 $(PY) tools/chaos_soak.py --quick

# Observability gate: solve a small graph coloring through the real
# CLI with --trace + --metrics and assert the Chrome trace validates
# (json loads, spans well-nested, expected span kinds), the metrics
# JSONL has a monotone cycle counter, the Prometheus dump parses, and
# `pydcop trace summary` aggregates it.  See tools/trace_demo.py.
trace-demo:
	$(PY) tools/trace_demo.py

# Serve-smoke gate: the solve service end-to-end over real HTTP —
# a mixed-structure burst of N requests must complete in fewer than
# N device dispatches (batch coalescing counter-asserted), every
# response must equal the solo api.solve assignment, and an overload
# burst past the high-water mark must yield clean 429s (never a hang
# or a dropped request) with pydcop_requests_total accounting for
# every request.  See tools/serve_smoke.py + docs/serving.md.
serve-smoke:
	$(PY) tools/serve_smoke.py

# Shard-smoke gate: the partitioned engine on 8 forced host devices —
# a 2k-var loopy grid partitioned with edge_cut_fraction < 0.3,
# per-superstep halo-exchange volume asserted strictly below the
# replicated all-reduce volume, and bit-parity with the unsharded
# solve; plus the shard_graph auto-padding regression.  See
# tools/shard_smoke.py + docs/sharding.md.
shard-smoke:
	$(PY) tools/shard_smoke.py

test: trace-demo serve-smoke shard-smoke
	$(MAKE) chaos-soak-quick
	$(PY) -m pytest tests/ -q

unit:
	$(PY) -m pytest tests/unit -q

api:
	$(PY) -m pytest tests/api -q

cli:
	$(PY) -m pytest tests/cli -q

check: doctest
	$(PY) tools/static_check.py

dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"

# On the chip only: drive solve and serve once through the normal
# entry points on the TPU and check every cost on the host.  Fails
# (non-zero, no result line) when JAX finds no TPU.  See chip_smoke.py.
chip-smoke:
	$(PY) chip_smoke.py

# Development gate — the discipline the reference enforces via
# .rustfmt.toml + .pre-commit-config.yaml (cargo check / clippy / fmt).
# `make check` is the pre-commit bar: nothing ships with it red.

PY ?= python

.PHONY: check lint analyze test native sim-smoke profile-smoke constrained-smoke delta-smoke defrag-smoke train-smoke latency-smoke elasticity-smoke protocol-smoke fuzz-smoke jit-stability-smoke clean

check: lint test profile-smoke constrained-smoke delta-smoke defrag-smoke train-smoke latency-smoke elasticity-smoke protocol-smoke fuzz-smoke jit-stability-smoke

lint: analyze
	$(PY) -m compileall -q tpu_scheduler tests scripts __graft_entry__.py

# The whole static-analysis policy (scripts/analyze/): ported hygiene rules
# plus THRD lock discipline, JAXP jit purity, DTRM sim determinism, SHPE
# shape contracts, EXCP failure-class closure, and the baseline gate (fails
# on new findings and on stale baseline entries).  --budget asserts the
# suite stays the fast part of this gate (pre-commit uses the
# --changed-only fast path).
analyze:
	$(PY) -m scripts.analyze --json-out .analyze_report.json --budget 5

test:
	$(PY) -m pytest tests/ -x -q

# The tier-1 simulation gate: one seeded scenario (~2k pods × 200 nodes,
# node churn + an api-brownout window) must finish green on CPU, plus the
# multi-replica failover scenario (two sharded replicas, owner crash-killed
# between solve and flush) — the same contracts tests/test_sim.py and
# tests/test_multi_replica_sim.py pin, runnable standalone for a verdict.
sim-smoke:
	JAX_PLATFORMS=cpu $(PY) -m tpu_scheduler.cli sim --scenario sim-smoke --seed 0
	JAX_PLATFORMS=cpu $(PY) -m tpu_scheduler.cli sim --scenario replica-kill-mid-cycle --seed 0

# The profiler gate: one steady-state scenario with the always-on profiler,
# failing (exit 1) when attribution coverage drops below 0.9 or the measured
# span+ring overhead estimate exceeds 2% of the cycle wall — the same
# contracts tests/test_profiler.py pins, runnable standalone for a verdict.
profile-smoke:
	JAX_PLATFORMS=cpu $(PY) -m tpu_scheduler.cli sim --scenario steady-state --seed 0 --profile-check

# The fused-conflict-filter gate: native-vs-jit binding parity on a
# constrained synth cluster plus a single-digit-seconds budget on the shape
# that needed ~60 s before the round-7 active-set fusion — fails (exit 1) if
# the filter ever re-grows a full-shape per-round sweep
# (scripts/constrained_smoke.py).
constrained-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.constrained_smoke

# The incremental-engine gate: the churn-steady-state scenario must pass
# with the scorecard incremental block green (delta cycles the default,
# zero shadow-solve parity mismatches) plus a delta-vs-full budget check on
# a downscaled synthetic cluster (scripts/delta_smoke.py).
delta-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.delta_smoke

# The background-rebalancer gate: the defrag-smoke fragmentation scenario
# must recover the scorecard rebalance block's packing-efficiency gate
# within its migration budget (zero orphaned migrations), while the
# rebalancer-off baseline must FAIL the same gate (scripts/defrag_smoke.py).
defrag-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.defrag_smoke

# The policy-learning gate: a tiny seeded CEM run (3 generations on the
# train-smoke scenario) must keep its best objective at or above the
# generation-0 default-profile objective, reproduce byte-identically from
# the one seed, and survive the tuned-artifact round-trip
# (scripts/train_smoke.py).
train-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.train_smoke

# The time-to-bind waterfall gate: the steady-state scenario must pass with
# the scorecard latency block green and segment coverage >= 0.95 of bound
# pods, and a live controller's /debug/latency route must serve the
# per-tier decomposition (scripts/latency_smoke.py).
latency-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.latency_smoke

# The closed-loop autoscaler gate: the flash-crowd elasticity scenario
# must pass its joint cost+SLO objective with real scale-ups and zero
# reclaim orphans, and the autoscaler-off static baseline must FAIL the
# same gate (scripts/elasticity_smoke.py).
elasticity-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.elasticity_smoke

# The protocol-verification gate: model-check every committed # protocol:
# spec against its crash/retry environment — all six protocol sites parse,
# zero invariant/progress violations, every composite state space within
# bounds, inside a pinned wall budget (scripts/protocol_smoke.py).
protocol-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.protocol_smoke

# The chaos-fuzzer gate: every checked-in reproducer in tests/fuzz_corpus/
# replays bit-identically, a pinned 24-plan seed-0 campaign finds zero
# violations, and coverage reaches its (fault-op × state-facet) floor —
# inside a pinned wall budget (scripts/fuzz_smoke.py).
fuzz-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.fuzz_smoke

# The compile-cache boundedness gate: the JITC/XFER analyzer rules must be
# clean over the annotated tree, and the steady-state scenario driven by
# the real TpuBackend (JAX on CPU) must show ZERO XLA compiles after the
# warmup window — the scorecard compile block live and flat
# (scripts/jit_stability_smoke.py).
jit-stability-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.jit_stability_smoke

# C++ shim (optional; ops/native_ext.py gates on its presence)
native:
	$(MAKE) -C native

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
	rm -rf build dist *.egg-info

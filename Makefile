# Developer entry points (the reference's Makefile/hack scripts equivalent:
# /root/reference/Makefile:47-107 unit-test / integration-test / verify).

PY ?= python

# tier-1 filter: `slow`-marked tests (the Pallas full-solve differential
# matrix) are excluded here and run by their own target (make pallas-smoke)
.PHONY: test
test: host-health
	$(PY) -m pytest tests/ -x -q -m "not slow"

# one host-health JSON line (timed matmul under timeout + loadavg) so
# every archived suite log is self-describing about the machine it ran
# on; the same probe() stamps tools/perf_sentry.py verdicts. --cost-arm
# attaches the committed static-cost digest (docs/cost_model.json): a
# degraded host still carries one trustworthy perf statement
.PHONY: host-health
host-health:
	JAX_PLATFORMS=cpu $(PY) tools/host_health.py --cost-arm

# the benchmark (BENCHMARK.json + benchmark/) is measured on the chip by
# `python3 benchmark/run.py` (benchmark/README.md); this is its own CPU
# rehearsal of every cell through the real command (~4 min, not tier-1;
# a count and correctness result, never a speed result)
.PHONY: benchmark-rehearse
benchmark-rehearse:
	JAX_PLATFORMS=cpu $(PY) -m pytest benchmark/tests -q

.PHONY: multichip
multichip:
	$(PY) -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

# CI observability gate: the cycle tracer must emit a Perfetto-loadable
# trace (pipeline H2D/solve/D2H rows per buffer, framework extension-point
# spans, the pipelined cycle's rows, failure attribution populated)
.PHONY: trace-smoke
trace-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/trace_smoke.py

# CI record/replay gate: a recorded cycle (real run_cycle hooks) must
# replay bit-identically through the sequential parity path and the explain
# JSON must validate (per-plugin columns summing to the solver's total)
.PHONY: replay-smoke
replay-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/replay.py smoke

# CI tuning gate: record a reduced trimaran corpus through the real
# run_cycle hooks, sweep >= 64 candidate weight vectors in ONE vmapped
# compile (compile-watch asserts <= 1 trace for the sweep program), and
# require the emitted tuned profile to pass the hard-constraint replay
# oracles (fit / queue-order quota / gang quorum) with zero violations
.PHONY: tune-smoke
tune-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/tune.py smoke

# the `slow` tests (ISSUE 13): the interpret-mode Pallas sharded wave solve
# (parallel/kernels ring programs — the CPU twins of the on-chip kernels)
# must be bit-identical to the lax collectives build across the
# differential matrix (2 shard counts x 3 seeds + the gang/quota envelope).
# The census of the Pallas program is held by the committed manifests
# (tests/test_pallas_kernels.py); the compiled kernels by chip-smoke-4
.PHONY: pallas-smoke
pallas-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_differential.py -q \
		-m "slow or not slow" -k TestPallasWaveParity \
		-p no:cacheprovider

# the chip check: the served daemon path, the north-star pipeline and
# every plugin profile on one TPU, one process, every result asserted
# (exits non-zero without a TPU). `chip-smoke-4` adds the sharded wave
# solve, lax collectives and compiled Pallas ring kernels, on four chips.
.PHONY: chip-smoke chip-smoke-4
chip-smoke:
	$(PY) chip_smoke.py
chip-smoke-4:
	$(PY) chip_smoke.py --devices 4

# CI bench-regression sentry gate (ISSUE 19 + 20): on really-measured
# timings, a reshuffle stays quiet (paired-sorted deltas are exactly
# zero), an injected uniform slowdown is flagged, an unhealthy host
# probe downgrades regression -> degraded-host, and the committed
# degenerate BENCH history classifies as no-baseline; the cost arm's
# two-arm split is proven on the same run (an injected algorithmic cost
# regression stays `regression` under the simulated sick host where the
# timing arm downgrades, and a zero cost delta stays quiet)
.PHONY: sentry-smoke
sentry-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/perf_sentry.py selftest

# compiled-cost observatory (ISSUE 20): CPU-compile the full program
# registry, record XLA cost/memory analyses joined with the TPU op
# histograms + collective census + VMEM envelopes, project rooflines,
# refresh docs/cost_model.json only on a fully clean run (budgets carry
# forward; re-derive explicitly with --rebudget)
.PHONY: cost-audit
cost-audit:
	$(PY) tools/cost_observatory.py

# read-only CI gate: re-measure and fail closed on missing manifest,
# coverage gap, budget breach, or cost-digest drift (digest equality
# enforced only under the manifest's jax version)
.PHONY: cost-audit-check
cost-audit-check:
	$(PY) tools/cost_observatory.py --check

# verify composes the READ-ONLY gates (tpu-lower-check, jaxpr-audit-check):
# it must never rewrite the committed manifests as a side effect —
# refreshing digests is the explicit `make tpu-lower` / `make jaxpr-audit`.
# Every prerequisite is a target of this file that runs a file that exists
# (tests/test_makefile.py); none of them gates on a CPU speed of the system
.PHONY: verify
verify: test multichip lint tpu-lower-check jaxpr-audit-check kernel-audit-check race-audit-check cost-audit-check race-smoke trace-smoke replay-smoke tune-smoke sentry-smoke

.PHONY: lint
lint:
	$(PY) tools/graft_lint.py

# trace every registered program (BASELINE cfgs 0-6, both sharded solves,
# entry()) to closed jaxprs, run the JA001-JA004 invariant rules, refresh
# docs/jaxpr_audit.json
.PHONY: jaxpr-audit
jaxpr-audit:
	$(PY) tools/jaxpr_audit.py

# read-only CI gate: rule verdicts + manifest coverage + census drift
# (census equality enforced only under the manifest's jax version)
.PHONY: jaxpr-audit-check
jaxpr-audit-check:
	$(PY) tools/jaxpr_audit.py --check

# kernel-resource & exactness audit over the same registry: KA001 VMEM
# envelopes (the derived PALLAS_MAX_ELECTION_ELEMS gate), KA002 DMA
# start/wait discipline, KA003 the 2^53 exactness lattice; refreshes
# docs/kernel_audit.json only on a fully clean run
.PHONY: kernel-audit
kernel-audit:
	$(PY) tools/kernel_audit.py

# read-only CI gate: zero violations + manifest coverage + envelope/gate
# agreement (fail-closed when the manifest is missing)
.PHONY: kernel-audit-check
kernel-audit-check:
	$(PY) tools/kernel_audit.py --check

# whole-program concurrency audit: discover thread entry points, walk
# reachable locksets, run CA001-CA005, refresh docs/race_audit.json
.PHONY: race-audit
race-audit:
	$(PY) tools/race_audit.py

# read-only CI gate: zero violations + entry-table/census drift vs the
# committed manifest (fail-closed when the manifest is missing)
.PHONY: race-audit-check
race-audit-check:
	$(PY) tools/race_audit.py --check

# the dynamic half: replay the pipelined-cycle/shadow-tuner/hung-watchdog
# composite under seeded interleavings (SPT_RACE=1 lock/event proxies) —
# zero violations, bit-identical placements across every interleaving
.PHONY: race-smoke
race-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/race_smoke.py

# AOT-lower every registered program (BASELINE cfgs 0-6, both sharded
# solves, entry()) to TPU StableHLO, scan for CLAUDE.md landmines, refresh
# docs/tpu_lowering.json
.PHONY: tpu-lower
tpu-lower:
	$(PY) tools/tpu_lower.py

# read-only CI gate: lowering + landmines + digest drift vs the committed
# manifest (digest equality enforced only under the manifest's jax version)
.PHONY: tpu-lower-check
tpu-lower-check:
	$(PY) tools/tpu_lower.py --check

.PHONY: native
native:
	$(PY) -c "from scheduler_plugins_tpu import bridge; print(bridge.build_native(bridge._SRC))"

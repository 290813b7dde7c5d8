# Developer entry points (the reference's Makefile/hack scripts equivalent:
# /root/reference/Makefile:47-107 unit-test / integration-test / verify).

PY ?= python

# tier-1 filter: `slow`-marked tests (the Pallas full-solve differential
# matrix) are excluded here — the suite sits near the 870s runtime cliff —
# and run by their dedicated smoke target instead (make pallas-smoke)
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

.PHONY: bench
bench:
	$(PY) bench.py

.PHONY: bench-all
bench-all:
	for c in 1 2 3 4 5; do $(PY) bench.py --config $$c || exit 1; done

.PHONY: multichip
multichip:
	$(PY) -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

.PHONY: tpu-smoke
tpu-smoke:
	$(PY) bench.py --config 0

# CI perf gate: reduced-shape batch-vs-sequential comparison on the CPU
# backend — the batched throughput mode must never lose to its own
# sequential parity path (>= 0.9x pods/s absorbs runner timing noise;
# ISSUE 2 reversed the measured 0.83-0.89x split on the NUMA config)
.PHONY: bench-smoke
bench-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --smoke-compare 2,3

# CI observability gate: the cycle tracer must emit a Perfetto-loadable
# trace (pipeline H2D/solve/D2H rows per buffer, framework extension-point
# spans, failure attribution populated) and its enabled-path overhead must
# stay within max(2%, the run's own timing jitter) on a reduced
# north-star shape
.PHONY: trace-smoke
trace-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/trace_smoke.py

# CI record/replay gate: a recorded cycle (real run_cycle hooks) must
# replay bit-identically through the sequential parity path, the explain
# JSON must validate (per-plugin columns summing to the solver's total),
# and recorder-enabled overhead must stay within max(2%, the run's own
# off-recorder jitter)
.PHONY: replay-smoke
replay-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/replay.py smoke

# CI serving gate: reduced sustained-churn run (Poisson arrivals/
# departures + node adds on the same event stream, serve mode vs full
# re-snapshot) — the resident-state delta path must beat the baseline
# >= 1.5x on cycles/s with IDENTICAL placements and zero hard-constraint
# violations
.PHONY: churn-smoke
churn-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --churn-smoke

# CI tuning gate: record a reduced trimaran corpus through the real
# run_cycle hooks, sweep >= 64 candidate weight vectors in ONE vmapped
# compile (compile-watch asserts <= 1 trace for the sweep program), and
# require the emitted tuned profile to pass the hard-constraint replay
# oracles (fit / queue-order quota / gang quorum) with zero violations
.PHONY: tune-smoke
tune-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/tune.py smoke

# CI sharded-solver gate: reduced mega shape on an 8-host-device ("nodes",)
# mesh — the shard_map ring-election waterfill's placements must MATCH the
# single-device wave path bit-exactly, the replayed hard-constraint audit
# must be clean, and the traced program's collective census must stay
# O(shards) with NO all_gather of the node axis (graft_lint GL009's
# compiled-level twin)
.PHONY: shard-smoke
shard-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --shard-smoke

# the full mega-scale bench (100k nodes x 1M pods on the sharded wave
# solver, 8-host-device mesh vs the single-device wave path) — minutes,
# not a CI gate; shard-smoke is the CI-sized version
.PHONY: mega
mega:
	JAX_PLATFORMS=cpu $(PY) bench.py --config 8

# CI Pallas-kernel gate (ISSUE 13): the SPT_PALLAS=1 interpret-mode
# sharded wave solve (parallel/kernels ring programs — the CPU twins of
# the on-chip kernels) must be bit-identical to the lax collectives build
# on the reduced mega shape AND across the slow differential matrix
# (2 extra shard counts x 3 seeds + the gang/quota envelope), with the
# ring kernels actually replacing the framework collectives (census) and
# the kernel programs covered by the committed lowering manifest
.PHONY: pallas-smoke
pallas-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --pallas-smoke
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

# CI packing gate (ISSUE 14): reduced packing-frontier run — the packing
# solve mode must STRICTLY improve packed_utilization AND fragmentation
# over the wave path with ZERO hard-constraint violations (the
# tuning/gates.py replay oracles), budget-0 placements bit-identical to
# the wave path, and score-sum drift bounded
.PHONY: pack-smoke
pack-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --pack-smoke

# CI online-tuning gate (ISSUE 15): reduced drifting-mix config-14 run —
# the online-tuned lane (flight-recorder ring + shadow sweeps + guarded
# rollout through the shared tuning/promotion gates) must beat the
# static profile on the placement-quality gauges over the drifted mix
# with ZERO hard-constraint violations, per-tick shadow-lane overhead
# within max(5%, the run's jitter floor), observe-only lane placements
# bit-identical to the lane-off control, and the injected-regression
# phase rolling back to last-known-good within 2 cycles with no flapping
.PHONY: tune-live-smoke
tune-live-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --tune-live-smoke

# CI resilience gate: reduced chaos-churn run under the FULL seeded fault
# plan (hung solve, device error, garbage output, dropped/duplicated/
# corrupted sink deltas, feed stall, crash mid-cycle) — zero
# hard-constraint violations, every fault fired and recovered within a
# bounded cycle count, EVERY cycle bit-identical to the no-chaos control,
# and fault-free watchdog overhead within max(2%, the run's jitter floor)
.PHONY: chaos-smoke
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --chaos-smoke

# CI endurance gate: reduced cluster-life config-11 run (one seeded
# churn+gangs+chaos+waves stream, concurrent pipelined cycle engine vs
# the serial engine, shared scheduler) — the pipelined engine must beat
# the serial engine >= 1.5x on serve-phase (churn+waves) cycles/s with IDENTICAL
# per-cycle placements, a bit-identical final cluster state and a clean
# replayed capacity audit
.PHONY: endurance-smoke
endurance-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --endurance-smoke

# CI rank-gang gate: reduced config-10 run — the gang phase's max
# inter-rank cost strictly below the quorum-only Coscheduling baseline on
# the same event stream, jit solve bit-identical to its numpy sequential
# twin (drift 0.0), zero fit/quota/quorum violations, and elastic
# grow/shrink converging within 2 cycles
.PHONY: gang-smoke
gang-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --gang-smoke

# CI K-lane gate (ISSUE 17): reduced config-15 run — every K's placements
# bit-identical to the defined serial order on EVERY cycle (the
# adversarial contended tail included), zero hard-constraint violations,
# zero serial fallbacks, the contended phase forcing real cross-lane
# conflicts through the fence, and the headline-K solve-boundary ratio
# >= 1.5 (the full config-15 shape targets 2x at K=4; the smoke bound
# absorbs 2-core CI runners — the shard-smoke precedent)
.PHONY: lane-smoke
lane-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --lane-smoke

# CI pod-lifecycle ledger gate (ISSUE 19): ledger-on overhead within
# max(2%, the off-series jitter floor) via interleaved paired deltas,
# stage decomposition exactly summing to e2e on every retired pod, and
# serial run_cycle vs PipelinedCycle producing event-SEQUENCE-identical
# ledgers on the shared churn scenario
.PHONY: ledger-smoke
ledger-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/ledger_smoke.py

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
# refreshing digests is the explicit `make tpu-lower` / `make jaxpr-audit`
.PHONY: verify
verify: test multichip lint tpu-lower-check jaxpr-audit-check kernel-audit-check race-audit-check cost-audit-check race-smoke sanitize-smoke trace-smoke replay-smoke churn-smoke shard-smoke pallas-smoke tune-smoke tune-live-smoke chaos-smoke gang-smoke endurance-smoke pack-smoke lane-smoke ledger-smoke sentry-smoke

.PHONY: lint
lint:
	$(PY) tools/graft_lint.py

# trace every registered program (bench cfgs 0-6, both sharded solves,
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

# CI sanitizer gate: reduced cfg-2/cfg-3 shapes + the donated chunk
# pipeline + entry() under SPT_SANITIZE=1 checkify instrumentation —
# fails on ANY index-OOB/NaN/div-by-zero finding
.PHONY: sanitize-smoke
sanitize-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --sanitize-smoke 2,3

# AOT-lower every bench program + both sharded solves + entry() to TPU
# StableHLO, scan for CLAUDE.md landmines, refresh docs/tpu_lowering.json
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

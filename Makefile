# Build/test entry points (the reference's Makefile equivalent, reduced to
# what is meaningful for the TPU framework: /root/reference/Makefile's
# test / test-race / ebpf-generate / bench roles).

PY ?= python

.PHONY: test test-fast bench bench-checked build-bench slo-bench \
	churn-bench flow-bench resident-bench telemetry-bench mlscore-bench \
	payload-bench pipeline-bench native entry-check dryrun-multichip \
	mesh-check \
	spill-read wire-check lint static-check state-check lock-check \
	sched-check bounds-check clean

# 8 virtual host devices for every CPU-side audit/gate: the mesh serving
# entrypoints (classify-mesh/*) need a multi-device pool to build, and a
# single-device audit would silently skip them.
MESH_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

# Full suite including slow-marked scale tests (1M analyzer tier, full
# registry audit); the tier-1 budgeted run and test-fast exclude them.
test:
	$(PY) -m pytest tests/ -q

test-fast:
	$(PY) -m pytest tests/ -q -x -m "not slow"

# One JSON line on stdout; diagnostics on stderr (driver contract).
bench:
	$(PY) bench.py

# Build the native C++ reference classifier explicitly (normally built
# on demand by infw.backend.cpu_ref — the bpf2go-generate analogue).
native:
	$(PY) -c "from infw.backend.cpu_ref import load_library; load_library()"

# Single-chip compile check of the flagship forward step, then the
# static hot-path audit (x64 leaks, host callbacks, recompile lint,
# Pallas VMEM budget) over every registered jitted entrypoint —
# --strict so warnings fail CI too.
entry-check:
	$(PY) -c "import __graft_entry__ as g, jax; fn, args = g.entry(); \
	jax.block_until_ready(jax.jit(fn)(*args)); print('entry OK')"
	$(MESH_ENV) $(PY) tools/infw_lint.py jax --strict

# Lint (ruff when installed, AST fallback otherwise — same conservative
# F + E9 rule set; see pyproject.toml [tool.ruff]).
lint:
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
		$(PY) -m ruff check infw tools tests deploy bench.py __graft_entry__.py; \
	else \
		$(PY) tools/_lint_fallback.py; \
	fi

# Patch-path model checker (infw.analysis.statecheck): seeded op
# sequences over the device-table edit state machine — after every op
# the incrementally-patched device state must be bit-identical to a
# cold rebuild and classify-equivalent to the CPU oracle — plus two
# injected-defect acceptances:
#   1. --inject-defect (joined-pad) re-introduces the PR-4 joined-
#      placeholder bucket-padding bug; the checker must catch it with a
#      shrunk reproducer of <= 3 ops (exit 0 = caught);
#   2. --inject-defect cskip zeroes the compressed layout's skip-node
#      chain-bits words (jaxpath._INJECT_CSKIP_BUG); resident and cold
#      rebuild share the defect, so the catch must come from oracle
#      divergence — proving the classify-equivalence half covers the
#      skip-node path;
#   3. --inject-defect flowstale drops the flow tier's generation-bump
#      invalidation (infw.flow._INJECT_FLOW_STALE_BUG): a rule edit
#      then leaves the exact-match flow cache serving the PRE-edit
#      verdict — device state, host model and cold rebuild all agree,
#      so the catch must be oracle divergence on the flow-path witness,
#      shrunk to a (flow_traffic, rules_edit) pair;
#   4. --inject-defect cowleak makes the CoW arena's clone path forget
#      the donor page's refcount decrement (jaxpath._INJECT_COWLEAK_
#      BUG); check_arena's refcount-vs-page-table-rows invariant must
#      catch it on the shared-then-edited-biased arena-cow config;
#   5. --inject-defect spliceleak makes the subtree-splicing arena's
#      unsplice path forget the old plane's refcount decrement
#      (jaxpath._INJECT_SPLICELEAK_BUG); check_arena's plane-refcount-
#      vs-splice-row-recount invariant must catch it on the near-copy-
#      biased arena-splice config;
#   6. the strict jax audit must FAIL on a deliberately injected
#      implicit host->device transfer (and pass without it — the plain
#      strict audit runs in entry-check/static-check);
#   7. the bounds verifier acceptances: --inject-defect clampgather
#      (drop the spliced page-table & mask decode; caught as oob-gather
#      with a diverging bank-1 witness) and --inject-defect i8wrap
#      (int8 restage of the AC carried DFA state; caught as int-wrap
#      with a diverging deep-state payload witness) — each in a fresh
#      process, the flags act at trace time.
# The full defect inventory is declarative (infw.analysis.defects);
# `infw_lint acceptance` loops it end to end.
# Must be green before any bench record is published.
state-check:
	$(MESH_ENV) $(PY) tools/infw_lint.py state --strict
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect cskip
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect fold
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect pageflip
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect cowleak
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect spliceleak
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect flowstale
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect residentstale
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect slotepoch
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect sketchsat
	$(MESH_ENV) $(PY) tools/infw_lint.py state --inject-defect mlquant
	$(MESH_ENV) $(PY) tools/infw_lint.py bounds --inject-defect clampgather
	$(MESH_ENV) $(PY) tools/infw_lint.py bounds --inject-defect i8wrap
	@$(MESH_ENV) $(PY) tools/infw_lint.py jax --strict \
		--inject-donation-defect --entries defect/undonated-buffer \
		>/dev/null 2>&1; rc=$$?; \
	if [ $$rc -eq 1 ]; then \
		echo "donation-lint injection caught"; \
	elif [ $$rc -eq 0 ]; then \
		echo "state-check FAIL: injected undonated buffer NOT caught"; \
		exit 1; \
	else \
		echo "state-check FAIL: donation audit exited $$rc (want 1 = caught)"; \
		exit 1; \
	fi
	$(MAKE) sched-check
	@$(MESH_ENV) $(PY) tools/infw_lint.py jax --strict \
		--inject-transfer-defect --entries defect/implicit-transfer \
		>/dev/null 2>&1; rc=$$?; \
	if [ $$rc -eq 1 ]; then \
		echo "transfer-lint injection caught"; \
	elif [ $$rc -eq 0 ]; then \
		echo "state-check FAIL: injected implicit transfer NOT caught"; \
		exit 1; \
	else \
		echo "state-check FAIL: inject audit exited $$rc (want 1 = caught)"; \
		exit 1; \
	fi

# Repo-level static gate: rule-table semantics + jitted hot-path audit
# + the patch-path model checker.
#   1. examples lint — the shipped deny-all example INTENTIONALLY denies
#      failsafe ports (that finding is the analyzer's demo; see README
#      "Static analysis"), so that one check is silenced here;
#   2. the acceptance gate: a table with one injected shadowed rule and
#      one Allow/Deny conflict must report EXACTLY those two findings,
#      each witness confirmed by replay against the CPU oracle;
#   3. the jax audit across the shape ladder, strict (incl. the
#      transfer-guard lint);
#   4. the state checker with its injected-defect acceptances.
# Concurrency verifier (ISSUE-18): the static lock-order/guard pass
# (repo-wide, zero unsuppressed findings) plus its lockorder
# injected-defect acceptance, and the deterministic interleaving
# explorer's four production scenarios plus the cowrace acceptance.
lock-check:
	$(PY) tools/infw_lint.py lock --strict
	$(PY) tools/infw_lint.py lock --inject-defect lockorder

sched-check:
	$(MESH_ENV) $(PY) tools/infw_lint.py sched --strict
	$(MESH_ENV) $(PY) tools/infw_lint.py sched --inject-defect cowrace

# Kernel admission verifier (infw.analysis.boundscheck): jaxpr abstract
# interpretation over EVERY registered entrypoint, seeded from the
# declared tensor bounds (infw.contracts.TENSOR_BOUNDS — the same
# declarations statecheck's runtime invariant sweeps enforce), proving
# gather/scatter/dynamic_slice bounds and integer-overflow freedom.
# Intentional modular arithmetic lives in
# infw/analysis/boundscheck_suppressions.txt with required
# justifications; --strict means zero unsuppressed findings.  The two
# injected-defect acceptances (clampgather, i8wrap) run in state-check
# (fresh processes — the flags act at trace time).
bounds-check:
	$(MESH_ENV) $(PY) tools/infw_lint.py bounds --strict

static-check: lint
	$(PY) tools/infw_lint.py rules --ignore failsafe-violation --strict
	$(PY) tools/infw_lint.py rules --acceptance
	$(MESH_ENV) $(PY) tools/infw_lint.py jax --strict
	$(MAKE) bounds-check
	$(MAKE) lock-check
	$(MAKE) state-check
	@echo "static-check OK"

# The 1M cold-build microbenchmark (bench.bench_build): vectorized
# columnar compiler vs the retired per-key reference on the SAME host
# and content, runs INTERLEAVED so both see the same ambient load,
# output bit-identity checked, with a regression threshold on the
# measured speedup (INFW_BUILD_SPEEDUP_MIN, default 1.3x — observed
# 1.7-2.3x interleaved on the 2-core CI host, up to ~5x under memory
# pressure, while a reversion to per-key work lands at ~1x; the
# recorded-baseline ratio is in the emitted vs_baseline field).
build-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --build-bench

# The SLO serving tier (bench.bench_slo) standalone at a smoke load
# off-TPU: open-loop Poisson arrivals through the deadline-aware
# continuous microbatching scheduler (infw.scheduler), p50/p99/p999
# above link floor at 3 offered loads, deadline-miss rate, achieved
# batch sizes, and the fixed-ingest_chunk A/B — gated on the scheduled
# path's p99-above-floor beating the baseline (INFW_SLO_P99_MAX_RATIO,
# default 0.9x; verdicts are oracle-checked inside the tier).
slo-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --slo-bench

# The update-storm churn tier (bench.bench_churn) standalone at a smoke
# load off-TPU: folded 64-edit transaction vs the sequential
# one-edit-one-generation path (amortized per-edit A/B, gated on
# INFW_CHURN_SPEEDUP_MIN, default 5x), plus sustained edits/s under a
# fixed offered classify load with p99 edit-visible latency and a
# classify-throughput retention gate (INFW_CHURN_RETENTION_MIN, default
# 0.9).  The statecheck multi-op transaction equivalence (txn configs)
# runs inside the gate BEFORE any record is published.
churn-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --churn-bench

# The multi-tenant arena tier (bench.bench_tenant) standalone at smoke
# scale off-TPU: pre-staged tenant hot-swap (page-table row flip) vs
# the full re-upload A/B (interleaved min-vs-min, gated on
# INFW_SWAP_SPEEDUP_MIN, default 10x — the ISSUE-10 acceptance), plus
# mixed-tenant batch vs sequential per-tenant dispatch at 64 tenants
# and the arena-vs-N-padded-tables HBM footprint line.  Mixed-batch
# verdicts are oracle-checked bit-exact inside the tier, and the
# statecheck arena equivalence configs run BEFORE any record is
# published.
tenant-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --tenant-bench

# The structural-compression ladder (bench.bench_splice) standalone at
# smoke scale off-TPU: a drift chain of similar-NOT-identical tenants
# (every tenant a k-edit delta of its predecessor, k in {1, 16, 256})
# through the shared-subtree splice layer — HBM bytes/tenant vs one
# flat slab per tenant (gated on INFW_SPLICE_BYTES_RATIO_MIN, default
# 10x at the k=16 rung over 2.5K CPU tenants, the ISSUE-17
# acceptance), the splice-indirect walk-latency tax vs a flat arena
# (INFW_SPLICE_WALK_TAX_MAX, default 2x, interleaved min-vs-min), and
# the zero-recompile warm drift lifecycle.  Sampled tenants are
# oracle-checked bit-exact inside the tier, and the arena-splice
# statecheck config runs BEFORE any record is published.
# INFW_SPLICE_TENANTS overrides the gate rung's tenant count.
splice-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --splice-bench

# The stateful flow tier (bench.bench_flow) standalone at smoke scale
# off-TPU: classify throughput at the 0/50/90/99% established-flow
# ladder (flow tier vs the stateless baseline, interleaved, verdicts
# oracle-gated bit-exact per rung), the eviction-storm line (flow table
# much smaller than the flow population), and the zero-recompile warm
# flow lifecycle — gated on the 90%-point speedup
# (INFW_FLOW_SPEEDUP_MIN, default 1.15x).  The statecheck flow configs
# run inside the gate BEFORE any record is published.
flow-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --flow-bench

# The zero-copy resident serving tier (bench.bench_resident) standalone
# at smoke scale off-TPU: per-admission p50 latency of the ONE-fused-
# program donated-buffer loop vs the probe-then-classify multi-dispatch
# plan at batch 32/128 (interleaved min-vs-min, same trace, both flow
# tiers reset per pass), gated on the batch-32 speedup
# (INFW_RESIDENT_SPEEDUP_MIN, default 3x — the ISSUE-12 acceptance),
# with verdict bit-identity to the CPU oracle AND the multi-dispatch
# path gated in-tier, plus a warmed 1000-dispatch steady-state run that
# asserts ZERO resident-pool allocations and ZERO recompiles.  The
# statecheck resident config runs FIRST and gates record publication.
resident-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --resident-bench

# The device-resident telemetry tier (bench.bench_telemetry) standalone
# at smoke scale off-TPU: served classify-throughput retention with the
# in-kernel sketches on vs off at a FIXED OFFERED LOAD (70% of the
# sketches-off capacity, calibrated in-record — telemetry must fit the
# serving headroom; gated at INFW_TELEMETRY_RETENTION_MIN, default
# 0.95, with the raw full-speed dispatch A/B reported ungated beside
# it), a warmed zero-recompile/zero-alloc steady-state run with
# sketches on (the resident-bench discipline), attack-detection
# latency on synflood/denystorm traces (drained summaries must surface
# the planted attacker), and a live in-process --telemetry --trace
# daemon leg whose /metrics must serve the per-stage span histograms
# and whose event log the per-tenant heavy-hitter summaries.  Verdicts
# and sketch tensors are oracle-gated bit-exact in-tier, and the
# statecheck telemetry config runs FIRST and gates record publication.
telemetry-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --telemetry-bench

# The MXU anomaly-scoring tier (bench.bench_mlscore) standalone at
# smoke scale off-TPU: shadow-mode device scores bit-identical to the
# HostScoreModel oracle AND verdicts bit-identical to the scoring-off
# path + the CPU oracle (gated before any timing line), detection
# precision >= INFW_MLSCORE_PRECISION_MIN (default 0.95) and recall >=
# INFW_MLSCORE_RECALL_MIN (default 0.9) on the seeded synflood +
# portscan traces with detection latency reported beside them, served
# classify-throughput retention with scoring on at a FIXED OFFERED
# LOAD (70% of the scoring-off capacity, gated at
# INFW_MLSCORE_RETENTION_MIN, default 0.95), a warmed zero-recompile /
# zero-alloc steady state with scoring on, and an enforce-mode leg
# (attacker flows denied, failsafe cells never rewritten).  The
# statecheck mlscore config runs FIRST and gates record publication.
mlscore-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --mlscore-bench

# The payload matching tier (bench.bench_payload) standalone at smoke
# scale off-TPU: device/host bit-identity of the Aho-Corasick match
# bitmaps vs the naive substring oracle across the classic + resident
# fused paths BEFORE any timing line, the standalone automaton ladder
# (64/256/1024 patterns x 64/128 prefix bytes), served classify
# retention with matching on at a FIXED OFFERED LOAD (70% of the
# headers-only capacity, gated at INFW_PAYLOAD_RETENTION_MIN, default
# 0.9, at the 64-pattern/64-byte rung), a warmed zero-recompile /
# zero-alloc run spanning an in-bucket hot swap + mode flips, and an
# enforce-mode leg (signature lanes denied, failsafe cells never
# rewritten).  The statecheck payload configs run FIRST and gate
# record publication.
payload-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --payload-bench

# The pipelined-admission tier (bench.bench_pipeline) standalone at
# smoke scale off-TPU: the K=4 device-side superbatch epoch loop + the
# two-slot overlap vs the single-dispatch resident loop, packets/s
# above the link floor at batch 32 and 128, interleaved min-vs-min,
# gated at INFW_PIPELINE_OVERLAP_MIN (default 1.3x, the ISSUE-16
# acceptance).  Superbatch verdicts/stats/flow-columns/sketch tensors
# are gated bit-identical to K sequential fused dispatches in-tier, a
# warmed steady-state run cycling BOTH pipeline slots asserts zero
# allocations + zero recompiles, and the DeviceStripe mesh leg reports
# packets/s + device-busy fraction at 1/2/4/8 devices (hence the
# 8-virtual-device MESH_ENV).  The statecheck pipeline config runs
# FIRST and gates record publication.
pipeline-bench:
	$(MESH_ENV) $(PY) bench.py --pipeline-bench

# Bench behind the static gate (jaxpr drift must
# not silently change what the bench measures).  `make bench` itself is
# left untouched — its stdout is a driver contract.
bench-checked: static-check build-bench slo-bench churn-bench tenant-bench splice-bench flow-bench resident-bench telemetry-bench mlscore-bench payload-bench pipeline-bench bench

# Wire-codec gate: the delta+varint codec unit/fuzz suite plus a
# 10K-packet replay smoke through the real daemon ingest on CPU
# (verdicts checked bit-exact vs the oracle, delta engagement asserted).
wire-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_wire_codec.py -q
	JAX_PLATFORMS=cpu $(PY) tools/wire_smoke.py

# Decode a binary deny-event spill into reference-format event lines
# (the operator-facing consumer of the sustained-rate event path).
# Usage: make spill-read SPILL=path/to/deny-events.bin [ARGS=--follow]
spill-read:
	$(PY) tools/spill_read.py $(SPILL) $(ARGS)

# Full distributed step on a virtual 8-device CPU mesh, then the
# measured multi-chip throughput ladder (bench.multichip_ladder) whose
# final MULTICHIP_BENCH line is the driver's MULTICHIP record.
dryrun-multichip:
	$(MESH_ENV) \
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# Multi-chip serving gate: the mesh parity suite (MeshTpuClassifier vs
# single-chip TpuClassifier vs the CPU oracle, incl. reshard/overlay/
# edge cases) plus the smoke scaling bench — all on 8 simulated host
# devices, so the production mesh path is exercised on every run
# without TPU hardware.
mesh-check:
	$(MESH_ENV) $(PY) -m pytest tests/test_mesh.py tests/test_mesh_serving.py -q
	$(MAKE) dryrun-multichip

clean:
	rm -rf infw/backend/native/_build **/__pycache__ .pytest_cache

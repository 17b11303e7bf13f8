# Repo-level targets.  `make gate` is the pre-snapshot ritual: the static
# determinism lint (shadowlint, both passes), the full suite, the
# 20x-repeat determinism stress gate (tests/test_stress.py), the managed
# scale gate (SHADOW_TPU_SCALE=1, 145 OS processes), and an examples/
# end-to-end determinism smoke.  Mirrors the reference's determinism
# CTest gate (src/test/determinism/CMakeLists.txt).

.PHONY: test gate native smoke-faults smoke-examples lint-determinism \
	obs-smoke netobs-smoke flows-smoke turns-smoke \
	fusion-smoke checkpoint-smoke chaos-smoke sweep-smoke \
	multichip-smoke check-fixtures

test: native
	python -m pytest tests/ -q

# the suite runs -m 'not slow': the only slow-marked test re-runs the
# full two-pass shadowlint in a subprocess, which the lint-determinism
# step above has just done — no point tracing six kernels twice
gate: native check-fixtures lint-determinism
	python -m pytest tests/ -q -m 'not slow'
	SHADOW_TPU_STRESS=1 python -m pytest tests/test_stress.py -q
	SHADOW_TPU_SCALE=1 python -m pytest tests/test_managed_scale.py -q
	SHADOW_TPU_SCALE=1 JAX_PLATFORMS=cpu python -m pytest \
	  tests/test_hybrid_mp.py -q
	$(MAKE) smoke-examples
	$(MAKE) obs-smoke
	$(MAKE) netobs-smoke
	$(MAKE) flows-smoke
	$(MAKE) turns-smoke
	$(MAKE) fusion-smoke
	$(MAKE) checkpoint-smoke
	$(MAKE) chaos-smoke
	$(MAKE) sweep-smoke
	$(MAKE) multichip-smoke

# Runtime fixture dirs (hermdir/, shadow.data/, pytest caches, the XLA
# compile cache .jax_cache/, chip-run output chiprun_out/) are
# .gitignore'd; a force-add or an ignore regression would commit
# megabytes of run artifacts — fail the gate if any tracked path lands
# inside them.
check-fixtures:
	@bad=$$(git ls-files -- 'hermdir/*' 'shadow.data/*' '*.pyc' \
	  '.pytest_cache/*' '__pycache__/*' '.jax_cache/*' 'chiprun_out/*' \
	  '*/hermdir/*' '*/shadow.data/*' \
	  '*/.pytest_cache/*' '*/__pycache__/*'); \
	if [ -n "$$bad" ]; then \
	  echo "committed runtime fixtures detected:"; echo "$$bad"; exit 1; \
	fi

native:
	$(MAKE) -C native

# Static determinism & lane-parity analysis (shadow_tpu/analysis/):
# pass 1 lints the package AST for nondeterminism hazards, pass 2 traces
# the lane/stream kernels and audits the jaxpr.  Exit 1 on any finding
# not fixed, inline-suppressed, or justified in the versioned baseline
# (shadow_tpu/analysis/baseline.json).  See docs/analysis.md.
lint-determinism:
	JAX_PLATFORMS=cpu python -m shadow_tpu.analysis

# End-to-end fault-injection smoke: run the partition/heal example on the
# cpu backend twice and require byte-identical event logs + counters (the
# determinism contract of docs/faults.md).
smoke-faults:
	JAX_PLATFORMS=cpu python -m shadow_tpu examples/partition-heal.yaml \
	  --determinism-check --data-directory /tmp/shadow-tpu-smoke-faults.data

# Observability smoke for the gate: a metrics+trace-enabled phold run
# asserting a valid METRICS_*.json artifact, a Perfetto-loadable Chrome
# trace whose per-phase span sums match the report, and a parseable
# JSONL stream (docs/observability.md).
obs-smoke:
	JAX_PLATFORMS=cpu python scripts/obs_smoke.py

# Network-telemetry smoke for the gate: a phold run plus a faulted
# drop-heavy scenario, both through the CLI with --netobs, asserting a
# valid NETOBS_*.json artifact with nonzero drop-cause attribution and
# sent == delivered + drops conservation (docs/observability.md).
netobs-smoke:
	JAX_PLATFORMS=cpu python scripts/netobs_smoke.py

# Flowtrace smoke for the gate: a faulted loss-ramp stream run through
# the CLI with --flowtrace --netobs, asserting a valid FLOWS_*.json
# artifact, a sampled flow exhibiting the full send -> drop ->
# retransmit -> delivery lifecycle, and event counts conserving against
# the netobs counter plane (docs/observability.md).
flows-smoke:
	JAX_PLATFORMS=cpu python scripts/flows_smoke.py

# Device-turn-ledger smoke for the gate: a gate-scale managed hybrid run
# (relay chains, 2 syscall workers, CPU-JAX lanes) with --obs-turns
# semantics, asserting a valid TURNS_*.json artifact, the
# turns == sum(cause_counts) conservation law, and a non-empty
# fusable-run histogram (docs/observability.md).
turns-smoke: native
	JAX_PLATFORMS=cpu python scripts/turns_smoke.py

# k-window fusion smoke for the gate: the gate-scale managed hybrid run
# with the ledger on, asserting blocking device turns dropped >= 2x vs
# the PR 11 pinned 651-turn unfused baseline with the fused-turn
# conservation law green (docs/hybrid.md "k-window fusion law").
fusion-smoke: native
	JAX_PLATFORMS=cpu python scripts/fusion_smoke.py

# Crash-safety smoke for the gate: the checkpoint -> resume ->
# byte-compare round trip on the cpu and tpu backends through the CLI,
# with every retained checkpoint passing the checkpoint-inspect
# validator (docs/robustness.md "deterministic replay from the newest
# valid state").
checkpoint-smoke:
	JAX_PLATFORMS=cpu python scripts/checkpoint_smoke.py

# Kill-a-worker chaos smoke for the gate: the flagship mesh on the
# 4-worker MpCpuEngine with a seeded mid-run SIGKILL (respawn + journal
# replay, byte-identical) and a repeated-hang escalation to the serial
# oracle (also byte-identical) — docs/robustness.md "supervision model".
chaos-smoke:
	JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

# Fleet-sweep smoke for the gate: a 4-variant seed x loss grid on the
# flagship mesh batched through ONE compiled vmapped kernel, asserting
# per-scenario bit-identity vs serial reference runs, a single XLA
# trace, and nonzero cross-scenario drop variance (docs/sweep.md).
sweep-smoke:
	JAX_PLATFORMS=cpu python scripts/sweep_smoke.py

# Multi-chip smoke for the gate: 8 forced virtual CPU devices, phold
# facade bit-identity at 1/2/4/8 devices with netobs on, nonzero
# per-device work on every shard, mixed-mesh (stream tier) bit-identity
# at 8 devices, hybrid sync_stats transfer counts unchanged under a
# 2-device mesh, and the columnar 100k-host startup bound
# (docs/multichip.md).
multichip-smoke: native
	JAX_PLATFORMS=cpu python scripts/multichip_smoke.py

# Examples smoke for the gate: the phold classic, run twice with a
# run-twice determinism diff (bit-identical event orderings + counters).
smoke-examples:
	JAX_PLATFORMS=cpu python -m shadow_tpu examples/phold.yaml \
	  --determinism-check --stop-time 2s \
	  --data-directory /tmp/shadow-tpu-smoke-examples.data

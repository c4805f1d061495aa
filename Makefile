# tpu-cluster-capacity build/test entry points.
# Mirrors the reference's Makefile targets (build/test-unit/test-integration/
# test-e2e, /root/reference/Makefile:41-69) for a Python+C++ tree.

PY ?= python
CXX ?= g++
CXXFLAGS ?= -O2 -std=c++17 -fPIC -Wall
NATIVE_LIB := cluster_capacity_tpu/models/libccsnap.so

.PHONY: all build native lint concgate shardgate gates test-unit test-parity test-fuzz test-dist test-integration test-e2e bench multichip perfgate compilegate trend chaos profile-smoke soak soak-smoke clean verify-native ci

all: build

build: native

native: $(NATIVE_LIB)

# build beside the target and rename: a process that has the old library
# mapped keeps its inode
$(NATIVE_LIB): native/ccsnap.cpp
	$(CXX) $(CXXFLAGS) -shared -o $@.tmp $< && mv -f $@.tmp $@

# Format/boilerplate gate (reference: make verify-gofmt + golangci-lint +
# verify-boilerplate.sh, /root/reference/Makefile:41,54-66).  Self-contained:
# the image ships no Python linter.  jaxlint is the JAX/TPU antipattern
# analysis (trace-safety, recompile-hazard, host-sync, dtype-discipline)
# over cluster_capacity_tpu/ — see doc/architecture.md for the rule table.
lint:
	$(PY) tools/lint.py
	$(PY) -m tools.jaxlint
	$(PY) -m tools.concgate
	$(PY) -m tools.irgate

# Static concurrency gate (tools/concgate): lock-order graph, guarded-state
# discipline (tools/concgate/guards.json + cc- annotations), blocking-under-
# lock, thread-hostile JAX mutations, check-then-act windows — clears the
# runway for the multi-threaded daemon front-end (ROADMAP item 1).  Emits
# the CONCGATE.json artifact for tools/trend.
concgate:
	$(PY) -m tools.concgate --json-out CONCGATE.json

# Static sharding & per-device memory gate (tools/shardgate): lowers every
# sharded canonical entry under the {1x1, 2x4, 4x2, 8x1} mesh matrix on
# the virtual 8-device CPU backend WITHOUT executing, and enforces
# partition coverage (SP001), per-cell collective budgets (SP002,
# tools/shardgate/budgets.json), the scale-extrapolated per-shard memory
# model vs the pinned device HBM (SP003 — the 64k rung must be statically
# proven to fit), padding/divisibility invariants (SP004), and the
# host-readback audit over the drain/scan call graph (SP005).  Emits the
# SHARDGATE.json artifact for tools/trend.
shardgate:
	$(PY) -m tools.shardgate --json-out SHARDGATE.json

# The whole static-analysis suite in one verdict: jaxlint + irgate +
# concgate + shardgate, merged into GATES.json for tools/trend.
gates:
	$(PY) tools/gates.py

# Unit + behavioral suite (fake in-memory clusters; no hardware needed).
test-unit:
	$(PY) -m pytest tests/ -x -q

# Differential parity sweep vs the sequential CPU oracle.
test-parity:
	$(PY) -m pytest tests/test_oracle_parity.py tests/test_fast_path.py -q

# Full differential fuzz: 200 mixed-family seeds + 60 fused-kernel seeds.
test-fuzz:
	$(PY) -m pytest tests/test_fuzz.py tests/test_fused.py -m fuzz -q

# Chaos suite: deterministic fault injection into every device dispatch
# site; each injected OOM/hang/corruption must degrade down the runtime
# ladder to a bit-identical result (runtime/, tests/test_runtime.py).
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_runtime.py -q

# Multi-host DCN proof: 2 CPU processes over one 8-device mesh.
test-dist:
	$(PY) -m pytest tests/test_distributed.py -m dist -q

# Integration smoke: drive the CLI end-to-end against the example snapshot
# (the analog of test/integration-tests.sh's live-cluster grep).
test-integration:
	JAX_PLATFORMS=cpu $(PY) -m cluster_capacity_tpu cluster-capacity \
		--podspec examples/pod.yaml --snapshot examples/cluster-snapshot.yaml \
		--verbose | grep -q "Termination reason"
	JAX_PLATFORMS=cpu $(PY) -m cluster_capacity_tpu genpod \
		--snapshot examples/cluster-snapshot.yaml --namespace limited \
		| grep -q "cluster-capacity-stub-container"
	@echo integration OK

# e2e: multichip dryrun on a virtual 8-device CPU mesh + bench smoke.
test-e2e:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
		$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

bench:
	$(PY) bench.py

# Fleet-scale mesh-sharded sweep bench (tools/multichip_bench.py): N-1
# resilience sweep over a synthetic 2k-node fleet on a virtual 8-device
# CPU mesh; proves sharded == unsharded bit-identity twice (bounds-pruned
# pass + forced-solve pass) and records placements/s (total and per
# device) into MULTICHIP_r07.json for tools/perfgate and tools/trend.
# The interleaved multi-template rung runs at 2k (pinned) and 16k nodes
# by default; pass INTERLEAVE_SCALES=2000,16000,64000 for the slow 64k
# rung.
INTERLEAVE_SCALES ?= 2000,16000
multichip:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
		$(PY) -m tools.multichip_bench --out MULTICHIP_r07.json \
		--interleave-scales $(INTERLEAVE_SCALES)

# Throughput regression gate: latest committed BENCH_r*.json vs the pinned
# floors in tools/perfgate/pins.json (the perf counterpart of irgate's
# static cost budgets; regenerate with `python -m tools.perfgate
# --update-pins` and review the diff).
perfgate:
	$(PY) -m tools.perfgate

# Compile-budget gate (PG005): re-run the canonical irgate ladder entries
# from a cold compile cache, tally backend-compile seconds per entry
# (tools/perfgate/compilebudget.py), and gate against the compile_budgets
# pinned in tools/perfgate/pins.json — plus the steady-recompile invariant
# from the latest bench artifact.  Re-pin budgets with
# `python -m tools.perfgate --update-pins --compile-budget`.
compilegate:
	JAX_PLATFORMS=cpu $(PY) -m tools.perfgate --compile-budget \
		--json-out COMPILEGATE.json

# Cross-round metric history: merge the committed BENCH_r*.json /
# MULTICHIP_r*.json artifacts (and the gates' --json-out reports when
# present) into TREND.md + TREND.json, flagging >10% throughput drops
# between consecutive rounds.
trend:
	$(PY) -m tools.trend

# Deep-profiling smoke: `hypercc profile` in-process on a tiny cluster;
# asserts the attribution/calibration artifact schemas and that an
# injected fault yields a loadable flight-recorder bundle whose repro
# line carries the injection spec (obs/profile.py, obs/costmodel.py,
# obs/flight.py).
profile-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/profile_smoke.py

# Chaos soak of the capacity daemon (tools/soak.py): serve.Supervisor
# in-process under randomized fault injection + scripted snapshot churn,
# continuously asserting same-rung bit-identity, zero steady-state
# recompiles, breaker open/recover-within-cooldown, one flight bundle per
# classified fault, and bounded thread/ring/memo growth.  Writes
# SOAK_r07.json for tools/trend and perfgate's informational soak floors
# (PG006).  soak-smoke is the ~60s CI-sized run; the full soak turns the
# steady loop up.
soak:
	JAX_PLATFORMS=cpu $(PY) -m tools.soak

soak-smoke:
	JAX_PLATFORMS=cpu $(PY) -m tools.soak --smoke

# Full CI pipeline: lint + native + default suite + fuzz slice +
# integration + multichip dryrun, as configured in ci.yaml (the
# cloudbuild.yaml analog; tools/ci.py is the local step runner).
ci:
	$(PY) tools/ci.py

verify-native: native
	$(PY) -m pytest tests/test_native.py -q

clean:
	rm -f $(NATIVE_LIB)
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true

# Convenience targets; everything here is also runnable through pytest.

PY ?= python

.PHONY: test sanitize fuzz bench perfbench-rehearse lint rtlint jaxlint \
	xlacheck \
	check-metrics microbench-quick \
	databench-quick servebench-quick llmbench-quick tracebench-quick \
	releasebench-quick fleetbench-quick obsbench-quick \
	profbench-quick failoverbench-quick trainbench-quick leakcheck

test:
	$(PY) -m pytest tests/ -x -q

# Lint gate (SURVEY.md §4 CI row): dependency-free flake8/clang-format
# stand-in — ast checks for Python, g++ -fsyntax-only -Wall for C++ —
# plus rtlint in incremental mode: passes whose git-changed input set
# is empty are skipped (interprocedural passes still run over their
# full inputs when any input moved — partial summaries are unsound).
# CI and `make rtlint` run the full tree.  Incremental timings on
# this tree (13 passes): full 8.1s, doc-only change 0.07s ("running
# nothing"), one-file util/ change 4.6s (6 of 13 passes; the §4q
# compute-plane passes only wake when ops/models/parallel/serve-llm/
# bench inputs move).
lint: jaxlint
	$(PY) tools/lint.py
	$(PY) -m tools.rtlint --changed-only

# rtlint (DESIGN.md §4d/§4f/§4p/§4q): machine-enforces the GCS locking
# discipline (lock-order DAG, no blocking under leaf locks),
# guarded-field annotations, wire-protocol exhaustiveness,
# spawned-thread hygiene, metrics-catalog honesty, resource lifecycle
# (close/transfer on every exit path incl. exception edges), wire
# reply discipline (exactly-one-reply per two-way dispatch arm),
# interprocedural blocking-flow (REACTOR_SAFE / hot-arm / bounded-
# timeout policies + the BLOCK_BOUNDS static==runtime identity),
# session-FSM conformance over the old x new version matrix, and the
# compute-plane jaxlint passes (§4q: donation discipline, retrace
# triggers, host-sync freedom of step paths, mesh-axis/activation-rule
# drift).
# Fixture corpus: tests/rtlint_fixtures/.  `--list-rules` prints the
# catalog.  `--waiver-audit` (CI) additionally fails on stale waivers.
rtlint:
	$(PY) -m tools.rtlint

# Compute-plane passes alone (DESIGN.md §4q): donation / retrace /
# host-sync / mesh-axes over ray_tpu/{ops,models,parallel,serve/llm}
# and the benches, pinned to the lock_watchdog.py declaration tables
# (STEP_PATHS / DONATED / COMPILE_BUDGETS) and mesh.py's AXES /
# ACTIVATION_RULES.  Also rides `make lint` and full `make rtlint`.
jaxlint:
	$(PY) -m tools.rtlint --pass donation --pass retrace \
		--pass hostsync --pass meshaxes

# Runtime half of the §4q contract (the XLA hygiene oracle): the
# train-step + LLM-engine suite under RAY_TPU_XLA_WATCHDOG=1 — zero
# host transfers inside step regions, zero steady-state recompiles
# over the declared COMPILE_BUDGETS, injected violations raise with
# site + stack (leakcheck pattern).
xlacheck:
	JAX_PLATFORMS=cpu RAY_TPU_XLA_WATCHDOG=1 $(PY) -m pytest \
		tests/test_xla_watchdog.py -q -x

# Runtime half of the resource pass (DESIGN.md §4f): the leak-hammer
# suite under RAY_TPU_RESOURCE_SANITIZER=1 — N pulls/tasks/actor churns
# through a live cluster, then assert zero net leaked
# sockets/fds/mmaps/threads/conns at clean shutdown (acquisition stacks
# reported otherwise).
leakcheck:
	JAX_PLATFORMS=cpu RAY_TPU_RESOURCE_SANITIZER=1 $(PY) -m pytest \
		tests/test_resource_sanitizer.py -q -x

# Every built-in rtpu_* metric used in the tree must be declared in
# ray_tpu/util/metrics_catalog.py — and every declared one must be live
# (rtlint's metrics pass; also runs as part of `make lint`/`rtlint`).
check-metrics:
	$(PY) -m tools.rtlint --pass metrics

# ASAN + TSAN over the native slab store (SURVEY.md §5.2): longer runs
# than the in-suite smoke (tests/test_native_sanitizers.py).
sanitize:
	RTPU_SANITIZE_SECONDS=20 $(PY) -m pytest \
		tests/test_native_sanitizers.py -q -x

# Seedable protocol fuzz (lease/refcount/lineage state machines) at
# multi-million-step depth (the in-suite run uses a smaller budget).
fuzz:
	RTPU_SIM_STEPS=2000000 $(PY) -m pytest \
		tests/test_protocol_sim.py -q -x

# The benchmark that judges the repo is perfbench (BENCHMARK.json: seven
# cells on one TPU v5e; PERF.md, PERF_LEDGER.jsonl).  On the chip:
#   python3 -m perfbench.run --workload <cell> --seed N --seconds 51 --trace 0
# Here, one serving cell's control flow at a toy size, under CPU names:
perfbench-rehearse:
	JAX_PLATFORMS=cpu $(PY) -m perfbench.run \
		--workload gpt2-xl-1558m.serve-chat-steady --seed 1 \
		--seconds 5 --trace 0 --rehearse

# An older script no chip has run since the step was last edited (ROADMAP
# D7); it measures nothing the ledger holds.
bench:
	$(PY) bench.py

# Control-plane microbenchmark smoke (CI): --quick scale, asserts
# completion + sane serial-RT latency bounds, and leaves a JSON artifact
# (benchmarks/results/microbench_ci.json) for the uploader.
microbench-quick:
	JAX_PLATFORMS=cpu $(PY) -m ray_tpu.scripts.cli microbenchmark --quick \
		--assert-sane --json benchmarks/results/microbench_ci.json \
		--label ci

# Data-plane transfer smoke (CI): same-run A/B of the streamed pooled
# pull vs the in-tree legacy (fresh-dial chunked) path, asserts the
# streamed path isn't slower + the warm pool beats dial-per-pull, and
# leaves a JSON artifact for the uploader.
databench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/data_bench.py --pull --quick \
		--assert-sane --json benchmarks/results/databench_ci.json \
		--label ci

# Serve data-path smoke (CI): tiny BERT through the real controller →
# router → replica path, scale-up + replica-kill recovery asserted,
# JSON artifact for the uploader.
servebench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/serve_bench.py --quick \
		--assert-sane --json benchmarks/results/servebench_ci.json \
		--label ci

# Tracing-overhead smoke (CI): serial task RTs with the always-on
# observability layer (timeline + flight recorder + wire trace field at
# default sampling) vs fully off, interleaved A/B in one process;
# asserts <5% overhead and leaves a JSON artifact for the uploader.
tracebench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/trace_bench.py --quick \
		--assert-sane --json benchmarks/results/tracebench_ci.json \
		--label ci

# Raylet lease-protocol smoke (CI): 2 simulated nodes (NodeAgent
# processes with per-node local schedulers) on this host running the
# many_tasks workload with fixed simulated work; asserts completion and
# that the fleet actually parallelizes (>1 effective worker slot).
# The committed full-scale artifact (release_suite_r10.json, --nodes-ab)
# shows the node-count scaling claim.
releasebench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/release_suite.py --nodes 2 \
		--node-cpus 2 --tasks 60 --task-ms 10 --assert-sane \
		--json benchmarks/results/releasebench_ci.json --label ci

# Fleet elasticity smoke (CI): seeded preemption trace over the
# 100-simulated-node fleet against the real autoscaler bin-packing
# loop; asserts determinism from the seed, zero stranded demand, zero
# double-placements, and elastic re-mesh >= 2x the restart-from-
# checkpoint goodput.  The second run is the closed-loop autopilot A/B
# (DESIGN.md §4n): the same weather plus degradation episodes, the
# real reflex engine actuating — asserts the autopilot beats the
# reactive ratio, drains stay inside the rate budget (zero actuation
# storms), and the forecast reflex reduces demand lag.  Committed
# full-scale artifacts: benchmarks/results/fleet_bench_r11.json
# (reactive), fleet_bench_r15.json (closed loop).
fleetbench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/fleet_bench.py --quick \
		--assert-sane --json benchmarks/results/fleetbench_ci.json \
		--label ci
	JAX_PLATFORMS=cpu $(PY) benchmarks/fleet_bench.py --quick \
		--closed-loop --assert-sane \
		--json benchmarks/results/fleetbench_ci.json --label ci-closed

# Observability-history smoke (CI): serial task RTs with the head TSDB
# ingesting every snapshot + detectors ticking + live metrics_query
# traffic vs tsdb_enabled=0, interleaved A/B in one process; asserts
# <5% overhead on the serial-RT floor and leaves a JSON artifact for
# the uploader.  The committed full-scale artifact is
# benchmarks/results/obs_bench_r12.json.
obsbench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/obs_bench.py --quick \
		--assert-sane --json benchmarks/results/obsbench_ci.json \
		--label ci

# Continuous-profiler smoke (CI): serial task RTs with every process
# sampling at 10Hz + deltas riding the metrics cadence + live
# profile_query traffic vs profiler_enabled=0, interleaved A/B in one
# process; asserts <5% overhead on the serial-RT floor and leaves a
# JSON artifact for the uploader.  The committed full-scale artifact
# is benchmarks/results/prof_bench_r16.json.
profbench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/prof_bench.py --quick \
		--assert-sane --json benchmarks/results/profbench_ci.json \
		--label ci

# Head-failover smoke (CI): SIGKILL the primary GCS with a warm
# standby attached and tasks in flight; asserts ZERO lost tasks on
# every trial and sub-second promote-to-first-settled-task (best of
# <=3 trials — shared runners jitter), JSON artifact for the uploader.
# The committed full-scale artifact is
# benchmarks/results/failover_bench_r13.json.
failoverbench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/failover_bench.py --quick \
		--assert-sane --json benchmarks/results/failoverbench_ci.json \
		--label ci

# Overlap-scheduled train-step smoke (CI): interleaved A/B of the
# decomposed-collective-matmul + sequence-parallel step vs the
# un-overlapped GSPMD step on the same (data, seq, tensor) mesh;
# asserts loss-trajectory parity and (where device traces exist) that
# the overlapped step exposes no more collective time than the
# baseline.  The committed full-scale artifact is
# benchmarks/results/overlap_bench_r14.json.
trainbench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/train_bench.py --quick \
		--assert-sane --json benchmarks/results/trainbench_ci.json \
		--label ci

# LLM serving smoke (CI): the continuous-batching engine vs the naive
# request-level baseline on one seeded diurnal+burst trace; asserts the
# engine completes every request and does not lose to the baseline
# (the committed full-scale artifact shows the 2x goodput target).
llmbench-quick:
	JAX_PLATFORMS=cpu $(PY) benchmarks/llm_bench.py --ab --quick \
		--assert-sane --json benchmarks/results/llmbench_ci.json \
		--label ci

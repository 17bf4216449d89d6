# Runnable encodings of the project's standard invocations (tox.ini holds
# the same recipes for environments with tox installed; this image bakes
# in make but not tox). `make test` reproduces the full suite exactly as
# CI/judging runs it (-m "not slow", matching the tier-1 verify; run
# `pytest tests/ -q -m slow` for the excluded long-running set).

PY ?= python
TEST_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: test examples bench dryrun telemetry-check chaos-check perf-check \
	analysis-check supervise-check audit-check build-check race-check \
	batch-check ring-check scope-check serve-check query-check quake-check \
	sight-check churn-check mem-check dur-check

test:
	$(TEST_ENV) $(PY) -m pytest tests/ -q -m "not slow"

examples:
	$(TEST_ENV) $(PY) -m pytest tests/test_examples.py -q

# Telemetry plane: the dedicated test subset plus a ~5 s live sockets demo
# that scrapes its own Prometheus endpoint over HTTP (tox env "telemetry").
telemetry-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_telemetry.py -q
	$(TEST_ENV) $(PY) examples/telemetry_demo.py

# Chaos plane: the full chaos test subset — slow-marked partition-heal soak
# included — plus the reconnect/quarantine recovery tests and a live 4-node
# demo walking the fault menu (tox env "chaos").
chaos-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_chaos.py tests/test_phi.py -q
	$(TEST_ENV) $(PY) examples/chaos_demo.py

# Frontier fast path + bit-packed state: the full equivalence sweep
# (frontier ≡ dense, bitset ≡ bool, donation, slow-marked edge-gather
# bench included) plus a small-n smoke of the bench 1M stage on the CPU
# backend — proves the frontier method column and its occupancy
# attribution end to end (tox env "perf").
perf-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_frontier.py -q
	$(TEST_ENV) BENCH_N_1M=4000 BENCH_CACHE=0 BENCH_TELEMETRY_DIR=/tmp \
		$(PY) bench.py --stage 1m

# Supervised execution plane: watchdog/store/crash-recovery tests (the
# slow-marked double-SIGKILL subprocess soak included) plus a live demo
# that preempts a PRNG-dependent run twice, corrupts a checkpoint, and
# proves bit-identical resume (tox env "supervise").
supervise-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_supervise.py -q
	$(TEST_ENV) $(PY) examples/supervised_run_demo.py

# graftlint + graftaudit gates: zero non-baselined findings at BOTH
# layers — source AST (retrace/host-sync/lock discipline) and compiled IR
# (jaxpr rules, signature parity, donation aliasing, cost ratchet, AND
# the graftmem memory ratchet/model-drift gate, which rides the full
# graftaudit run by default) — then both test subsets (tox env
# "analysis").
analysis-check:
	$(PY) -m p2pnetwork_tpu.analysis p2pnetwork_tpu/
	$(PY) -m p2pnetwork_tpu.analysis.ir
	$(TEST_ENV) $(PY) -m pytest tests/test_analysis.py -q

# graftaudit gate alone: the device-free IR audit over the full lowering
# registry (the CLI pins JAX_PLATFORMS=cpu + the 8-device virtual mesh
# itself), then its test subset — rule fixtures, parity gate, donation
# audit, budgets round-trip/ratchet (tox env "audit").
audit-check:
	$(PY) -m p2pnetwork_tpu.analysis.ir
	$(TEST_ENV) $(PY) -m pytest tests/test_iraudit.py -q

# graftmem static memory plane: the full graftaudit gate (the
# membudgets ratchet + analytic/compiled model-drift check ride it by
# default), the north-star capacity plan evaluated from the checked-in
# coefficients (fails loudly when membudgets.json lacks a capacity
# model), then the graftmem test subset — liveness-walk parity, ratchet
# arithmetic, planner extrapolation, the SimService hbm_budget_bytes
# 429 gate (tox env "mem").
mem-check:
	$(PY) -m p2pnetwork_tpu.analysis.ir
	$(PY) -m p2pnetwork_tpu.analysis.ir --plan
	$(TEST_ENV) $(PY) -m pytest tests/ -q -m mem

# graftrace gate: the deterministic-concurrency scenario battery (every
# builtin scenario × K seeded schedules, zero non-baselined races or
# deadlocks) plus its test subset — scheduler replay determinism, the
# racy/clean twin per HB edge kind, detector internals, CLI exit codes
# (tox env "race").
race-check:
	$(TEST_ENV) $(PY) -m p2pnetwork_tpu.analysis.race
	$(TEST_ENV) $(PY) -m pytest tests/test_graftrace.py -q

# Incremental builds + IO-aware layouts: delta/rebuild bit-identity
# property sweep (native + numpy fallback), reorder-pass parity, layout
# cache, and the CI perf ratchet — a 1%-edge delta at 1M-edge scale must
# beat the from-scratch rebuild >= 10x on CPU (ratio-based, no
# wall-clock thresholds, no TPU; tox env "buildperf").
build-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_layout_delta.py -q
	$(TEST_ENV) $(PY) -m pytest tests/ -q -m buildperf

# Batched message plane: lane-packed kernel parity, MessageBatch
# lifecycle (admission/retire/freeze), batched-vs-sequential bit
# identity, donation, and the slow-marked B=1024 aggregate-throughput
# ratchet (>= 20x vs sequential single-message runs, ratio-based on
# CPU; tox env "batch").
batch-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_messagebatch.py -q

# Comm seam: the ppermute vs Pallas ring-DMA halo backends must be
# bit-identical on every sharded protocol (interpret mode on the
# 8-device virtual CPU mesh), the lane-word batched path included, and
# the ICI accounting must price the DMA hops like the ppermute hops
# they replace (tox env "ring").
ring-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_ring.py -q

# graftscope observability plane: flight-recorder bit-parity across
# engine/batch/sharded (both comm backends), trace-plane span trees +
# Perfetto export schema, history ring + /history endpoint, and the
# profiler satellite (tox env "scope"; the slow-marked
# 1.10x overhead ratchet runs with -m 'scope and slow').
scope-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_graftscope.py -q

# graftserve serving plane: submit/poll/stream lifecycle, admission
# pacing + quotas + structured load shedding, seeded-traffic
# determinism, preempt/resume bit-identity, and the HTTP endpoints
# riding the telemetry httpd (tox env "serve"; the slow-marked
# 1k-concurrent-lane 100k-node soak runs with -m 'serve and slow').
serve-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_serve.py -q

# graftquake device-plane chaos: seeded halo-hop fault injection
# (byte-replayable, chunked == unchunked via fault_round0, bit-identical
# across both comm backends), one-shot chip-loss/wedge dispatch faults,
# integrity checks + RetryPolicy/Healer recovery bit-identity across
# engine/sharded/graftserve, and the store/bench satellites (tox env
# "quake"; the slow-marked 100k chaos soak + 1.10x integrity-check
# overhead ratchet run with -m 'quake and slow').
quake-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_graftquake.py -q

# graftsight observability plane: ticket-scoped correlated tracing
# (one Perfetto tree per ticket lifecycle, chaos included), the
# tick-phase profiler + /dashboard endpoint, the SLO burn-rate engine
# and its AIMD admission consumption, and the tracer-on bit-identity
# pins (tox env "sight"; the slow-marked 1.10x serve-tick overhead
# ratchet runs with -m 'sight and slow').
sight-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_graftsight.py -q

# graftchurn live-growth plane: bit-identical overlay growth with the
# O(log K) geometric repad schedule, checkpoint/supervised resume
# across a repad, mid-service grow/delta mutations (zero admitted
# lanes dropped, untouched tickets bit-identical), sidecar growth
# replay, and seeded churn storms (tox env "churn"; the slow-marked
# 100k churn-under-chaos soak runs with -m 'churn and slow').
churn-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_graftchurn.py -q

# graftdur durability plane: write-ahead intent journal (CRC records,
# torn-tail fuzz at every byte offset, segment rotation/compaction),
# crash-seam resume bit-identity (mid-tick, mid-sidecar-publish,
# mid-journal-append), DurabilityLost shedding + HTTP 503s, hot-standby
# promote + FencedEpoch fencing (tox env "dur"; the slow-marked
# crash-storm campaign and the 1.10x fsync=tick overhead ratchet run
# with -m 'dur and slow').
dur-check:
	$(TEST_ENV) $(PY) -m pytest tests/ -q -m dur

# Batched query lanes: byte-budget gate, lane-kernel parity, the three
# family identity sweeps (min-plus vs Bellman-Ford reference, DHT vs the
# numpy greedy walk, push-sum float-op-order vs models/pushsum.py), the
# query engine loop + observability pins (tox env "query"; the
# slow-marked 10x aggregate ratchets run with -m 'query and slow').
query-check:
	$(TEST_ENV) $(PY) -m pytest tests/test_querybatch.py -q

# North-star benchmark on the real TPU chip (fails without one).
bench:
	$(PY) bench.py

# Compile-check the single-chip entry and the multi-chip sharded training
# step on an 8-device virtual mesh (what the driver validates).
dryrun:
	$(TEST_ENV) $(PY) -c "import __graft_entry__ as g; fn, args = g.entry(); fn(*args); g.dryrun_multichip(8); print('dryrun OK')"

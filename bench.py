"""North-star benchmark (BASELINE.json): 1M-node Watts–Strogatz single-source
flood to 99% coverage, one chip, whole run device-side (lax.while_loop — zero
host round-trips per round), plus the 10M-node scale config.

Prints the headline JSON record — {"metric", "value", "unit", "vs_baseline",
...} — as its LAST stdout line. ``value`` is the wall-clock seconds of the
best aggregation path at 1M; ``vs_baseline`` is (1 s north-star target) /
value, so > 1 beats the target; ``scale_10M`` carries the 10M-node
result.

Process layout (one process per chip):

- the parent never imports JAX. It runs each measuring stage
  (``--stage 1m`` / ``--stage 10m``) in its own child process under a
  hard timeout, one after the other, so only one process ever wants the
  chip;
- a stage that finds no TPU fails, unless ``JAX_PLATFORMS=cpu`` was set
  explicitly (the tests do). A failing method or column fails its stage,
  and a failed stage makes the run exit non-zero with an error record
  that carries no ``value``;
- the 1M record is printed the moment the 1M stage returns, before the
  10M stage starts; on success the merged record (1M + scale_10M) is the
  last line.

Graph construction is the dominant host-side cost: built graphs are
persisted once through the shared content-addressed layout store
(``sim/layoutcache.py``, which generalized this file's original private
cache) under ``bench_cache/`` and reloaded on later runs.
``BENCH_CACHE=0`` disables; a corrupt/missing cache file falls back to a
fresh build, reported as a structured ``bench_cache_miss`` warning event
(stderr JSONL, telemetry-schema) plus a
``bench_cache_miss_total{reason=...}`` counter — never swallowed. Cold
builds additionally publish the per-phase attribution of where the build
seconds went (dedup/sort/tables/CSR/layouts/reorder — sim/graph.py) as
``build_phases`` in the stage telemetry artifact.

Telemetry (telemetry/): each measuring stage writes a per-stage artifact —
``BENCH_TELEMETRY.json`` for the 1M headline stage (``BENCH_TELEMETRY_10M
.json`` for the scale row; override dir via BENCH_TELEMETRY_DIR) — carrying
graph-build / cache / compile / run / transfer timings and the full
registry snapshot; the ``frontier`` method column additionally attributes
per-round frontier occupancy (``frontier_occupancy_per_round``) so the
sparse/dense crossover constant (ops/frontier.py) is measured, not
guessed. The 1M stage additionally publishes the ``batched`` message-plane
column: B concurrent floods advanced by ONE compiled program per round
(models/messagebatch.py lane packing + engine.run_batch_until_coverage)
on the 100k-node WS class, with ``batch_completion_rounds_p99`` and the
aggregate-throughput ratio vs sequential single-message runs
(BENCH_BATCH_B=1024 / BENCH_BATCH_N=100000 / BENCH_BATCH=0 to disable),
and the ``queries`` column: the three non-boolean batched query families
(models/querybatch.py — min-plus route lookups and push-sum aggregations
on the batched WS class, DHT greedy lookups on a 100k-node chord
overlay), each with lanes/s, completion-rounds p50/p99 and the aggregate
speedup vs warm sequential capacity-1 runs (BENCH_QUERY_K_MINPLUS=64 /
_PUSHSUM=32 / _DHT=2048, BENCH_QUERY_DHT_N=100000, BENCH_QUERIES=0 to
disable). Each measuring stage runs inside an ``analysis.retrace_guard``
with a per-stage jit compile budget (BENCH_COMPILE_BUDGET_1M/_10M):
a breach — something retracing mid-measurement — emits a structured
``bench_recompile_budget_breach`` warning plus the
``bench_recompile_total{stage}`` counter, never a failed bench. The
last-line headline JSON record is unchanged.

Reference anchor: the reference implementation moves one message per peer per
10 ms poll tick per Python thread [ref: p2pnetwork/nodeconnection.py:220];
simulating this workload there would take hours — it publishes no numbers
(BASELINE.md), so the driver-set 1 s target is the baseline.
"""

import contextlib
import json
import os
import subprocess
import sys
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

from p2pnetwork_tpu import telemetry  # noqa: E402 — stdlib-only, no jax


def _warn_event(name: str, **data) -> None:
    """Structured warning on stderr in the shared telemetry JSONL schema
    (export.event_record) — greppable by the driver, parseable by tools,
    and mirrored as a counter by the callers that need one."""
    rec = telemetry.event_record(name, time.time(), data=data)
    print("# WARN " + json.dumps(rec), file=sys.stderr, flush=True)


def time_flood(graph, method: str, *, target: float, max_rounds: int,
               reps: int = None, occupancy_attribution: bool = False):
    """Returns ``(best_seconds, last_out, timing)`` where ``timing`` splits
    the wall clock into the warmup (compile-carrying) call and the measured
    reps — the per-stage attribution BENCH_TELEMETRY.json reports.
    ``reps`` defaults to BENCH_REPS (5).

    ``occupancy_attribution=True`` re-runs the measured round count once
    through the scan engine and attaches the per-round
    ``frontier_occupancy`` series to ``timing`` — the measurement that
    lets the frontier crossover constant (ops/frontier.py) be re-fit from
    real runs instead of guessed."""
    import jax
    import numpy as np

    from p2pnetwork_tpu.models.adaptive_flood import AdaptiveFlood
    from p2pnetwork_tpu.models.flood import Flood
    from p2pnetwork_tpu.sim import engine

    if reps is None:
        reps = int(os.environ.get("BENCH_REPS", "5"))
    if method.startswith("adaptive"):
        # "adaptive-<k>": frontier-sparse rounds under k, dense hybrid above
        # (models/adaptive_flood.py) — bit-identical results to Flood.
        k = int(method.split("-")[1])
        protocol = AdaptiveFlood(source=0, method="hybrid", k=k)
    elif method == "frontier":
        # lax.cond-compacted sparse rounds with dense fallback
        # (ops/frontier.py), packed carry state — bit-identical to Flood.
        protocol = Flood(source=0, method="frontier", bitset=True)
    else:
        protocol = Flood(source=0, method=method)
    key = jax.random.key(0)

    def once():
        # run_until_coverage itself blocks on a real device->host transfer
        # of the packed run summary (engine._unpack_summary), which ends
        # the timed region.
        state, out = engine.run_until_coverage(
            graph, protocol, key, coverage_target=target, max_rounds=max_rounds
        )
        return out

    t0 = time.perf_counter()
    out = once()  # compile + warm up
    warmup_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = once()
        times.append(time.perf_counter() - t0)
    timing = {"warmup_s": round(warmup_s, 4),
              "measure_s": round(sum(times), 4), "reps": reps}
    if occupancy_attribution:
        # One scan-engine pass at the measured round count: per-round
        # frontier occupancy, straight off the device-side stat.
        _, stats = engine.run(graph, protocol, key, int(out["rounds"]))
        timing["frontier_occupancy_per_round"] = [
            round(float(v), 6)
            for v in np.asarray(stats["frontier_occupancy"])]
    return min(times), out, timing


# --------------------------------------------------------------- graph cache

def _cache_dir():
    return os.environ.get("BENCH_CACHE_DIR", os.path.join(_HERE, "bench_cache"))


def _layout_fingerprint():
    """Hash of the sources that determine a built graph's arrays and kernel
    layouts, via the shared library-level store (sim/layoutcache.py — its
    DEFAULT_SOURCES cover the graph builder, reorder pass, topology
    generators, kernel layouts, native sort/merge kernels and the
    serializer). bench.py itself is folded in on top: the cache NAME only
    carries n, so an edit to a build call's other kwargs (k, p, layout
    flags) must also invalidate."""
    from p2pnetwork_tpu.sim import layoutcache

    return layoutcache.fingerprint(
        extra_sources=(os.path.join(_HERE, "bench.py"),))


def _cached_graph(name: str, build):
    """Load ``bench_cache/<name>.npz`` if present, else build + persist —
    the shared content-addressed layout store (sim/layoutcache.py) keyed
    under BENCH_CACHE_DIR.

    Returns ``(graph, build_seconds, from_cache)``. Any cache failure
    (missing file, version skew, truncated write) falls back to a fresh
    build — the cache can only ever make the bench faster, never wrong:
    topology is seed-determined, so cached and rebuilt graphs are
    identical arrays. Every fallback is REPORTED: a structured
    ``bench_cache_miss`` warning event on stderr plus a
    ``bench_cache_miss_total{reason=missing|corrupt|disabled}`` counter —
    a driver round quietly paying a 49 s rebuild is a diagnosis, not noise.
    """
    from p2pnetwork_tpu.sim import layoutcache

    misses = telemetry.default_registry().counter(
        "bench_cache_miss_total",
        "Graph-cache misses by cause; every miss costs a full rebuild.",
        ("reason",))

    def on_miss(reason, path, error):
        misses.labels(reason=reason).inc()
        data = {"reason": reason, "graph": name}
        if reason != "disabled":
            data["path"] = path
        if error is not None:
            data["error"] = error
        _warn_event("bench_cache_miss", **data)

    return layoutcache.cached_graph(
        name, build, cache_dir=_cache_dir(),
        extra_sources=(os.path.join(_HERE, "bench.py"),),
        enabled=os.environ.get("BENCH_CACHE", "1") != "0",
        on_miss=on_miss,
        log=lambda msg: print(f"# {msg}", file=sys.stderr, flush=True))


def time_batch_flood(graph, *, B: int, target: float, max_rounds: int,
                     reps: int = None, seq_sample: int = 4):
    """The batched message plane's bench column: advance ``B`` concurrent
    floods (random distinct-ish sources, seeded) through ONE compiled
    program per round (`engine.run_batch_until_coverage`), and price the
    same B messages as SEQUENTIAL single-message engine runs from a
    measured sample of ``seq_sample`` of them — the aggregate-throughput
    ratio (sequential-estimate / batched wall) is the number ROADMAP item
    2a targets (>= 20x at B=1024 on the 100k-node class). Returns the
    column dict BENCH_TELEMETRY.json publishes, ``batch_completion_
    rounds_p99`` included."""
    import jax
    import numpy as np

    from p2pnetwork_tpu.models.flood import Flood
    from p2pnetwork_tpu.models.messagebatch import BatchFlood
    from p2pnetwork_tpu.sim import engine

    if reps is None:
        reps = int(os.environ.get("BENCH_REPS", "5"))
    rng = np.random.default_rng(0)
    n_live = graph.n_nodes
    sources = rng.integers(0, n_live, size=B).astype(np.int32)
    proto = BatchFlood(method="auto")
    key = jax.random.key(0)

    def once():
        batch = proto.init(graph, sources, coverage_target=target)
        return engine.run_batch_until_coverage(
            graph, proto, batch, key, max_rounds=max_rounds)

    t0 = time.perf_counter()
    _, out = once()  # compile + warm up
    warmup_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _, out = once()
        times.append(time.perf_counter() - t0)
    batch_s = min(times)

    # Sequential baseline: a seeded sample of the SAME messages run one
    # at a time through the single-message engine (what production pays
    # today), extrapolated to B — measuring all B sequentially would
    # take B x the batched run's win, which is the point. Each sampled
    # source runs once UNTIMED first: Flood(source) is a static jit arg,
    # so a cold run carries a per-source recompile — charging compile
    # time to the baseline would flatter the ratio.
    seq = []
    for s in sources[:max(seq_sample, 1)]:
        proto_s = Flood(source=int(s))
        engine.run_until_coverage(graph, proto_s, key,
                                  coverage_target=target,
                                  max_rounds=max_rounds)
        t0 = time.perf_counter()
        _, single = engine.run_until_coverage(
            graph, proto_s, key, coverage_target=target,
            max_rounds=max_rounds)
        seq.append(time.perf_counter() - t0)
        del single
    seq_per_run = sum(seq) / len(seq)
    seq_est = seq_per_run * B
    lane_rounds = int(np.sum(out["lane_rounds"]))
    return {
        "B": int(B),
        "n_nodes": graph.n_nodes,
        "best_s": round(batch_s, 6),
        "warmup_s": round(warmup_s, 4),
        "reps": reps,
        "rounds": int(out["rounds"]),
        "completed": int(out["completed"]),
        "active_lanes_end": int(out["active_lanes"]),
        "messages": int(out["messages"]),
        "batch_completion_rounds_p99": out.get("completion_rounds_p99"),
        "batch_completion_rounds_p50": out.get("completion_rounds_p50"),
        "batch_occupancy_mean": round(float(out["occupancy_mean"]), 6),
        "lane_rounds_per_s": round(lane_rounds / batch_s, 1),
        "msgs_per_sec": round(int(out["messages"]) / batch_s, 1),
        "seq_sample_runs": len(seq),
        "seq_per_run_s": round(seq_per_run, 6),
        "aggregate_speedup_vs_sequential": round(seq_est / batch_s, 2),
    }


# -------------------------------------------------------------------- stages

def _graph_spec_batch():
    """(n, cache name, build thunk) for the batched column's 100k-node WS
    class (ROADMAP 2a's target shape). Separate cache entry from the 1M
    headline graph — different n, different layout kwargs (the batched
    kernels ride the neighbor table + source CSR; no MXU layouts)."""
    from p2pnetwork_tpu.sim import graph as G

    n = int(os.environ.get("BENCH_BATCH_N", 100_000))
    return n, f"ws_n{n}_k10_p0.1_s0_batchcol", lambda: G.watts_strogatz(
        n, 10, 0.1, seed=0, source_csr=True)


def bench_batched():
    """The ``batched`` bench column: B concurrent floods through the
    lane-packed message plane on the 100k-node WS class."""
    B = int(os.environ.get("BENCH_BATCH_B", 1024))
    _, name, build = _graph_spec_batch()
    g, build_s, cached = _cached_graph(name, build)
    col = time_batch_flood(g, B=B, target=0.99, max_rounds=64)
    col["graph_build_s"] = round(build_s, 2)
    col["graph_cached"] = cached
    print(f"# batched B={B}: {col['best_s']*1000:.1f} ms/run, "
          f"rounds={col['rounds']}, p99={col['batch_completion_rounds_p99']}"
          f", aggregate x{col['aggregate_speedup_vs_sequential']} vs "
          f"sequential", file=sys.stderr, flush=True)
    return col


def time_durability(graph, *, cap: int, chunk: int, ticks: int,
                    rate: float, seed: int = 0,
                    policies=("off", "tick", "record"),
                    replay_records: int = 1000) -> dict:
    """The ``durability`` slice of the serving column (graftdur): what
    the write-ahead journal costs per fsync policy, and how fast a
    recovery scan replays.

    Drives the SAME seeded traffic schedule four times over a scratch
    checkpoint store — once unjournaled (the baseline: checkpoint
    cadence included, so the ratio isolates the JOURNAL, not the
    store), once per fsync policy — and reports
    ``overhead_ratio = journaled_wall / unjournaled_wall``. The
    slow-marked ratchet (tests/test_graftdur.py) pins fsync=tick at
    <= 1.10x. ``replay_scan_ms_per_1k`` times the torn-tail-tolerant
    segment scan (:func:`serve.journal.read_records`) over a
    synthetic ``replay_records``-record journal — the recovery-path
    latency a resume pays per 1k acknowledged intents."""
    import shutil
    import tempfile

    from p2pnetwork_tpu.serve import SimService, TrafficPattern
    from p2pnetwork_tpu.serve import drive as serve_drive
    from p2pnetwork_tpu.serve import generate as serve_generate
    from p2pnetwork_tpu.serve.journal import Journal, read_records

    pattern = TrafficPattern(ticks=ticks, rate=rate,
                             coverage_target=0.99)
    sched = serve_generate(pattern, graph.n_nodes, seed=seed)

    def one_drive(journal, fsync):
        d = tempfile.mkdtemp(prefix="bench_dur_")
        try:
            svc = SimService(graph, capacity=cap, queue_depth=cap,
                             chunk_rounds=chunk, seed=seed, store=d,
                             journal=journal, journal_fsync=fsync)
            t0 = time.perf_counter()
            out = serve_drive(svc, sched)
            wall = time.perf_counter() - t0
            stats = svc.stats()
            svc.close()
            return wall, out, stats
        finally:
            shutil.rmtree(d, ignore_errors=True)

    # Warm the engine program (and the store/sidecar write path) before
    # any timed drive: called standalone — e.g. by the ratchet test —
    # the first drive would otherwise charge one-time XLA compile to
    # whichever arm runs first and invert the ratio.
    one_drive(False, "tick")
    base_wall, base_out, _ = one_drive(False, "tick")
    col = {
        "ticks": ticks, "rate": rate,
        "offered": base_out["submitted"] + len(base_out["shed"]),
        "unjournaled_wall_s": round(base_wall, 4),
        "fsync": {},
    }
    for pol in policies:
        wall, _, stats = one_drive(True, pol)
        jstats = stats.get("journal") or {}
        col["fsync"][pol] = {
            "wall_s": round(wall, 4),
            "overhead_ratio": round(wall / max(base_wall, 1e-9), 4),
            "appends": jstats.get("appended"),
            "fsyncs": jstats.get("fsyncs"),
        }
    jd = tempfile.mkdtemp(prefix="bench_dur_replay_")
    try:
        j = Journal(jd, fsync="off")
        for i in range(int(replay_records)):
            j.append("submit", ticket=f"t{i:08d}", source=i % 1024,
                     tenant="default", round=i, tick=i // 8)
        j.close()
        t0 = time.perf_counter()
        records, corrupt = read_records(jd)
        scan_s = time.perf_counter() - t0
        assert len(records) == int(replay_records) and corrupt == 0
        col["replay_scan_ms_per_1k"] = round(
            scan_s * 1000.0 * 1000.0 / max(int(replay_records), 1), 3)
    finally:
        shutil.rmtree(jd, ignore_errors=True)
    return col


def bench_serving():
    """The ``serving`` bench column: seeded open-loop traffic
    (serve/traffic.py — Poisson arrivals, hot-key skew, diurnal bursts)
    through the admission-controlled SimService on the batched column's
    100k-node WS class, driven synchronously (deterministic). Publishes
    the serving-SLO numbers ROADMAP item 2 asks for: sustained lanes/s
    (completed tickets over the drive wall), submit→completion p50/p99
    in engine rounds (queue wait included), peak concurrent lanes, and
    the shed rate of the structured load-shedding path. Env seams:
    BENCH_SERVE_CAP (lane capacity, default 1024), BENCH_SERVE_TICKS,
    BENCH_SERVE_RATE (arrivals/tick; default oversubscribes capacity so
    the queue and shed path engage), BENCH_SERVE_CHUNK (engine rounds
    per tick)."""
    from p2pnetwork_tpu.serve import SimService, TrafficPattern
    from p2pnetwork_tpu.serve import drive as serve_drive
    from p2pnetwork_tpu.serve import generate as serve_generate

    cap = int(os.environ.get("BENCH_SERVE_CAP", 1024))
    ticks = int(os.environ.get("BENCH_SERVE_TICKS", 16))
    rate = float(os.environ.get("BENCH_SERVE_RATE", cap / 3.0))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", 4))
    _, name, build = _graph_spec_batch()
    g, build_s, cached = _cached_graph(name, build)
    pattern = TrafficPattern(
        ticks=ticks, rate=rate, hot_fraction=0.5, hot_keys=32,
        diurnal_amplitude=0.3, diurnal_period=max(ticks / 2.0, 1.0),
        burst_prob=0.125, burst_mult=3.0, coverage_target=0.99)
    sched = serve_generate(pattern, g.n_nodes, seed=0)
    # Warm the (capacity, chunk_rounds) engine program on a scratch
    # service first — the batched column warms up the same way; a cold
    # drive would charge one-time XLA compile to the SLO headline.
    warm = SimService(g, capacity=cap, queue_depth=cap, chunk_rounds=chunk,
                      seed=0)
    warm.submit(0)
    warm.tick()
    warm.close()
    svc = SimService(g, capacity=cap, queue_depth=cap, chunk_rounds=chunk,
                     seed=0)
    t0 = time.perf_counter()
    out = serve_drive(svc, sched)
    wall = time.perf_counter() - t0
    stats = svc.stats()
    offered = out["submitted"] + len(out["shed"])
    col = {
        "capacity": svc.capacity,
        "n_nodes": g.n_nodes,
        "ticks": ticks + out["drain_ticks"],
        "chunk_rounds": chunk,
        "wall_s": round(wall, 4),
        "offered": offered,
        "submitted": out["submitted"],
        "completed": out["completed"],
        "shed": len(out["shed"]),
        "shed_rate": round(len(out["shed"]) / max(offered, 1), 4),
        "peak_concurrent_lanes": out["peak_concurrent_lanes"],
        "executed_rounds": out["executed_rounds"],
        "sustained_lanes_per_s": round(out["completed"] / wall, 1),
        "submit_to_completion_rounds_p50":
            stats.get("completion_rounds_p50"),
        "submit_to_completion_rounds_p99":
            stats.get("completion_rounds_p99"),
        "graph_build_s": round(build_s, 2),
        "graph_cached": cached,
        # graftsight tick-phase profile: where the driven ticks spent
        # their wall (retire/admit/dispatch/harvest/checkpoint) — the
        # same document /dashboard publishes live.
        "tick_phases": svc.tick_phases(),
    }
    print(f"# serving cap={svc.capacity}: {col['sustained_lanes_per_s']} "
          f"lanes/s sustained, peak {col['peak_concurrent_lanes']} "
          f"concurrent, p99={col['submit_to_completion_rounds_p99']} "
          f"rounds, shed_rate={col['shed_rate']}",
          file=sys.stderr, flush=True)
    # graftdur durability slice: journal overhead per fsync policy +
    # recovery-scan latency, on a reduced drive (BENCH_DUR=0 disables).
    if os.environ.get("BENCH_DUR", "1") != "0":
        dur_ticks = int(os.environ.get("BENCH_DUR_TICKS", 8))
        dur_rate = float(os.environ.get("BENCH_DUR_RATE", cap / 8.0))
        col["durability"] = time_durability(
            g, cap=cap, chunk=chunk, ticks=dur_ticks, rate=dur_rate,
            seed=0)
        tick_ratio = col["durability"]["fsync"]["tick"]["overhead_ratio"]
        print(f"# durability: fsync=tick x{tick_ratio} vs unjournaled, "
              f"replay {col['durability']['replay_scan_ms_per_1k']} "
              f"ms/1k records", file=sys.stderr, flush=True)
    return col


def _graph_spec_query_dht():
    """(n, cache name, build thunk) for the query column's DHT overlay:
    a chord graph — the structured topology whose fingers the greedy
    lookup lanes actually chase (a lookup on the WS class would mostly
    measure stalls)."""
    from p2pnetwork_tpu.sim import graph as G

    n = int(os.environ.get("BENCH_QUERY_DHT_N", 100_000))
    return n, f"chord_n{n}_querycol", lambda: G.chord(n)


def time_query_family(graph, proto, make_batch, make_single, *, K: int,
                      max_rounds: int = 256, reps: int = None,
                      seq_sample: int = 3) -> dict:
    """One query family's bench row: run the K-lane batch through
    ``engine.run_queries_until_done`` (one compiled program per round)
    and price the same K queries as WARM sequential capacity-1 runs of
    the SAME family — one query per engine call, what a serving loop
    without lane batching would pay — extrapolated from ``seq_sample``
    measured runs. ``make_batch()`` / ``make_single(i)`` build the
    admitted batches (each run re-admits, so donation invalidating the
    carry between reps is fine)."""
    import jax

    from p2pnetwork_tpu.sim import engine

    if reps is None:
        reps = int(os.environ.get("BENCH_REPS", "5"))
    key = jax.random.key(0)

    def once():
        return engine.run_queries_until_done(
            graph, proto, make_batch(), key, max_rounds=max_rounds)

    t0 = time.perf_counter()
    _, out = once()  # compile + warm up
    warmup_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _, out = once()
        times.append(time.perf_counter() - t0)
    batch_s = min(times)

    # Warm the capacity-1 program once untimed (its own compile), then
    # measure the sequential sample (clamped to K — a tiny lane-count
    # knob must shrink the sample, not index past the query list).
    engine.run_queries_until_done(graph, proto, make_single(0), key,
                                  max_rounds=max_rounds)
    seq = []
    for i in range(max(min(seq_sample, int(K)), 1)):
        t0 = time.perf_counter()
        engine.run_queries_until_done(graph, proto, make_single(i), key,
                                      max_rounds=max_rounds)
        seq.append(time.perf_counter() - t0)
    seq_per_run = sum(seq) / len(seq)
    return {
        "K": int(K),
        "n_nodes": graph.n_nodes,
        "best_s": round(batch_s, 6),
        "warmup_s": round(warmup_s, 4),
        "reps": reps,
        "rounds": int(out["rounds"]),
        "completed": int(out["completed"]),
        "active_lanes_end": int(out["active_lanes"]),
        "messages": int(out["messages"]),
        "completion_rounds_p50": out.get("completion_rounds_p50"),
        "completion_rounds_p99": out.get("completion_rounds_p99"),
        "lanes_per_s": round(int(out["completed"]) / batch_s, 1),
        "seq_sample_runs": len(seq),
        "seq_per_run_s": round(seq_per_run, 6),
        "aggregate_speedup_vs_sequential": round(
            seq_per_run * K / batch_s, 2),
    }


def bench_queries():
    """The ``queries`` bench column (ROADMAP item 3): the three
    non-boolean batched query families — min-plus route lookups and
    push-sum aggregations on the batched column's 100k-node WS class,
    DHT greedy lookups on a 100k-node chord overlay — each publishing
    aggregate speedup vs warm sequential capacity-1 runs, lanes/s, and
    completion-rounds p50/p99. Env seams: BENCH_QUERY_K_MINPLUS /
    _PUSHSUM / _DHT (lane counts), BENCH_QUERY_DHT_N (chord size)."""
    import numpy as np

    from p2pnetwork_tpu.models.querybatch import (DhtLookups,
                                                  MinPlusQueries,
                                                  PushSumQueries)

    rng = np.random.default_rng(0)
    col = {}
    _, name, build = _graph_spec_batch()
    g, build_s, cached = _cached_graph(name, build)
    col["graph_build_s"] = round(build_s, 2)
    col["graph_cached"] = cached

    k_mp = int(os.environ.get("BENCH_QUERY_K_MINPLUS", 64))
    mp = MinPlusQueries(method="auto")
    srcs = rng.integers(0, g.n_nodes, k_mp).astype(np.int32)
    tgts = rng.integers(0, g.n_nodes, k_mp).astype(np.int32)
    col["minplus"] = time_query_family(
        g, mp,
        lambda: mp.init(g, srcs, tgts),
        lambda i: mp.init(g, srcs[i:i + 1], tgts[i:i + 1]),
        K=k_mp)

    k_ps = int(os.environ.get("BENCH_QUERY_K_PUSHSUM", 32))
    ps = PushSumQueries(method="auto")
    seeds = (np.arange(k_ps) * 7 + 1).astype(np.int32)
    col["pushsum"] = time_query_family(
        g, ps,
        lambda: ps.init(g, seeds, threshold=1e-4),
        lambda i: ps.init(g, seeds[i:i + 1], threshold=1e-4),
        K=k_ps, max_rounds=512)

    k_dht = int(os.environ.get("BENCH_QUERY_K_DHT", 2048))
    _, dname, dbuild = _graph_spec_query_dht()
    gd, dbuild_s, dcached = _cached_graph(dname, dbuild)
    dht = DhtLookups(metric="ring")
    orgs = rng.integers(0, gd.n_nodes, k_dht).astype(np.int32)
    keys = rng.integers(0, gd.n_nodes, k_dht).astype(np.int32)
    col["dht"] = time_query_family(
        gd, dht,
        lambda: dht.init(gd, orgs, keys),
        lambda i: dht.init(gd, orgs[i:i + 1], keys[i:i + 1]),
        K=k_dht, max_rounds=128)
    col["dht"]["graph_build_s"] = round(dbuild_s, 2)
    col["dht"]["graph_cached"] = dcached

    for fam in ("minplus", "dht", "pushsum"):
        f = col[fam]
        print(f"# queries {fam} K={f['K']}: {f['best_s']*1000:.1f} ms/run"
              f", rounds={f['rounds']}, p99={f['completion_rounds_p99']},"
              f" aggregate x{f['aggregate_speedup_vs_sequential']} vs "
              f"sequential", file=sys.stderr, flush=True)
    return col


def _graph_spec_multichip():
    """(n, cache name, build thunk) for the ``multichip`` column's ring
    class: plain segment-bucket layout — the ring pass carries its own
    edge-bucket representation (parallel/sharded.py), so the single-chip
    tables/MXU layouts would be dead weight in the cache entry."""
    from p2pnetwork_tpu.sim import graph as G

    n = int(os.environ.get("BENCH_MULTICHIP_N", 65_536))
    return n, f"ws_n{n}_k10_p0.1_s0_ring", lambda: G.watts_strogatz(
        n, 10, 0.1, seed=0)


def bench_multichip():
    """The ``multichip`` bench column: the ring-sharded run-to-coverage
    flood over every visible device (the promoted Makefile
    ``dryrun_multichip``, measured and published instead of side-channel
    MULTICHIP_r*.json files) — multi-chip wall-clock, the scaling ratio
    vs a single-chip engine run of the SAME graph on the SAME backend,
    and the per-round ICI byte estimates of BOTH halo-exchange backends
    from the commviz comm census (the pallas ring-DMA traffic is censused
    like its ppermute twin — a Pallas-comm program must never read as
    zero ICI bytes). It runs on the devices of this process only — never
    in a child on another platform — so a chip record never carries a
    CPU number; under an explicit ``JAX_PLATFORMS=cpu`` run (the tests)
    those are the virtual CPU devices, and the column says so in
    ``platform``."""
    import jax
    import jax.numpy as jnp

    from p2pnetwork_tpu.models.flood import Flood
    from p2pnetwork_tpu.parallel import auto, commviz
    from p2pnetwork_tpu.parallel import mesh as M
    from p2pnetwork_tpu.parallel import sharded
    from p2pnetwork_tpu.sim import engine

    n_devices = min(8, len(jax.devices()))
    if n_devices < 2:
        return {"skipped": f"this process sees {n_devices} device(s); "
                           "the ring needs >= 2"}
    n, name, build = _graph_spec_multichip()
    g, build_s, cached = _cached_graph(name, build)
    mesh = M.ring_mesh(n_devices)
    sg = sharded.shard_graph(g, mesh)
    comm = auto.resolve_comm(os.environ.get("BENCH_MULTICHIP_COMM", "auto"))
    reps = int(os.environ.get("BENCH_REPS", "3"))
    target, max_rounds = 0.99, 64

    def once():
        _, out = sharded.flood_until_coverage(
            sg, mesh, source=0, coverage_target=target,
            max_rounds=max_rounds, comm=comm)
        return out  # summary transfer = the honest sync point

    t0 = time.perf_counter()
    out = once()
    warmup_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = once()
        times.append(time.perf_counter() - t0)
    multi_s = min(times)

    # Single-chip baseline: the same flood on the same backend through
    # the engine loop — the ratio's denominator runs in THIS process, so
    # backend and clock are held fixed.
    proto = Flood(source=0)
    engine.run_until_coverage(g, proto, jax.random.key(0),
                              coverage_target=target, max_rounds=max_rounds)
    single_times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _, sout = engine.run_until_coverage(
            g, proto, jax.random.key(0), coverage_target=target,
            max_rounds=max_rounds)
        single_times.append(time.perf_counter() - t0)
    single_s = min(single_times)

    # Per-round ICI bytes per halo backend: static comm census of the
    # actual compiled-shape program, scan-trip-weighted — all S-1 hops
    # of the round body's ring pass are priced; the while loop's dynamic
    # trip count is what the measured `rounds` multiplies back in.
    seen0, frontier0 = sharded.init_state(sg, proto, None)
    ici = {}
    for backend in sharded.COMM_BACKENDS:
        fn = sharded._flood_cov_fn(mesh, mesh.axis_names[0], sg.n_shards,
                                   sg.block, max_rounds, sg.diag_pieces,
                                   sg.mxu_block, backend)
        args = (jnp.float32(target), sg.bkt_src, sg.bkt_dst, sg.bkt_mask,
                *sharded._dyn_or_empty(sg), *sharded._mxu_or_empty(sg),
                sharded._diag_masks_or_empty(sg), sg.node_mask,
                sg.out_degree, seen0, frontier0)
        ici[backend] = {
            "per_round_bytes": commviz.ici_bytes_estimate(fn, args,
                                                          n_devices),
            "census": commviz.jaxpr_comm_census(fn, args, n_devices),
        }
    rounds = int(out["rounds"])
    col = {
        "n_nodes": n,
        "n_edges": g.n_edges,
        "n_devices": n_devices,
        "platform": jax.devices()[0].platform,
        "comm": comm,
        "best_s": round(multi_s, 6),
        "warmup_s": round(warmup_s, 4),
        "reps": reps,
        "rounds": rounds,
        "coverage": round(float(out["coverage"]), 5),
        "messages": int(out["messages"]),
        "single_chip_best_s": round(single_s, 6),
        "scaling_ratio": round(single_s / multi_s, 3),
        "per_round_ici_bytes": {b: ici[b]["per_round_bytes"] for b in ici},
        "ici_bytes_total_est": ici[comm]["per_round_bytes"] * rounds,
        "ici_census": {b: ici[b]["census"] for b in ici},
        "graph_build_s": round(build_s, 2),
        "graph_cached": cached,
    }
    print(f"# multichip {n_devices}dev comm={comm}: "
          f"{multi_s*1000:.1f} ms/run vs single {single_s*1000:.1f} ms "
          f"(ratio {col['scaling_ratio']}), "
          f"ICI/round {col['per_round_ici_bytes']}",
          file=sys.stderr, flush=True)
    return col


def _graph_spec_1m():
    """(cache name, build thunk) for the 1M config — one definition shared
    by the measuring stage and ``--stage prebuild``, so the cache they
    key on cannot drift. BENCH_N_* shrink the configs so the
    orchestration is testable on CPU in seconds (tests/test_bench.py);
    the driver runs the defaults."""
    from p2pnetwork_tpu.sim import graph as G

    n = int(os.environ.get("BENCH_N_1M", 1_000_000))
    return n, f"ws_n{n}_k10_p0.1_s0", lambda: G.watts_strogatz(
        n, 10, 0.1, seed=0, blocked=True, hybrid=True, source_csr=True)


def _graph_spec_10m():
    from p2pnetwork_tpu.sim import graph as G

    n = int(os.environ.get("BENCH_N_10M", 10_000_000))
    return n, f"ws_n{n}_k10_p0.1_s0_notable", lambda: G.watts_strogatz(
        n, 10, 0.1, seed=0, hybrid=True, build_neighbor_table=False,
        source_csr=True)


def bench_1m(record):
    """Fills ``record`` (the headline JSON, format pinned by the driver)
    and returns the per-stage telemetry dict BENCH_TELEMETRY.json carries."""
    import jax

    from p2pnetwork_tpu.sim import graph as G

    n, name, build = _graph_spec_1m()
    target = 0.99
    g, build_s, cached = _cached_graph(name, build)
    # Per-phase attribution of where the build seconds went (dedup/sort/
    # tables/CSR/layouts/reorder) — empty on a cache hit, which built
    # nothing.
    build_phases = {} if cached else G.last_build_phases()
    methods = ["pallas", "hybrid", "adaptive-1024", "adaptive-2048",
               "frontier"]
    # BENCH_METHODS replaces the contest list (the tests pin cheap ones).
    only = os.environ.get("BENCH_METHODS")
    if only:
        methods = [s.strip() for s in only.split(",") if s.strip()] or methods
    results = {}
    per_method = {}
    for m in methods:
        secs, out, timing = time_flood(
            g, m, target=target, max_rounds=64,
            occupancy_attribution=(m == "frontier"))
        results[m] = (secs, out)
        per_method[m] = {"best_s": round(secs, 6), **timing}
        print(f"# 1M {m}: {secs*1000:.1f} ms, rounds={int(out['rounds'])}, "
              f"coverage={float(out['coverage']):.4f}, "
              f"messages={int(out['messages'])}", file=sys.stderr, flush=True)

    # The columns ride the 1M stage; each BENCH_<COLUMN>=0 publishes an
    # empty column instead. A failing column fails the stage.
    # batched (ROADMAP 2a): B concurrent floods per compiled program on
    # the 100k-node class, aggregate throughput vs sequential runs and
    # the completion-rounds p99.
    batched = {}
    if os.environ.get("BENCH_BATCH", "1") != "0":
        batched = bench_batched()
    # serving (ROADMAP 2): seeded open-loop traffic through the
    # admission-controlled service — sustained lanes/s, submit→completion
    # p50/p99, shed rate.
    serving = {}
    if os.environ.get("BENCH_SERVE", "1") != "0":
        serving = bench_serving()
    # queries (ROADMAP 3): the three non-boolean batched query families
    # with their aggregate-vs-sequential ratios.
    queries = {}
    if os.environ.get("BENCH_QUERIES", "1") != "0":
        queries = bench_queries()
    # multichip: the ring-sharded flood over this process's own devices
    # (skipped below two).
    multichip = {}
    if os.environ.get("BENCH_MULTICHIP", "1") != "0":
        multichip = bench_multichip()

    best_method = min(results, key=lambda m: results[m][0])
    secs, out = results[best_method]
    msgs = int(out["messages"])
    record.update({
        "value": round(secs, 6),
        "vs_baseline": round(1.0 / secs, 3),  # north-star target: 1 s
        "method": best_method,
        "platform": jax.devices()[0].platform,
        "rounds": int(out["rounds"]),
        "coverage": round(float(out["coverage"]), 5),
        "messages": msgs,
        "msgs_per_sec_per_chip": round(msgs / secs, 1),
        "graph_build_s": round(build_s, 2),
        "graph_cached": cached,
        "n_nodes": n,
        "n_edges": g.n_edges,
    })
    return {"graph_build_s": round(build_s, 4), "cache_hit": cached,
            "build_phases": build_phases, "per_method": per_method,
            "batched": batched, "serving": serving, "queries": queries,
            "multichip": multichip}


def bench_10m():
    """The scale row: 10M nodes / ~100M directed edges on ONE chip."""
    from p2pnetwork_tpu.sim import graph as G

    n, name, build = _graph_spec_10m()
    g, build_s, cached = _cached_graph(name, build)
    build_phases = {} if cached else G.last_build_phases()
    secs, out, timing = time_flood(g, "adaptive-2048", target=0.99,
                                   max_rounds=64, reps=3)
    msgs = int(out["messages"])
    print(f"# 10M adaptive-2048: {secs:.3f} s, rounds={int(out['rounds'])}, "
          f"coverage={float(out['coverage']):.4f}, messages={msgs}",
          file=sys.stderr, flush=True)
    return {
        "value_s": round(secs, 4),
        "method": "adaptive-2048",
        "rounds": int(out["rounds"]),
        "coverage": round(float(out["coverage"]), 5),
        "messages": msgs,
        "msgs_per_sec_per_chip": round(msgs / secs, 1),
        "graph_build_s": round(build_s, 1),
        "graph_cached": cached,
        "n_nodes": n,
        "n_edges": g.n_edges,
    }, {"graph_build_s": round(build_s, 4), "cache_hit": cached,
        "build_phases": build_phases,
        "per_method": {"adaptive-2048": {"best_s": round(secs, 6), **timing}}}


def _telemetry_path(stage: str) -> str:
    base = os.environ.get("BENCH_TELEMETRY_DIR", _HERE)
    suffix = "" if stage == "1m" else f"_{stage.upper()}"
    return os.path.join(base, f"BENCH_TELEMETRY{suffix}.json")


def _write_stage_telemetry(stage: str, tel: dict, stage_wall_s: float) -> None:
    """The per-stage telemetry artifact: where the time and bytes of one
    measuring stage went — graph build vs cache, compile (jax.monitoring
    lowering hooks; warmup wall as the fallback when hooks are absent),
    measured run, device->host transfer — plus the full registry snapshot.
    ``graph_build_s`` / ``warmup_s`` / ``run_s`` are disjoint wall-clock
    attributions summing (with untracked host overhead) to
    ``stage_wall_s``; ``compile_s`` and ``transfer_s``/``transfer_bytes``
    are finer-grained attributions INSIDE the warmup/run phases, not
    additional siblings.
    Written next to the headline (BENCH_TELEMETRY.json for the 1M stage);
    failure to write must not sink a measured bench."""
    from p2pnetwork_tpu.telemetry import jaxhooks

    reg = telemetry.default_registry()
    compile_s = jaxhooks.compile_seconds(reg)
    per_method = {k: v for k, v in tel.get("per_method", {}).items()
                  if isinstance(v, dict)}
    warmup_s = sum(m.get("warmup_s", 0.0) for m in per_method.values())
    run_s = sum(m.get("measure_s", 0.0) for m in per_method.values())
    artifact = {
        "schema": "bench-telemetry-v1",
        "stage": stage,
        "stage_wall_s": round(stage_wall_s, 4),
        "build_phases": tel.get("build_phases", {}),
        "stages": {
            "graph_build_s": tel.get("graph_build_s", 0.0),
            "cache_hit": tel.get("cache_hit", False),
            "compile_s": round(compile_s if compile_s > 0 else warmup_s, 4),
            "compile_count": int(jaxhooks.compile_count(reg)),
            "warmup_s": round(warmup_s, 4),
            "run_s": round(run_s, 4),
            "transfer_s": round(reg.value("sim_transfer_seconds_total"), 6),
            "transfer_bytes": int(reg.value("sim_transfer_bytes_total")),
        },
        "device": _device_record(),
        "per_method": tel.get("per_method", {}),
        # The batched message-plane column: B in-flight floods per
        # compiled program, aggregate-throughput ratio vs sequential
        # runs, batch_completion_rounds_p99 (empty for stages without
        # the column, error-carrying when it failed).
        "batched": tel.get("batched", {}),
        # The serving column: seeded open-loop traffic through the
        # admission-controlled SimService — sustained lanes/s,
        # submit→completion p50/p99 rounds, peak concurrent lanes, shed
        # rate (empty for stages without the column, error-carrying
        # when it failed).
        "serving": tel.get("serving", {}),
        # The queries column: the three non-boolean batched query
        # families (min-plus routing, DHT lookups, push-sum) — per-family
        # aggregate speedup vs warm sequential capacity-1 runs, lanes/s,
        # completion-rounds p50/p99 (empty for stages without the
        # column, error-carrying when it failed).
        "queries": tel.get("queries", {}),
        # The multichip ring column: multi-device run-to-coverage wall,
        # scaling ratio vs a single-chip run of the same graph, and the
        # per-round ICI byte estimates of both halo-exchange backends
        # (commviz comm census — Pallas ring DMAs priced like ppermute).
        "multichip": tel.get("multichip", {}),
        # The static cost model beside the measured numbers: graftaudit's
        # blessed flops/bytes per lowering for this stage's shape-class,
        # so drift between model and wall-clock is visible per artifact.
        "ir_cost_model": _ir_cost_slice(stage),
        # The graftmem slice: the static capacity plan for this stage's
        # node count (checked-in membudgets.json closed-form
        # coefficients — nothing is built or compiled) beside the live
        # allocator numbers (`device_memory_stats`), so planned-vs-
        # resident drift is visible per artifact.
        "memory": _memory_slice(stage),
        "metrics": reg.snapshot(),
    }
    path = _telemetry_path(stage)
    try:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(artifact, f, indent=1)
        print(f"# stage {stage}: telemetry written to {path}",
              file=sys.stderr, flush=True)
    except Exception as e:
        _warn_event("bench_telemetry_write_failed", path=path,
                    error=f"{type(e).__name__}: {e}")


def _device_record() -> dict:
    """The device this process measured on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _require_tpu() -> None:
    """A measuring stage runs on a TPU. The one exception is an explicit
    ``JAX_PLATFORMS=cpu`` (the tests): its records say ``platform: cpu``."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"no TPU: JAX found platform {platform!r} (set "
            "JAX_PLATFORMS=cpu to measure the CPU on purpose)")


def _ir_cost_slice(stage: str) -> dict:
    """The graftaudit cost-table slice for this stage — flops/bytes (and
    the collective census) per lowering on the stage's shape-class, read
    from the checked-in analysis/ir/budgets.json. Both measuring stages
    run the WS family, so the canonical ``ws1k`` class is the static
    model the measured per-method wall-clocks are compared against
    (cost_analysis prices the program; the graph scale multiplies both
    sides). Failure to load must not sink a measured bench."""
    try:
        from p2pnetwork_tpu.analysis.ir import budgets as irb

        doc = irb.load_budgets()
        cls = "ws1k"
        entries = {name: rec for name, rec in
                   doc.get("entries", {}).items()
                   if name.endswith("@" + cls) and "error" not in rec}
        return {"shape_class": cls, "jaxlib": doc.get("jaxlib"),
                "tolerance": doc.get("tolerance"), "entries": entries}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _device_memory_stats() -> dict:
    """Per-device allocator occupancy at snapshot time
    (``device.memory_stats()``). Backends without allocator stats — the
    CPU backend returns None — record ``available: False`` with a
    structured warning, never a crash: the static plan beside it is the
    number the artifact is really for on such hosts."""
    out = {"available": False, "devices": []}
    try:
        import jax

        for d in jax.devices():
            stats = getattr(d, "memory_stats", lambda: None)()
            if not stats:
                out["devices"].append(
                    {"id": d.id, "platform": d.platform, "stats": None})
                continue
            out["available"] = True
            out["devices"].append(
                {"id": d.id, "platform": d.platform,
                 "stats": {k: int(v) for k, v in stats.items()
                           if isinstance(v, (int, float))}})
        if not out["available"]:
            _warn_event("bench_device_memory_stats_unavailable",
                        platform=jax.devices()[0].platform
                        if jax.devices() else "none")
    except Exception as e:
        _warn_event("bench_device_memory_stats_failed",
                    error=f"{type(e).__name__}: {e}")
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def _memory_slice(stage: str) -> dict:
    """The graftmem slice: capacity.plan at this stage's node count
    (the 1M headline plans the north-star 10k-lane shape) from the
    checked-in coefficients, beside the measured per-device allocator
    stats. Failure to plan must not sink a measured bench — a host
    without a blessed capacity model records the error and moves on."""
    nodes = {"1m": 1_000_000, "10m": 10_000_000}.get(stage, 1_000_000)
    out = {"device_memory_stats": _device_memory_stats()}
    try:
        from p2pnetwork_tpu.analysis.ir import capacity as irc

        p = irc.plan(nodes, lanes=10_016)
        out["plan"] = {k: p[k] for k in
                       ("entry", "n_nodes", "n_pad", "e_pad", "lanes",
                        "lane_words", "global_bytes",
                        "recommended_shards")}
    except Exception as e:
        out["plan"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def _stage_compile_budget(stage: str) -> int:
    """Per-stage jit compile budget for retrace_guard. The 1M contest
    stage legitimately compiles several programs per method (engine loop
    variants, occupancy re-run); the 10M stage runs one method. Beyond
    the budget something is RE-tracing — shape churn, a fresh jit wrapper
    per call — which silently eats the wins the stage measures. Override
    with BENCH_COMPILE_BUDGET_1M / BENCH_COMPILE_BUDGET_10M."""
    defaults = {"1m": 64, "10m": 24}
    return int(os.environ.get(f"BENCH_COMPILE_BUDGET_{stage.upper()}",
                              defaults.get(stage, 64)))


def _on_stage_breach(guard) -> None:
    """retrace_guard breach handler: never sinks the bench — emits the
    structured warning plus the ``bench_recompile_total{stage}`` counter
    (the registry snapshot lands in BENCH_TELEMETRY.json; the headline
    record is untouched)."""
    telemetry.default_registry().counter(
        "bench_recompile_total",
        "Backend compiles beyond a bench stage's compile budget "
        "(retrace_guard breaches) — recompiles eating measured time.",
        ("stage",)).labels(guard.block).inc(guard.compiles - guard.budget)
    _warn_event("bench_recompile_budget_breach", stage=guard.block,
                compiles=guard.compiles, budget=guard.budget)


@contextlib.contextmanager
def _maybe_profile(stage: str):
    """Opt-in ``jax.profiler.trace`` bracket around a measuring stage
    (graftscope profiler wiring): BENCH_PROFILE_DIR=<dir> writes the
    XLA/TraceMe profile for stage ``<dir>/<stage>`` — load it in
    TensorBoard's profile plugin or Perfetto. Off by default (profiling
    is not free), and failure-tolerant both ways: an unavailable
    profiler degrades to a structured warning, never a failed bench."""
    pdir = os.environ.get("BENCH_PROFILE_DIR")
    if not pdir:
        yield
        return
    outdir = os.path.join(pdir, stage)
    try:
        import jax

        os.makedirs(outdir, exist_ok=True)
        jax.profiler.start_trace(outdir)
    except Exception as e:
        _warn_event("bench_profile_unavailable", stage=stage,
                    error=f"{type(e).__name__}: {e}")
        yield
        return
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
            print(f"# stage {stage}: profiler trace written to {outdir}",
                  file=sys.stderr, flush=True)
        except Exception as e:
            _warn_event("bench_profile_stop_failed", stage=stage,
                        error=f"{type(e).__name__}: {e}")


def _run_stage(stage: str) -> int:
    """Child-process entry (``--stage 1m|10m``): init the backend, run one
    stage, print ONE JSON line on stdout. Comments go to stderr, which the
    parent inherits straight through to the driver log."""
    try:
        from p2pnetwork_tpu.utils.jax_env import (apply_platform_env,
                                                  enable_compile_cache)

        apply_platform_env()
        enable_compile_cache()
        if stage != "prebuild":
            _require_tpu()
        from p2pnetwork_tpu.analysis import retrace_guard
        from p2pnetwork_tpu.telemetry import jaxhooks

        jaxhooks.install()  # compile accounting for the whole stage
        if stage == "1m":
            record = {}
            t0 = time.perf_counter()
            # The guard closes before the telemetry write, so a breach's
            # counter is already in the registry snapshot it publishes.
            with _maybe_profile("1m"), \
                    retrace_guard("1m", budget=_stage_compile_budget("1m"),
                                  on_breach=_on_stage_breach):
                tel = bench_1m(record)
            _write_stage_telemetry(stage, tel, time.perf_counter() - t0)
            print(json.dumps(record))
            return 0
        if stage == "10m":
            t0 = time.perf_counter()
            with _maybe_profile("10m"), \
                    retrace_guard("10m",
                                  budget=_stage_compile_budget("10m"),
                                  on_breach=_on_stage_breach):
                rec, tel = bench_10m()
            _write_stage_telemetry(stage, tel, time.perf_counter() - t0)
            print(json.dumps(rec))
            return 0
        if stage == "prebuild":
            # Populate the graph cache without measuring (builds are
            # host-side, any backend) so a later measuring run only loads.
            for _, name, build in (_graph_spec_1m(), _graph_spec_10m()):
                _cached_graph(name, build)
            print(json.dumps({"prebuilt": True}))
            return 0
    except Exception as e:
        # The error must reach the driver's parsed record, not just the
        # stderr log: emit it as this stage's JSON line (the parent
        # forwards it) before exiting nonzero.
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        return 1
    print(f"# unknown stage {stage!r}", file=sys.stderr)
    return 2


def _stage_in_child(stage: str, timeout_s: int):
    """Run ``--stage <stage>`` in a child under a hard timeout. Returns the
    stage's parsed JSON record, or ``{"error": ...}`` — never raises,
    never hangs."""
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", stage]
    t0 = time.perf_counter()
    try:
        r = subprocess.run(cmd, stdout=subprocess.PIPE, timeout=timeout_s,
                           text=True, cwd=_HERE)
    except subprocess.TimeoutExpired:
        return {"error": f"stage {stage} exceeded {timeout_s}s"}
    except Exception as e:
        return {"error": f"stage {stage} launcher failed: "
                         f"{type(e).__name__}: {e}"}
    dt = time.perf_counter() - t0
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    parsed = None
    if lines:
        try:
            parsed = json.loads(lines[-1])
        except ValueError:
            pass
    if r.returncode != 0:
        # A failing stage still emits an error-carrying JSON line
        # (_run_stage's handler) — prefer its actual cause over a bare
        # exit-code report.
        if isinstance(parsed, dict) and "error" in parsed:
            return {"error": f"stage {stage}: {parsed['error']}"}
        return {"error": f"stage {stage} exited rc={r.returncode} "
                         f"after {dt:.0f}s with "
                         f"{'no output' if not lines else lines[-1][-200:]}"}
    if parsed is None:
        return {"error": f"stage {stage} emitted unparseable output: "
                         f"{lines[-1][-200:] if lines else 'no output'}"}
    return parsed


def main():
    """The JAX-free parent: the 1M stage, then the 10M stage, each in its
    own child. Exits non-zero when either fails."""
    record = {
        "metric": "1M-node WS flood to 99% coverage (single chip)",
        "value": None,
        "unit": "s",
        "vs_baseline": 0.0,
    }
    stage_timeout = int(os.environ.get("BENCH_STAGE_TIMEOUT_S", "900"))
    r1m = _stage_in_child("1m", stage_timeout)
    if "error" in r1m:
        record["error"] = r1m["error"]
        print(f"# {r1m['error']}", file=sys.stderr, flush=True)
        print(json.dumps(record))
        return 1
    record.update(r1m)
    # Emit the measured headline NOW: if the 10M stage's child is killed
    # by its timeout the merged line below still prints, but if this
    # parent itself dies the 1M number is already out.
    print(json.dumps(record), flush=True)
    r10m = _stage_in_child("10m", stage_timeout)
    record["scale_10M"] = r10m
    print(json.dumps(record))
    return 1 if "error" in r10m else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--stage":
        sys.exit(_run_stage(sys.argv[2]))
    sys.exit(main())

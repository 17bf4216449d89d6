"""The BASELINE.json scale ladder: every config, one JSON line each.

Configs (BASELINE.json "configs"):
  0. 3-node localhost broadcast over real sockets — the CPU reference
     anchor, the workload the reference's examples run
     [ref: examples/my_own_p2p_application.py].
  1. 1K-node Erdős–Rényi single-source flood, one chip.
  2. 100K-node Barabási–Albert push-pull gossip averaging.
  3. 1M-node Watts–Strogatz SIR rumor spread.
  4. 1M + (with --full) 10M-node Watts–Strogatz seen-set flood — the
     tx-flood config; the 10M graph specced for a v4-8 runs on ONE chip.

Run: ``python benchmarks/ladder.py [--full]``. The headline driver metric
stays in bench.py; this is the breadth harness.
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

from p2pnetwork_tpu.utils.jax_env import (  # noqa: E402
    apply_platform_env, enable_compile_cache)

apply_platform_env()


def emit(record):
    print(json.dumps(record), flush=True)


def _sync(stats_entry):
    """Force device completion via a host transfer."""
    return float(stats_entry)


def bench_sockets_anchor():
    """Config 0: 3 real-socket nodes, timed broadcast delivery."""
    import threading

    from p2pnetwork_tpu import Node

    got = threading.Semaphore(0)

    class Counting(Node):
        def node_message(self, node, data):
            got.release()

    nodes = [Counting("127.0.0.1", 0, id=f"n{i}") for i in range(3)]
    try:
        for n in nodes:
            n.start()
        nodes[0].connect_with_node("127.0.0.1", nodes[1].port)
        nodes[1].connect_with_node("127.0.0.1", nodes[2].port)
        nodes[2].connect_with_node("127.0.0.1", nodes[0].port)
        deadline = time.monotonic() + 5
        while sum(len(n.all_nodes) for n in nodes) < 6 and time.monotonic() < deadline:
            time.sleep(0.01)
        n_msgs = 200
        t0 = time.perf_counter()
        for i in range(n_msgs):
            nodes[0].send_to_nodes(f"ping {i}")  # 2 deliveries each
        for _ in range(2 * n_msgs):
            got.acquire(timeout=10)
        secs = time.perf_counter() - t0
        emit({
            "config": "3-node localhost broadcast (sockets, CPU anchor)",
            "value": round(2 * n_msgs / secs, 1),
            "unit": "delivered msgs/s",
            "wall_s": round(secs, 4),
        })
    finally:
        for n in nodes:
            n.stop()
        for n in nodes:
            n.join(timeout=10)


def bench_flood_1k():
    import jax

    from p2pnetwork_tpu.models import Flood
    from p2pnetwork_tpu.sim import engine
    from p2pnetwork_tpu.sim import graph as G

    g = G.erdos_renyi(1000, 0.01, seed=0)
    p = Flood(source=0, method="segment")
    key = jax.random.key(0)
    state, out = engine.run_until_coverage(g, p, key, coverage_target=0.99)
    _ = int(out["rounds"])  # warm
    t0 = time.perf_counter()
    state, out = engine.run_until_coverage(g, p, key, coverage_target=0.99)
    rounds = int(out["rounds"])
    secs = time.perf_counter() - t0
    emit({
        "config": "1K ER flood (single chip)",
        "value": round(secs * 1000, 3),
        "unit": "ms to 99% coverage",
        "rounds": rounds,
        "messages": int(out["messages"]),
    })


def bench_gossip_100k():
    import jax
    import numpy as np

    from p2pnetwork_tpu.models import Gossip
    from p2pnetwork_tpu.sim import engine
    from p2pnetwork_tpu.sim import graph as G

    g = G.barabasi_albert(100_000, 4, seed=0, max_degree=128)
    p = Gossip(alpha=0.5)
    key = jax.random.key(0)
    rounds = 30
    state, stats = engine.run(g, p, key, rounds)
    _ = _sync(stats["variance"][-1])  # warm
    t0 = time.perf_counter()
    state, stats = engine.run(g, p, key, rounds)
    var_end = _sync(stats["variance"][-1])
    secs = time.perf_counter() - t0
    var = np.asarray(stats["variance"])
    emit({
        "config": "100K BA push-pull gossip (30 rounds)",
        "value": round(rounds * g.n_nodes / secs / 1e6, 1),
        "unit": "M node-updates/s",
        "wall_s": round(secs, 4),
        "variance_start": round(float(var[0]), 4),
        "variance_end": round(var_end, 6),
    })


def bench_sir_1m():
    import jax

    from p2pnetwork_tpu.models import SIR
    from p2pnetwork_tpu.sim import engine
    from p2pnetwork_tpu.sim import graph as G

    g = G.watts_strogatz(1_000_000, 10, 0.1, seed=0, hybrid=True,
                         build_neighbor_table=False)
    p = SIR(beta=0.3, gamma=0.05, source=0, method="hybrid")
    key = jax.random.key(0)
    rounds = 30
    state, stats = engine.run(g, p, key, rounds)
    _ = _sync(stats["coverage"][-1])  # warm
    t0 = time.perf_counter()
    state, stats = engine.run(g, p, key, rounds)
    cov = _sync(stats["coverage"][-1])
    secs = time.perf_counter() - t0
    emit({
        "config": "1M WS SIR rumor spread (30 rounds)",
        "value": round(secs * 1000, 1),
        "unit": "ms",
        "coverage": round(cov, 4),
        "messages": int(sum(stats["messages"].tolist())),
        "msgs_per_s": round(float(sum(stats["messages"].tolist())) / secs / 1e6, 1),
    })


def bench_flood_big(n, label, adaptive_k=1024, *, make_graph=None,
                    method="hybrid", compare_methods=(), extra_fields=None):
    """Dense-vs-adaptive flood rung: one warm + one timed coverage run per
    protocol. ``make_graph`` swaps the topology (default 1M-family WS),
    ``method`` the dense lowering (``compare_methods`` adds rival dense
    lowerings — each is timed, the fastest drives the adaptive run and
    every time lands in the record), ``extra_fields(g)`` appends
    per-graph facts to the emitted record — one harness for every flood
    rung, so a timing-protocol fix lands on all of them at once."""
    import jax

    from p2pnetwork_tpu.models import AdaptiveFlood, Flood
    from p2pnetwork_tpu.sim import engine
    from p2pnetwork_tpu.sim import graph as G

    t0 = time.perf_counter()
    if make_graph is None:
        g = G.watts_strogatz(n, 10, 0.1, seed=0, hybrid=True,
                             build_neighbor_table=False, source_csr=True)
    else:
        g = make_graph(G)
    build_s = time.perf_counter() - t0
    key = jax.random.key(0)

    def run(p):
        _, out = engine.run_until_coverage(g, p, key, coverage_target=0.99,
                                           max_rounds=64)
        _ = int(out["rounds"])  # warm
        t0 = time.perf_counter()
        _, out = engine.run_until_coverage(g, p, key, coverage_target=0.99,
                                           max_rounds=64)
        return time.perf_counter() - t0, out

    dense_times = {}
    for meth in (method, *compare_methods):
        dense_times[meth], _ = run(Flood(source=0, method=meth))
    method = min(dense_times, key=dense_times.get)
    dense_s = dense_times[method]
    secs, out = run(AdaptiveFlood(source=0, method=method, k=adaptive_k))
    emit({
        "config": label,
        "value": round(secs, 4),
        "unit": f"s to 99% coverage (adaptive-{adaptive_k}; "
                f"dense {method} {dense_s:.3f}s)",
        **({"dense_times_s": {m: round(s, 4)
                              for m, s in dense_times.items()}}
           if compare_methods else {}),
        "rounds": int(out["rounds"]),
        "messages": int(out["messages"]),
        "msgs_per_sec_per_chip": round(int(out["messages"]) / secs, 1),
        "graph_build_s": round(build_s, 1),
        **(extra_fields(g) if extra_fields else {}),
    })


def bench_flood_ba(n=100_000, m=4, adaptive_k=1024):
    """Seen-set flood on the scale-free (Barabási–Albert) family — the
    same 100K/m=4 edge topology as the BASELINE config-2 gossip rung
    (which additionally caps its gather TABLE at 128 — the edges and the
    hub degrees are identical), under the flood workload. Round 4's
    work-item chunking budgets sparse
    rounds by out-edge mass, so the hub-skewed degree distribution gets
    the adaptive win too (it was excluded before; VERDICT r3 #2).

    Dense lowerings raced per rung: sorted segment (the r4 answer —
    measured 0.118 s vs hybrid 0.41 s / pallas 2.17 s / padded gather
    3.97 s on this topology) vs the two-level skew table (ops/skew.py,
    VERDICT r4 #2) whose cost model predicts ~2x under segment."""
    bench_flood_big(
        n,
        f"{n//1_000_000}M BA (m={m}) seen-set flood, hub-tolerant "
        f"adaptive (single chip)" if n >= 1_000_000 else
        f"{n//1000}K BA (m={m}) seen-set flood, hub-tolerant adaptive "
        f"(single chip)",
        adaptive_k,
        make_graph=lambda G: G.barabasi_albert(
            n, m, seed=0, build_neighbor_table=False, source_csr=True,
            skew_table=True),
        method="segment",
        compare_methods=("skew",),
        extra_fields=lambda g: {"max_out_degree": max(1, g.max_out_span),
                                "skew_width": g.skew.width,
                                "skew_rows": g.skew.n_rows},
    )


def bench_flood_ba_1m(n=1_000_000, m=5, adaptive_k=2048):
    """The 1M-node scale-free rung (VERDICT r4 #2): ~10M directed edges
    under a power-law degree distribution — the realistic overlay shape
    at the north-star scale, where the hub machinery must prove itself
    end-to-end. Same recipe as the 100K rung, scaled."""
    bench_flood_ba(n, m, adaptive_k)


def bench_discovery(n=1_000_000, walkers=4096):
    """Peer-sampling discovery rung: how long a walker cohort takes to
    map 99% of a 1M-node overlay — the protocol family reference users
    hand-roll for crawling/peer sampling [ref: README.md:20], whole run
    device-side (models/walk.py RandomWalks + run_until_coverage)."""
    import jax

    from p2pnetwork_tpu.models import RandomWalks
    from p2pnetwork_tpu.sim import engine
    from p2pnetwork_tpu.sim import graph as G

    t0 = time.perf_counter()
    g = G.watts_strogatz(n, 10, 0.1, seed=0, build_neighbor_table=False,
                         source_csr=True)
    build_s = time.perf_counter() - t0
    proto = RandomWalks(n_walkers=walkers)

    def once():
        _, out = engine.run_until_coverage(
            g, proto, jax.random.key(0), coverage_target=0.99,
            max_rounds=8192,
        )
        return out

    out = once()  # warm
    t0 = time.perf_counter()
    out = once()
    secs = time.perf_counter() - t0

    # The crawl is rounds-bound (~1700 rounds at a per-iteration floor set
    # by while_loop dispatch, not bandwidth): batching T walk rounds per
    # iteration (engine steps_per_round — bit-exact vs T=1, pinned by
    # tests/test_walk.py::TestBatchedSteps) amortizes that floor.
    def once_batched(T):
        _, o = engine.run_until_coverage(
            g, proto, jax.random.key(0), coverage_target=0.99,
            max_rounds=8192, steps_per_round=T,
        )
        return o

    best_T, best_secs, out_b = 1, secs, out
    for T in (8, 16, 32):
        ob = once_batched(T)  # warm (fresh compile per T)
        t0 = time.perf_counter()
        ob = once_batched(T)
        sb = time.perf_counter() - t0
        if sb < best_secs:
            best_T, best_secs, out_b = T, sb, ob
    assert out_b["rounds"] == out["rounds"], "batched walk not bit-exact"
    assert out_b["messages"] == out["messages"]

    emit({
        "config": f"{n//1_000_000}M WS overlay discovery, "
                  f"{walkers}-walker cohort (single chip)",
        "value": round(best_secs, 3),
        "unit": "s to 99% of the overlay visited",
        "steps_per_round": best_T,
        "unbatched_s": round(secs, 3),
        "rounds": int(out_b["rounds"]),
        "messages": int(out_b["messages"]),
        "rounds_per_s": round(int(out_b["rounds"]) / best_secs, 1),
        "graph_build_s": round(build_s, 1),
    })


def bench_plumtree(n=1_000_000):
    """Broadcast-tree rung: Plumtree's self-optimization contrast at 1M —
    the first broadcast floods every edge; the extracted tree
    (models/plumtree.py tree_graph) then carries repeated broadcasts at
    ~N messages. Emits the steady-state (extracted-tree) broadcast time."""
    import jax

    from p2pnetwork_tpu.models import Flood, Plumtree
    from p2pnetwork_tpu.sim import engine
    from p2pnetwork_tpu.sim import graph as G

    t0 = time.perf_counter()
    g = G.watts_strogatz(n, 10, 0.1, seed=0, build_neighbor_table=False)
    build_s = time.perf_counter() - t0
    p = Plumtree(source=0)
    st = p.init(g, jax.random.key(0))
    st, stats0 = jax.jit(p.step)(g, st, jax.random.key(0))  # flood + prune
    flood_msgs = int(stats0["messages"])
    t0 = time.perf_counter()
    # The tree's max in-degree is 1: its neighbor table is one column
    # wide and the gather lowering is as cheap as aggregation gets.
    tg = p.tree_graph(g, st, source_csr=True)
    extract_s = time.perf_counter() - t0

    def once():
        _, out = engine.run_until_coverage(
            tg, Flood(source=0), jax.random.key(0), coverage_target=1.0,
            max_rounds=256)
        return out

    out = once()  # warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = once()
        times.append(time.perf_counter() - t0)
    emit({
        "config": f"{n//1_000_000}M WS Plumtree broadcast tree "
                  f"(single chip)",
        "value": round(min(times), 3),
        "unit": "s per steady-state broadcast over the extracted tree",
        "rounds": int(out["rounds"]),
        "messages": int(out["messages"]),
        "flood_messages": flood_msgs,
        "message_reduction": round(flood_msgs / int(out["messages"]), 1),
        "extract_s": round(extract_s, 1),
        "graph_build_s": round(build_s, 1),
    })


def bench_routing(n=1_000_000):
    """Weighted routing rung: latency-weighted distance-vector tables
    for the whole overlay (models/routing.py DistanceVector — one
    propagate_min_plus per round, run-to-quiescence device-side), the
    RIP-style protocol reference users hand-roll on node_message."""
    import jax
    import numpy as np

    from p2pnetwork_tpu.models import DistanceVector
    from p2pnetwork_tpu.sim import engine
    from p2pnetwork_tpu.sim import graph as G

    t0 = time.perf_counter()
    g = G.watts_strogatz(n, 10, 0.1, seed=0, build_neighbor_table=False)

    def latency(s, r):
        h = (s.astype(np.uint32) * np.uint32(2654435761)
             + r.astype(np.uint32))
        return 1.0 + (h % 2048).astype(np.float32) / 1024.0

    g = g.with_weights(latency)
    build_s = time.perf_counter() - t0

    def once():
        _, out = engine.run_until_converged(
            g, DistanceVector(source=0, method="segment"),
            jax.random.key(0), stat="changed", threshold=1, max_rounds=256,
        )
        return out

    out = once()  # warm
    t0 = time.perf_counter()
    out = once()
    secs = time.perf_counter() - t0
    emit({
        "config": f"{n:,}-node WS weighted distance-vector routing "
                  f"(single chip)",
        "value": round(secs, 3),
        "unit": "s to converged cost + next-hop tables",
        "rounds": int(out["rounds"]),
        "messages": int(out["messages"]),
        "graph_build_s": round(build_s, 1),
    })


def bench_flood_auto():
    """GSPMD auto path (parallel/auto.py) on every available device, both
    lowerings: the segment-method flood (the idiom's historical floor,
    paying the full scatter cost) and the hybrid-blocked method (diagonal
    rolls + einsum remainder — every op partitionable), which closes the
    gap to the explicit ring path. On one chip this measures the
    unpartitioned programs; multi-device communication is bounded
    node-extent by HLO inspection (tests/test_auto_comm.py), which no
    single-chip wall-clock can show."""
    import jax

    from p2pnetwork_tpu.models import Flood
    from p2pnetwork_tpu.parallel import auto
    from p2pnetwork_tpu.parallel import mesh as M
    from p2pnetwork_tpu.sim import engine
    from p2pnetwork_tpu.sim import graph as G

    mesh = M.ring_mesh()
    g = auto.shard_graph_auto(
        G.watts_strogatz(1_000_000, 10, 0.1, seed=0,
                         build_neighbor_table=False, hybrid=True),
        mesh,
    )
    key = jax.random.key(0)
    for method in ("segment", "hybrid-blocked"):
        p = Flood(source=0, method=method)
        _, out = engine.run_until_coverage(g, p, key, coverage_target=0.99,
                                           max_rounds=64)
        _ = int(out["rounds"])  # warm
        t0 = time.perf_counter()
        _, out = engine.run_until_coverage(g, p, key, coverage_target=0.99,
                                           max_rounds=64)
        secs = time.perf_counter() - t0
        emit({
            "config": f"1M WS flood, GSPMD auto ({mesh.devices.size} dev, "
                      f"{method} lowering)",
            "value": round(secs, 4),
            "unit": "s to 99% coverage (compiler-placed collectives)",
            "rounds": int(out["rounds"]),
            "messages": int(out["messages"]),
            "comm_evidence": "tests/test_auto_comm.py pins collectives to "
                             "node-extent payloads on the 8-device mesh",
        })


def bench_gossip_sharded():
    """Sharded (ring ppermute) gossip on every available device — the
    multi-chip path of configs[2]; on one chip this measures the S=1 ring
    overhead vs the single-device entry above."""
    import jax

    from p2pnetwork_tpu.models import Gossip
    from p2pnetwork_tpu.parallel import mesh as M
    from p2pnetwork_tpu.parallel import sharded
    from p2pnetwork_tpu.sim import graph as G

    n_dev = len(jax.devices())
    mesh = M.ring_mesh(n_dev)
    g = G.barabasi_albert(100_000, 4, seed=0, max_degree=128)
    sg = sharded.shard_graph(g, mesh)
    p = Gossip(alpha=0.5)
    key = jax.random.key(0)
    rounds = 30
    vals, stats = sharded.gossip(sg, mesh, p, key, rounds)
    _ = _sync(stats["variance"][-1])  # warm
    t0 = time.perf_counter()
    vals, stats = sharded.gossip(sg, mesh, p, key, rounds)
    var_end = _sync(stats["variance"][-1])
    secs = time.perf_counter() - t0
    emit({
        "config": f"100K BA push-pull gossip, sharded ring ({n_dev} dev, 30 rounds)",
        "value": round(rounds * g.n_nodes / secs / 1e6, 1),
        "unit": "M node-updates/s",
        "wall_s": round(secs, 4),
        "variance_end": round(var_end, 6),
    })


def bench_flood_sharded_ring():
    """1M flood-to-99% on the explicit ring path (every available device;
    one chip measures ring overhead vs the single-chip hybrid entry) —
    segment reductions vs the MXU bucket layout."""
    import numpy as np

    from p2pnetwork_tpu.parallel import mesh as M
    from p2pnetwork_tpu.parallel import sharded
    from p2pnetwork_tpu.sim import graph as G

    mesh = M.ring_mesh()
    g = G.watts_strogatz(1_000_000, 10, 0.1, seed=0,
                         build_neighbor_table=False)
    results = {}
    for label, kw, call_kw in (
        ("segment", {}, {}),
        ("mxu", dict(mxu=True), {}),
        ("hybrid", dict(hybrid=True), {}),
        ("adaptive", dict(hybrid=True, source_csr=True),
         dict(adaptive_k=1024)),
    ):
        sg = sharded.shard_graph(g, mesh, **kw)
        seen, out = sharded.flood_until_coverage(sg, mesh, source=0,
                                                 **call_kw)  # warm
        t0 = time.perf_counter()
        seen, out = sharded.flood_until_coverage(sg, mesh, source=0,
                                                 **call_kw)
        _ = out["messages"]  # blocking summary transfer
        results[label] = time.perf_counter() - t0
    emit({
        "config": f"1M WS flood, ring-sharded ({mesh.devices.size} dev)",
        "value": round(results["adaptive"], 4),
        "unit": "s to 99% coverage (hybrid layout + frontier-adaptive "
                "rounds)",
        "segment_s": round(results["segment"], 4),
        "mxu_s": round(results["mxu"], 4),
        "hybrid_s": round(results["hybrid"], 4),
        "adaptive_speedup_vs_segment": round(
            results["segment"] / results["adaptive"], 2
        ),
        "rounds": int(np.asarray(out["rounds"])),
    })


def bench_churn_connect():
    """Runtime connect cost vs graph size: the membership probe is a
    searchsorted window scan (sim/topology.py), so a connect batch should
    cost about the same at 100K and at 1M nodes — not 10x more."""
    import jax

    from p2pnetwork_tpu.sim import graph as G
    from p2pnetwork_tpu.sim import topology

    batch = 64
    rng_s = [(i * 37) % 99_000 for i in range(batch)]
    rng_r = [(i * 91 + 13) % 99_000 for i in range(batch)]
    times = {}
    for n in (100_000, 1_000_000):
        g = G.watts_strogatz(n, 10, 0.1, seed=0, build_neighbor_table=False)
        g = topology.with_capacity(g, extra_edges=4 * batch)
        s = jax.numpy.asarray(rng_s, jax.numpy.int32)
        r = jax.numpy.asarray(rng_r, jax.numpy.int32)
        g2 = topology.connect(g, s, r, check_capacity=False)
        jax.block_until_ready(g2.dyn_mask)  # warm (compile)
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            g2 = topology.connect(g, s, r, check_capacity=False)
            jax.block_until_ready(g2.dyn_mask)
        times[n] = (time.perf_counter() - t0) / reps
    emit({
        "config": f"runtime connect, {batch}-link batch (no capacity sync)",
        "value": round(times[1_000_000] * 1e3, 3),
        "unit": "ms/batch at 1M nodes (10M edges)",
        "ms_at_100k": round(times[100_000] * 1e3, 3),
        "scaling_1m_over_100k": round(times[1_000_000] / times[100_000], 2),
    })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="include the 10M-node config (long graph build)")
    args = ap.parse_args()
    enable_compile_cache()

    bench_sockets_anchor()
    bench_flood_1k()
    bench_gossip_100k()
    bench_gossip_sharded()
    bench_sir_1m()
    bench_churn_connect()
    bench_flood_sharded_ring()
    bench_flood_auto()
    bench_flood_ba()
    bench_flood_ba_1m()
    bench_discovery()
    bench_plumtree()
    bench_routing()
    bench_flood_big(1_000_000, "1M WS seen-set flood (single chip)")
    if args.full:
        bench_flood_big(10_000_000, "10M WS seen-set flood (single chip)",
                        adaptive_k=2048)
        bench_plumtree(10_000_000)
    return 0


if __name__ == "__main__":
    sys.exit(main())

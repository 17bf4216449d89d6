"""Chip smoke check: the simulation backend's main path on one TPU chip.

One process, no subprocesses, exit 0 only when every check passed:

- device: JAX must find a TPU (any other platform exits non-zero);
- main path: the 1M-node Watts-Strogatz flood (k=10, p=0.1, seed 0, the
  bench's layout) through ``JaxSimNode.run_until_coverage(0.99)`` with
  ``method="hybrid"`` (the Pallas remainder kernel, which must be compiled
  as a ``tpu_custom_call``) and with ``"gather"``; rounds and seen set must
  equal a NumPy BFS from the same source;
- serving: 64 seeded floods through ``SimService`` (capacity 1024) on the
  100k-node class; every ticket must complete with the BFS's rounds and
  seen set.

``--four-chips`` runs only the sharded ring instead: the same 1M flood
through ``JaxSimNode(mesh=ring_mesh(4))`` under ``comm="ppermute"`` and
``comm="pallas"``, each bit-identical to the single-device engine on
``jax.devices()[0]``.

Each check prints one JSON line; the last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

N_MAIN = 1_000_000
N_SERVE = 100_000
TARGET = 0.99
MAX_ROUNDS = 64
SERVE_FLOODS = 64
SERVE_CAPACITY = 1024
SERVE_TIMEOUT_S = 600.0


class SmokeFailure(RuntimeError):
    """A check whose result disagrees with its reference."""


def report(check: str, **fields) -> None:
    print(json.dumps({"check": check, **fields}), flush=True)


def expect(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _import_repo():
    """Import the package from this checkout only: a copy of this script
    without the repo next to it must fail, not find another install."""
    sys.path.insert(0, HERE)
    import p2pnetwork_tpu

    pkg = os.path.dirname(os.path.abspath(p2pnetwork_tpu.__file__))
    if os.path.dirname(pkg) != HERE:
        raise ImportError(f"p2pnetwork_tpu found at {pkg}, not in {HERE}")


# ------------------------------------------------------------- reference


def host_edges(g):
    """The graph's live directed edges and node mask as NumPy arrays."""
    mask = np.asarray(g.edge_mask)
    return (np.asarray(g.senders)[mask], np.asarray(g.receivers)[mask],
            np.asarray(g.node_mask))


def bfs_levels(edges, source: int) -> np.ndarray:
    """Hop distance from ``source`` over ``edges`` (-1: never reached),
    one level per synchronous flood round."""
    src, dst, node_mask = edges
    dist = np.full(node_mask.shape[0], -1, np.int32)
    if not node_mask[source]:
        return dist
    frontier = np.zeros(node_mask.shape[0], bool)
    frontier[source] = True
    dist[source] = 0
    level = 0
    while frontier.any():
        level += 1
        hit = np.zeros_like(frontier)
        hit[dst[frontier[src]]] = True
        frontier = hit & (dist < 0) & node_mask
        dist[frontier] = level
    return dist


def reference_run(dist: np.ndarray, n_live: int, target: float = TARGET,
                  max_rounds: int = MAX_ROUNDS):
    """``(rounds, seen)`` a flood from the BFS's source reaches: the first
    round whose coverage (float32, as the engine computes it) meets
    ``target``, and the nodes within that many hops."""
    reached = dist[dist >= 0]
    counts = np.bincount(reached, minlength=max_rounds + 1).cumsum()
    rounds = max_rounds
    for r in range(max_rounds + 1):
        if np.float32(counts[r]) / np.float32(n_live) >= np.float32(target):
            rounds = r
            break
    return rounds, (dist >= 0) & (dist <= rounds)


def seen_sha256(seen: np.ndarray) -> str:
    """SimService's per-lane seen digest (serve/service.py _hash_lanes)."""
    return hashlib.sha256(np.packbits(seen.astype(np.uint8)).tobytes()
                          ).hexdigest()


# ---------------------------------------------------------------- phases


def device_info() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    dev = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def build_main_graph(n: int):
    """The bench's 1M layout (bench.py ``_graph_spec_1m``) at ``n`` nodes;
    returns ``(graph, build_seconds, native_used)``."""
    from p2pnetwork_tpu import native
    from p2pnetwork_tpu.sim import graph as G

    t0 = time.perf_counter()
    g = G.watts_strogatz(n, 10, 0.1, seed=0, blocked=True, hybrid=True,
                         source_csr=True)
    return g, time.perf_counter() - t0, native.available()


def run_simnode(g, method: str = "auto", source: int = 0, mesh=None,
                comm: str = "ppermute"):
    """One flood to ``TARGET`` through the user-facing node: attach, run,
    stop. Returns ``(summary, seen over the padded node range, wall_s)``."""
    from p2pnetwork_tpu.models.flood import Flood
    from p2pnetwork_tpu.sim.simnode import JaxSimNode

    node = JaxSimNode("127.0.0.1", 0, graph=g, mesh=mesh, comm=comm,
                      protocol=Flood(source=source, method=method))
    node.start()
    try:
        t0 = time.perf_counter()
        summary = node.run_until_coverage(TARGET, max_rounds=MAX_ROUNDS)
        wall = time.perf_counter() - t0
        seen = np.asarray(node.sim_state[0] if mesh is not None
                          else node.sim_state.seen).reshape(-1)
    finally:
        node.stop()
        node.join()
    n_pad = g.n_nodes_padded
    expect(not seen[n_pad:].any(), "seen bits set past the padded range")
    return summary, seen[:n_pad], wall


def compiled_step_text(g, method: str) -> str:
    """The compiled text of one Flood round with ``method`` on ``g``."""
    import jax

    from p2pnetwork_tpu.models.flood import Flood

    proto = Flood(source=0, method=method)
    key = jax.random.key(0)
    state = proto.init(g, key)
    return jax.jit(proto.step).lower(g, state, key).compile().as_text()


def check_against_reference(label: str, summary: dict, seen: np.ndarray,
                            ref_rounds: int, ref_seen: np.ndarray) -> None:
    expect(int(summary["rounds"]) == ref_rounds,
           f"{label}: rounds {summary['rounds']} != BFS {ref_rounds}")
    expect(np.array_equal(seen, ref_seen),
           f"{label}: seen set differs from BFS in "
           f"{int(np.sum(seen != ref_seen))} nodes")


def phase_main(n: int = N_MAIN, *, require_kernel: bool = True) -> dict:
    """The flood at the north-star size, hybrid and the neighbor-table
    gather, against BFS (on a TPU ``auto`` resolves to hybrid here, so the
    gather is named to keep it checked)."""
    from p2pnetwork_tpu.telemetry import Registry, jaxhooks

    g, build_s, native_used = build_main_graph(n)
    edges = host_edges(g)
    n_live = int(edges[2].sum())
    t0 = time.perf_counter()
    ref_rounds, ref_seen = reference_run(bfs_levels(edges, 0), n_live)
    report("main_graph", n_nodes=g.n_nodes, n_edges=g.n_edges,
           build_s=round(build_s, 3), native=native_used,
           bfs_s=round(time.perf_counter() - t0, 3), bfs_rounds=ref_rounds)

    text = compiled_step_text(g, "hybrid")
    has_kernel = "tpu_custom_call" in text
    if require_kernel:
        expect(has_kernel, "compiled hybrid step has no tpu_custom_call")
    report("hybrid_kernel", tpu_custom_call=has_kernel)

    reg = Registry()
    jaxhooks.install(reg)
    try:
        out = {}
        for method in ("hybrid", "gather"):
            c0 = jaxhooks.compile_seconds(reg)
            summary, seen, cold_s = run_simnode(g, method)
            compile_s = jaxhooks.compile_seconds(reg) - c0
            check_against_reference(f"1M {method} cold", summary, seen,
                                    ref_rounds, ref_seen)
            summary, seen, run_s = run_simnode(g, method)
            check_against_reference(f"1M {method}", summary, seen,
                                    ref_rounds, ref_seen)
            report(f"flood_{method}", rounds=int(summary["rounds"]),
                   coverage=float(summary["coverage"]),
                   messages=int(summary["messages"]), equals_bfs=True,
                   cold_s=round(cold_s, 4), compile_s=round(compile_s, 4),
                   run_s=round(run_s, 4), peak_bytes_in_use=peak_bytes())
            out[method] = summary
    finally:
        jaxhooks.uninstall(reg)
    return out


def phase_serving(n: int = N_SERVE, floods: int = SERVE_FLOODS,
                  capacity: int = SERVE_CAPACITY, seed: int = 0) -> dict:
    """Seeded floods through SimService; each ticket against its BFS."""
    from p2pnetwork_tpu.serve import SimService
    from p2pnetwork_tpu.sim import graph as G

    t0 = time.perf_counter()
    g = G.watts_strogatz(n, 10, 0.1, seed=0, source_csr=True)
    build_s = time.perf_counter() - t0
    edges = host_edges(g)
    n_live = int(edges[2].sum())
    sources = np.random.default_rng(seed).integers(0, n, floods)
    refs = [reference_run(bfs_levels(edges, int(s)), n_live,
                          max_rounds=1024) for s in sources]

    svc = SimService(g, capacity=capacity, seed=seed,
                     record_seen_hash=True).start()
    try:
        t0 = time.perf_counter()
        tickets = [svc.submit(int(s), target_coverage=TARGET)
                   for s in sources]
        records = [svc.wait(t, timeout=SERVE_TIMEOUT_S) for t in tickets]
        wall = time.perf_counter() - t0
    finally:
        svc.close()
    for src, rec, (rounds, seen) in zip(sources, records, refs):
        label = f"ticket {rec['ticket']} (source {src})"
        expect(rec["status"] == "done", f"{label}: status {rec['status']}")
        expect(rec["rounds"] == rounds,
               f"{label}: rounds {rec['rounds']} != BFS {rounds}")
        expect(rec["seen_count"] == int(seen.sum()),
               f"{label}: seen {rec['seen_count']} != BFS {int(seen.sum())}")
        expect(rec["seen_sha256"] == seen_sha256(seen),
               f"{label}: seen set differs from BFS")
    report("serving", n_nodes=g.n_nodes, capacity=capacity,
           tickets=len(records), done=len(records), equal_bfs=len(records),
           build_s=round(build_s, 3), wall_s=round(wall, 4),
           rounds_max=max(r["rounds"] for r in records),
           peak_bytes_in_use=peak_bytes())
    return {"records": records}


def phase_four_chips(n: int = N_MAIN, shards: int = 4) -> dict:
    """The 1M flood on a ``shards``-device ring under both halo backends,
    each bit-identical to the single-device engine in this process."""
    import jax

    from p2pnetwork_tpu.parallel.mesh import ring_mesh

    expect(len(jax.devices()) >= shards,
           f"need {shards} devices, JAX sees {len(jax.devices())}")
    g, build_s, native_used = build_main_graph(n)
    edges = host_edges(g)
    ref_rounds, ref_seen = reference_run(bfs_levels(edges, 0),
                                         int(edges[2].sum()))
    # hybrid: the single-device lowering that compiles in seconds (the
    # gather takes minutes at 1M, paid here on four chips).
    with jax.default_device(jax.devices()[0]):
        single, single_seen, single_cold_s = run_simnode(g, "hybrid")
    check_against_reference("single-device", single, single_seen,
                            ref_rounds, ref_seen)
    report("ring_reference", n_nodes=g.n_nodes, build_s=round(build_s, 3),
           native=native_used, device=str(jax.devices()[0]),
           rounds=int(single["rounds"]), messages=int(single["messages"]),
           cold_s=round(single_cold_s, 4))
    mesh = ring_mesh(shards)
    out = {}
    for comm in ("ppermute", "pallas"):
        cold, seen, cold_s = run_simnode(g, mesh=mesh, comm=comm)
        summary, seen, run_s = run_simnode(g, mesh=mesh, comm=comm)
        for got in (cold, summary):
            for k in ("rounds", "coverage", "messages"):
                expect(got[k] == single[k],
                       f"ring {comm}: {k} {got[k]} != single-device "
                       f"{single[k]}")
        expect(np.array_equal(seen, single_seen),
               f"ring {comm}: seen set differs from the single device")
        report(f"ring_{comm}", shards=shards, rounds=int(summary["rounds"]),
               coverage=float(summary["coverage"]),
               messages=int(summary["messages"]),
               bit_identical_to_single=True, cold_s=round(cold_s, 4),
               run_s=round(run_s, 4), peak_bytes_in_use=peak_bytes())
        out[comm] = summary
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device ring and its single-device "
                         "reference")
    args = ap.parse_args(argv)
    try:
        _import_repo()
        info = device_info()
        if info["platform"] != "tpu":
            print(f"chip_smoke: JAX found platform {info['platform']!r} "
                  f"({info['kind']}), not a TPU", file=sys.stderr)
            return 2
        from p2pnetwork_tpu.utils.jax_env import enable_compile_cache

        report("device", cache=enable_compile_cache(), **info)
        if args.four_chips:
            phase_four_chips()
        else:
            phase_main()
            phase_serving()
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["kind"],
        "count": info["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Fault injection: node and edge failures as first-class, testable inputs.

The reference's failure story is reactive — a send/recv error tears down
that connection [ref: nodeconnection.py:123-126, :201-204] and reconnect
policy decides retry-vs-giveup [ref: node.py:203-225]. There is no way to
*inject* failures. In the sim backend failure is a feature (SURVEY.md
section 5 "Failure detection"): killing nodes or links flips mask bits in
device arrays — same shapes, no recompile, the next round simply routes
around (or into) the damage. That makes partition tolerance, epidemic
die-out, and coverage-under-churn testable properties (SURVEY.md section 7
hard part 4: capacity-padded adjacency + active masks).

Every function returns a NEW Graph with every carried representation
(COO masks, degrees, neighbor table, blocked kernel layout, hybrid
diagonals) consistently re-masked, entirely device-side. Failures are
fail-stop and one-way on the returned copy — keep the original Graph
object around to "restore" (it is immutable and untouched).
"""

from __future__ import annotations

import dataclasses

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from p2pnetwork_tpu import telemetry
from p2pnetwork_tpu.sim.graph import Graph


def _count_injected(kind: str, ids=None) -> None:
    """Injected failures are experiment inputs; counting them in the same
    registry as the protocol's own metrics lets a churn run report "N
    failures injected, coverage held at X" from one snapshot. For the
    deterministic APIs the increment is the entity count; for traced ids or
    the random_* draws (whose realized count lives on device) it is the
    injection-call count, under a distinct ``<kind>_draw`` label."""
    n = 1
    if ids is not None:
        try:
            n = int(np.asarray(ids).size)
        except Exception:
            n = 1  # traced ids: count the injection, not the entities
    telemetry.default_registry().counter(
        "sim_injected_failures_total",
        "Failures injected into sim graphs, by kind (entity counts for "
        "deterministic kinds, draw counts for *_draw).",
        ("kind",)).labels(kind).inc(n)


def _check_ids_in_range(ids, bound: int, what: str) -> None:
    """Host-side bounds check (JAX scatter silently drops out-of-bounds
    indices — a typo'd id would silently leave the graph undamaged).
    Skipped for traced ids, which cannot be inspected."""
    try:
        arr = np.asarray(ids)
    except Exception:
        return
    if arr.size and (arr.min() < 0 or arr.max() >= bound):
        raise ValueError(f"{what} id out of range [0, {bound})")


def _degrees(graph: Graph, edge_mask: jax.Array,
             dyn_mask: Optional[jax.Array] = None):
    """(in_degree, out_degree) recomputed from surviving-edge masks —
    static COO plus the dynamic region (sim/topology.py), if present."""
    live = edge_mask.astype(jnp.int32)
    in_degree = jax.ops.segment_sum(
        live, graph.receivers,
        num_segments=graph.n_nodes_padded, indices_are_sorted=True,
    )
    out_degree = jnp.zeros(graph.n_nodes_padded, jnp.int32).at[
        graph.senders].add(live)
    if dyn_mask is not None:
        dlive = dyn_mask.astype(jnp.int32)
        in_degree = in_degree.at[graph.dyn_receivers].add(dlive)
        out_degree = out_degree.at[graph.dyn_senders].add(dlive)
    return in_degree, out_degree


def _remask_blocked(blocked, node_alive: jax.Array):
    """Re-mask a BlockedEdges for the given per-node liveness."""
    if blocked is None:
        return None
    nb, w = blocked.src.shape
    block_base = jnp.arange(nb, dtype=jnp.int32)[:, None] * blocked.block
    global_dst = jnp.minimum(block_base + blocked.local_dst,
                             node_alive.shape[0] - 1)
    mask = blocked.mask & node_alive[blocked.src] & node_alive[global_dst]
    return dataclasses.replace(blocked, mask=mask)


def _remask_hybrid(hybrid, node_alive: jax.Array):
    """Re-mask a HybridEdges: diagonal masks need both endpoints alive."""
    if hybrid is None:
        return None
    core = node_alive[: hybrid.n]
    if len(hybrid.offsets):
        # mask[d, v] needs v alive and (v + off) % n alive.
        src_alive = jnp.stack(
            [jnp.roll(core, -off) for off in hybrid.offsets], axis=0
        )
        masks = hybrid.masks & core[None, :] & src_alive
    else:
        masks = hybrid.masks
    return dataclasses.replace(
        hybrid,
        masks=masks,
        remainder=_remask_blocked(hybrid.remainder, node_alive),
    )


def _remask_skew_nodes(skew, node_alive: jax.Array):
    if skew is None:
        return None
    from p2pnetwork_tpu.ops import skew as SK

    return SK.remask_nodes(skew, node_alive)


def with_node_liveness(graph: Graph, node_alive: jax.Array) -> Graph:
    """Apply a liveness mask (bool[N_pad]; False = failed) to ``graph``.

    An edge is active iff it was active and both endpoints live; degrees
    are recomputed from the surviving edges; the neighbor table and the
    blocked/hybrid kernel layouts are re-masked in place (no host rebuild,
    no recompile — shapes are unchanged).
    """
    node_mask = graph.node_mask & node_alive
    edge_mask = (
        graph.edge_mask & node_mask[graph.senders] & node_mask[graph.receivers]
    )
    dyn_mask = graph.dyn_mask
    if dyn_mask is not None:
        # Dynamic links (sim/topology.py) die with either endpoint too.
        dyn_mask = (
            dyn_mask
            & node_mask[graph.dyn_senders]
            & node_mask[graph.dyn_receivers]
        )
    in_degree, out_degree = _degrees(graph, edge_mask, dyn_mask)
    neighbors = graph.neighbors
    neighbor_mask = graph.neighbor_mask
    if neighbor_mask is not None:
        neighbor_mask = (
            neighbor_mask & node_mask[:, None] & node_mask[neighbors]
        )
    return dataclasses.replace(
        graph,
        node_mask=node_mask,
        edge_mask=edge_mask,
        dyn_mask=dyn_mask,
        in_degree=in_degree,
        out_degree=out_degree,
        neighbor_mask=neighbor_mask,
        blocked=_remask_blocked(graph.blocked, node_mask),
        hybrid=_remask_hybrid(graph.hybrid, node_mask),
        skew=_remask_skew_nodes(graph.skew, node_mask),
    )


def fail_nodes(graph: Graph, node_ids) -> Graph:
    """Fail-stop the given node ids (crashed peers: they neither send nor
    receive; their edges die with them)."""
    _check_ids_in_range(node_ids, graph.n_nodes_padded, "node")
    _count_injected("node", node_ids)
    ids = jnp.asarray(node_ids, dtype=jnp.int32)
    alive = jnp.ones(graph.n_nodes_padded, dtype=bool).at[ids].set(False)
    return with_node_liveness(graph, alive)


def mark_unresponsive(graph: Graph, node_ids) -> Graph:
    """Flip ``node_mask`` for the given ids WITHOUT re-masking edges,
    degrees, or the neighbor table — the crashed-but-still-configured
    view a failure DETECTOR needs: survivors still hold the dead peer in
    their tables (the reference keeps the socket in ``nodes_inbound``
    until a timeout fires [ref: nodeconnection.py]) and must discover the
    silence by probing. For every other protocol use :func:`fail_nodes`,
    which models the loss consistently (a mark-only graph still counts
    the dead peer's table slots as live links)."""
    _check_ids_in_range(node_ids, graph.n_nodes_padded, "node")
    _count_injected("node_unresponsive", node_ids)
    ids = jnp.asarray(node_ids, dtype=jnp.int32)
    node_mask = graph.node_mask.at[ids].set(False)
    return dataclasses.replace(graph, node_mask=node_mask)


def with_edge_liveness(graph: Graph, edge_alive: jax.Array) -> Graph:
    """Apply a per-edge liveness mask (bool[E_pad]; False = cut link).

    Directed: cutting one direction of an undirected pair leaves the other
    alive. Degrees are recomputed; a complete neighbor table is re-masked
    exactly (slot ``s`` of row ``v`` is COO edge ``starts[v] + s``, so the
    edge mask scatters straight into the table); a width-capped table has
    lost its slot->edge mapping and is dropped. Graphs carrying the
    blocked/hybrid kernel layouts must use node failures or rebuild —
    their edge order differs and a silent partial update would be wrong.
    """
    if graph.blocked is not None or graph.hybrid is not None:
        raise ValueError(
            "edge-level failures on a graph with blocked/hybrid "
            "representations would desynchronize them; use fail_nodes / "
            "with_node_liveness, or rebuild from the surviving edge list"
        )
    edge_mask = graph.edge_mask & edge_alive
    in_degree, out_degree = _degrees(graph, edge_mask, graph.dyn_mask)
    neighbors = graph.neighbors
    neighbor_mask = graph.neighbor_mask
    if neighbor_mask is not None:
        if graph.neighbors_complete:
            starts = jnp.searchsorted(
                graph.receivers, jnp.arange(graph.n_nodes_padded)
            )
            width = neighbors.shape[1]
            take = starts[:, None] + jnp.arange(width)[None, :]
            take = jnp.minimum(take, graph.n_edges_padded - 1)
            neighbor_mask = neighbor_mask & edge_mask[take]
        else:
            # Capped rows are a random edge subset; the slot->edge map is
            # gone, so the table cannot be re-masked exactly.
            neighbors = None
            neighbor_mask = None
    skew = graph.skew
    if skew is not None:
        # The two-level table keeps its slot->edge map (SkewTable.start),
        # so edge cuts re-mask it exactly, device-side.
        from p2pnetwork_tpu.ops import skew as SK

        skew = SK.remask_edges(skew, edge_mask, graph.n_edges_padded)
    return dataclasses.replace(
        graph,
        edge_mask=edge_mask,
        in_degree=in_degree,
        out_degree=out_degree,
        neighbors=neighbors,
        neighbor_mask=neighbor_mask,
        skew=skew,
    )


def fail_edges(graph: Graph, edge_ids) -> Graph:
    """Cut specific links (indices into the edge arrays)."""
    _check_ids_in_range(edge_ids, graph.n_edges_padded, "edge")
    _count_injected("edge", edge_ids)
    ids = jnp.asarray(edge_ids, dtype=jnp.int32)
    alive = jnp.ones(graph.n_edges_padded, dtype=bool).at[ids].set(False)
    return with_edge_liveness(graph, alive)


def revive_nodes(graph: Graph, node_ids, original: Graph) -> Graph:
    """Un-fail the given node ids, restoring their ``original`` wiring.

    The inverse of :func:`kill_nodes` on the sockets chaos plane
    (chaos/plane.py). A failed graph has already zeroed the dead nodes'
    edges, so reviving needs the pre-failure ``original`` to know what to
    restore: the result is ``original`` re-masked to (previously live ∪
    revived) nodes. Edge-level cuts applied after ``original`` was taken
    are forgotten — revive node-level damage before link-level damage, or
    reapply the cuts."""
    _check_ids_in_range(node_ids, graph.n_nodes_padded, "node")
    _count_injected("node_revive", node_ids)
    ids = jnp.asarray(node_ids, dtype=jnp.int32)
    revived = jnp.zeros(graph.n_nodes_padded, dtype=bool).at[ids].set(True)
    alive = graph.node_mask | (revived & original.node_mask)
    return with_node_liveness(original, alive)


def partition(graph: Graph, groups) -> Graph:
    """Cut every edge crossing between the node-id ``groups`` — static COO
    and dynamic-region links (sim/topology.py) both, so not a byte leaks
    across the split (nodes in no group are unconstrained) — the sim
    mirror of ``ChaosPlane.partition``. Keep the original graph around to
    heal. Uses edge-level liveness, so blocked/hybrid kernel graphs must
    use node failures or rebuild (see :func:`with_edge_liveness`)."""
    side = np.full(graph.n_nodes_padded, -1, dtype=np.int64)
    for gi, group in enumerate(groups):
        ids = np.asarray(group, dtype=np.int64)  # graftlint: ignore[host-sync-in-loop] -- groups are host-side id lists, never device arrays
        _check_ids_in_range(ids, graph.n_nodes_padded, "node")
        side[ids] = gi
    _count_injected("partition")

    def _crossing(senders, receivers):
        s, r = np.asarray(senders), np.asarray(receivers)
        return (side[s] >= 0) & (side[r] >= 0) & (side[s] != side[r])

    gp = with_edge_liveness(
        graph, jnp.asarray(~_crossing(graph.senders, graph.receivers)))
    if graph.dyn_mask is not None:
        # with_edge_liveness passes the dynamic region through untouched;
        # a runtime-added link spanning the split must die too.
        dyn_mask = gp.dyn_mask & jnp.asarray(
            ~_crossing(graph.dyn_senders, graph.dyn_receivers))
        in_degree, out_degree = _degrees(gp, gp.edge_mask, dyn_mask)
        gp = dataclasses.replace(gp, dyn_mask=dyn_mask,
                                 in_degree=in_degree, out_degree=out_degree)
    return gp


#: Name-for-name aliases shared with the sockets chaos plane
#: (chaos/plane.py): one failure-scenario vocabulary on both backends.
kill_nodes = fail_nodes
cut_links = fail_edges


def preempt(run, at_round: int):
    """Arm a deterministic preemption of a supervised run harness.

    The other fault kinds in this module damage the *simulated network*;
    ``preempt`` damages the *run itself* — the machine it executes on is
    reclaimed, as a preempted machine or a driver timeout does for real.
    ``run`` is a
    :class:`~p2pnetwork_tpu.supervise.runner.SupervisedRun` (anything with
    ``arm_preemption``); at the first chunk boundary at or past
    ``at_round`` it raises
    :class:`~p2pnetwork_tpu.supervise.runner.Preempted` *before* taking
    the checkpoint due there, so the durable trail ends where a real
    SIGKILL's would. Reviving is calling the same ``run_*`` entry again —
    it resumes from the last durable checkpoint, and the revived run's
    final state is bit-identical to an uninterrupted one (the supervised
    determinism contract). Counted as
    ``sim_injected_failures_total{kind="preempt"}`` like every other
    injected fault. Returns ``run`` for chaining."""
    _count_injected("preempt")
    run.arm_preemption(int(at_round))
    return run


def random_node_failures(graph: Graph, key: jax.Array, frac: float) -> Graph:
    """Fail each live node independently with probability ``frac`` —
    the churn model for coverage-under-failure experiments."""
    _count_injected("node_draw")
    alive = ~(
        jax.random.bernoulli(key, frac, (graph.n_nodes_padded,))
        & graph.node_mask
    )
    return with_node_liveness(graph, alive)


def random_edge_failures(graph: Graph, key: jax.Array, frac: float) -> Graph:
    """Cut each live directed edge independently with probability ``frac``."""
    _count_injected("edge_draw")
    cut = jax.random.bernoulli(key, frac, (graph.n_edges_padded,))
    return with_edge_liveness(graph, ~cut)

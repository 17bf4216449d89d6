"""Edge-aggregation primitives — the hot ops of the simulation backend.

One flooding/gossip round in the reference is an O(peers) sequential Python
loop of socket sends per node [ref: p2pnetwork/node.py:110-112] plus a 10 ms
poll per connection [ref: nodeconnection.py:220]. Here the same round is one
batched aggregation over every edge of the population at once:

- ``propagate_or``  — per-receiver OR of a boolean node signal (flooding:
  "did any of my neighbors have the message?")
- ``propagate_sum`` — per-receiver sum of a float node signal (gossip / SIR:
  infection pressure, value accumulation)
- ``frontier_messages`` — how many point-to-point messages this round
  corresponds to (the sim-side ``message_count`` parity metric).

Two lowerings, chosen by what the graph carries:

- ``segment``: COO edges sorted by receiver -> ``jax.ops.segment_*`` with
  ``indices_are_sorted=True``. General, handles any degree distribution.
- ``gather``: padded neighbor table ``[N, max_degree]`` -> row-wise gather +
  masked reduce along the degree axis. Dense, regular memory traffic that
  maps well onto TPU vector units for quasi-regular graphs; this shape is
  also what the Pallas kernel implements (ops/pallas_edge.py).

A third family prices the round by the FRONTIER instead of the graph:
``method="frontier"`` (ops/frontier.py) compacts the active nodes inside
jit and gathers only their out-edge rows through the source-CSR view,
falling back to the dense path via ``lax.cond`` when the active count
exceeds the crossover budget — the fast path for the sparse first/last
rounds of a flood. Requires ``from_edges(source_csr=True)``.

What ``auto`` picks for an OR aggregation (``_auto_or_method``): on a TPU,
the hybrid diagonal + Pallas lowering (ops/diag.py) when the graph carries
a hybrid layout whose circular diagonals hold at least half of its edges
(``_HYBRID_DIAG_SHARE``; ~90% on Watts-Strogatz at p = 0.1, almost none on
ER/BA); otherwise, and on every other backend, the table / skew / segment
routing of ``_auto_method``. On one v5e, a 1M-node WS flood (k = 10,
p = 0.1) to 99% ran 0.1111 s a flood through ``hybrid`` against 1.4728 s
through the table gather, in the same 11 rounds with the same seen set,
and compiled in 1.4 s against 218 s. In the benchmark's ``ws-1m.flood``
cell (one v5e, a new source per flood through ``JaxSimNode``) the route
took a flood from 2.10-2.12 s to 0.33-0.35 s and a round's device time
from ~133 ms to 9.8 ms, with every flood equal to the BFS. Sum, max and
min-plus keep ``_auto_method``: max and min-plus have no hybrid form, and
a float sum through the diagonals adds in another order. OR over 0/1 is
exact in any order. Each resolution counts into
``aggregation_route_total{op="or", method}`` at trace time, so the counter
counts traced programs, not rounds.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from p2pnetwork_tpu import telemetry
from p2pnetwork_tpu.sim.graph import Graph


#: ``auto`` prefers the neighbor-table gather only while the table's
#: padding waste (slots / true edges) stays under this bound. The padded
#: gather touches rows x width slots at the TPU's flat ~8 cycles/element
#: gather floor (BENCH.md), so a degree-skewed graph poisons it: one hub
#: widens EVERY row. Measured on chip (single-source flood to 99%):
#: WS 1M @ 1.7x waste — gather 1.53 s vs segment 1.87 s (gather wins);
#: ER 100K @ 2.5x — 0.142 vs 0.163 s (gather wins); BA 100K @ 178x —
#: 3.97 vs 0.12 s (gather loses 33x). Break-even sits near 3-4x. For OR
#: on a TPU the hybrid lowering outranks the gather where the graph
#: carries one (``_auto_or_method``): on the 1M WS flood the gather costs
#: ~133 ms a round and the hybrid ~10 ms.
_GATHER_WASTE_BOUND = 4.0

#: ``auto`` routes an OR aggregation to ``hybrid`` on a TPU only when the
#: layout's diagonals hold at least this share of the graph's edges: the
#: shifts then carry most of the round and the Pallas remainder the rest.
#: WS at p = 0.1 keeps ~90% on its diagonals; ER and BA keep almost none,
#: since a diagonal needs ``max(n // 256, 128)`` edges to be kept.
_HYBRID_DIAG_SHARE = 0.5


def _gather_ok(graph: Graph) -> bool:
    if graph.neighbors is None or not graph.neighbors_complete:
        return False
    slots = graph.neighbors.shape[0] * graph.neighbors.shape[1]
    return slots <= _GATHER_WASTE_BOUND * max(graph.n_edges, 1)


def _auto_method(graph: Graph) -> str:
    """``auto``'s routing: the plain table while its waste is bounded; the
    two-level skew table when the graph carries one (its waste is bounded
    by construction — attaching it signals a degree-skewed family, where
    it beats segment's per-edge constant); segment otherwise."""
    if _gather_ok(graph):
        return "gather"
    if graph.skew is not None:
        return "skew"
    return "segment"


def _auto_or_method(graph: Graph, backend: str | None = None) -> str:
    """``auto``'s routing for OR: ``hybrid`` on a TPU when the graph's
    hybrid layout keeps its Pallas remainder (not placed on a mesh) and
    its diagonals hold at least ``_HYBRID_DIAG_SHARE`` of the edges;
    ``_auto_method`` otherwise. ``backend`` defaults to JAX's."""
    hybrid = graph.hybrid
    if (hybrid is not None and hybrid.remainder_kernel == "pallas"
            and hybrid.n_diag_edges
            >= _HYBRID_DIAG_SHARE * max(graph.n_edges, 1)
            and (backend or jax.default_backend()) == "tpu"):
        return "hybrid"
    return _auto_method(graph)


def _route_counter():
    return telemetry.default_registry().counter(
        "aggregation_route_total",
        "Lowerings auto resolved, by aggregation; counted at trace time, "
        "so once per traced program.", ("op", "method"))


def _require_skew(graph: Graph) -> None:
    if graph.skew is None:
        raise ValueError(
            "method='skew' requires the two-level neighbor table — build "
            "with from_edges(skew_table=True) or graph.with_skew_table()"
        )


def _require_complete_table(graph: Graph) -> None:
    if graph.neighbors is None:
        raise ValueError("method='gather' requires a graph with a neighbor table")
    if not graph.neighbors_complete:
        raise ValueError(
            "method='gather' on a width-capped neighbor table "
            "(from_edges(max_degree=...)) would silently drop edges; use "
            "method='segment' for exact aggregation on this graph"
        )


def _dynamic_or(graph: Graph, signal: jax.Array) -> jax.Array:
    """OR-aggregate the dynamic edge region (sim/topology.py), if any."""
    contrib = (signal[graph.dyn_senders] & graph.dyn_mask).astype(jnp.int32)
    agg = jax.ops.segment_max(
        contrib, graph.dyn_receivers, num_segments=graph.n_nodes_padded
    )
    return (agg > 0) & graph.node_mask


def _dynamic_sum(graph: Graph, signal: jax.Array) -> jax.Array:
    """Sum-aggregate the dynamic edge region (sim/topology.py), if any."""
    contrib = signal[graph.dyn_senders] * graph.dyn_mask.astype(signal.dtype)
    agg = jax.ops.segment_sum(
        contrib, graph.dyn_receivers, num_segments=graph.n_nodes_padded
    )
    return agg * graph.node_mask.astype(signal.dtype)


def propagate_or(graph: Graph, signal: jax.Array, method: str = "auto", *,
                 frontier_crossover=None) -> jax.Array:
    """Per-node OR over incoming neighbors: ``out[v] = any(signal[u], u->v)``.

    ``signal`` is bool[N_pad]; masked (padding) edges and nodes contribute
    nothing. ``method`` is ``"segment"``, ``"gather"``, ``"skew"``,
    ``"blocked"``, ``"pallas"``, ``"hybrid"``, ``"hybrid-blocked"``,
    ``"frontier"`` or ``"auto"``. ``auto`` takes ``hybrid`` on a TPU
    when the graph's hybrid layout holds at least half its edges on
    diagonals (11 rounds of a 1M WS flood: 0.1111 s against the gather's
    1.4728 s on one v5e); else gather when the graph carries a complete
    neighbor table whose padding waste stays under
    ``_GATHER_WASTE_BOUND``; degree-skewed tables route to skew or
    segment. Every choice is exact for OR. Dynamic edges
    (sim/topology.py) are folded in for every method.
    ``frontier_crossover`` overrides the ``method="frontier"`` sparse
    budget (ops/frontier.py ``budget``: float = fraction of padded
    nodes, int = node budget) — the supported "apply" step for a
    crossover re-fit from measured occupancy.
    """
    if graph.dyn_senders is not None:
        static = dataclasses.replace(graph, dyn_senders=None,
                                     dyn_receivers=None, dyn_mask=None)
        return (propagate_or(static, signal, method,
                             frontier_crossover=frontier_crossover)
                | _dynamic_or(graph, signal))
    if method == "frontier":
        from p2pnetwork_tpu.ops import frontier as FR

        return FR.propagate_or_frontier(
            graph, signal, lambda sig: propagate_or(graph, sig, "auto"),
            crossover=frontier_crossover)
    if method == "auto":
        method = _auto_or_method(graph)
        _route_counter().labels(op="or", method=method).inc()
    if method == "gather":
        _require_complete_table(graph)
        vals = signal[graph.neighbors] & graph.neighbor_mask
        return jnp.any(vals, axis=1) & graph.node_mask
    if method == "skew":
        from p2pnetwork_tpu.ops import skew as SK

        _require_skew(graph)
        return SK.or_skew(graph.skew, signal,
                          graph.n_nodes_padded) & graph.node_mask
    if method in ("blocked", "pallas"):
        from p2pnetwork_tpu.ops import blocked as B
        from p2pnetwork_tpu.ops import pallas_edge as PK

        if graph.blocked is None:
            raise ValueError(f"method={method!r} requires graph.with_blocked()")
        fn = B.propagate_or_blocked if method == "blocked" else PK.propagate_or_pallas
        return fn(graph.blocked, signal, graph.node_mask)
    if method in ("hybrid", "hybrid-blocked"):
        from p2pnetwork_tpu.ops import diag as D

        if graph.hybrid is None:
            raise ValueError(f"method={method!r} requires graph.with_hybrid()")
        kernel = "pallas" if method == "hybrid" else "blocked"
        return D.propagate_or_hybrid(graph.hybrid, signal, graph.node_mask,
                                     kernel=kernel)
    contrib = (signal[graph.senders] & graph.edge_mask).astype(jnp.int32)
    agg = jax.ops.segment_max(
        contrib,
        graph.receivers,
        num_segments=graph.n_nodes_padded,
        indices_are_sorted=True,
    )
    return (agg > 0) & graph.node_mask


def _dynamic_or_lanes(graph: Graph, word: jax.Array) -> jax.Array:
    """Lane-packed OR over the dynamic edge region (sim/topology.py), one
    word (``u32[N_pad]``) at a time: bit-plane expand the (small) dynamic
    region's contributions and segment-max them per lane."""
    from p2pnetwork_tpu.ops import bitset

    contrib = jnp.where(graph.dyn_mask, word[graph.dyn_senders],
                        jnp.uint32(0))
    planes = jax.ops.segment_max(
        bitset.expand_lanes(contrib).astype(jnp.uint8),
        graph.dyn_receivers, num_segments=graph.n_nodes_padded,
    )
    return bitset.collapse_lanes(planes > 0) & jnp.where(
        graph.node_mask, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))


def propagate_or_lanes(graph: Graph, lanes: jax.Array,
                       method: str = "auto", *,
                       frontier_crossover=None) -> jax.Array:
    """Lane-packed neighbor-OR: 32·W concurrent boolean signals advanced
    by one round in one program — ``lanes`` is ``u32[W, N_pad]`` where bit
    L of word w at node v is message ``32w+L``'s signal (ops/bitset.py
    lane algebra), and ``out[w, v] = OR(lanes[w, u], u->v)`` word-level.

    This is :func:`propagate_or` batched across messages instead of
    called B times: the graph traffic (neighbor-row gathers, edge
    contributions) is priced PER WORD, so 32 messages ride each gathered
    element. Methods:

    - ``"gather"``: one u32 gather of each node's neighbor row serves all
      32 lanes of a word; the degree-axis reduce is a word-level bitwise
      OR. Same complete-table requirement as :func:`propagate_or`.
    - ``"segment"``: per-edge contributions bit-plane-expanded (uint8
      ``[E_pad, 32]``) through the sorted-receiver segment-max — the
      any-graph fallback (no table needed).
    - ``"frontier"``: union-frontier compaction shared across all words,
      32-message-wide scatter-OR (ops/frontier.py
      ``propagate_or_lanes_frontier``); dense fallback is ``auto``.
    - ``"auto"``: gather under the same waste bound as the scalar path,
      else segment (the skew/MXU lowerings have no word-level form —
      degree-skewed tables route to segment).

    Dynamic edges fold in for every method. Padding lanes are harmless:
    an unused lane's bits are never seeded, and OR propagates nothing
    from nothing. ``frontier_crossover`` as in :func:`propagate_or`.
    """
    if graph.dyn_senders is not None:
        static = dataclasses.replace(graph, dyn_senders=None,
                                     dyn_receivers=None, dyn_mask=None)
        return (propagate_or_lanes(static, lanes, method,
                                   frontier_crossover=frontier_crossover)
                | jax.vmap(lambda w: _dynamic_or_lanes(graph, w))(lanes))
    if method == "frontier":
        from p2pnetwork_tpu.ops import frontier as FR

        return FR.propagate_or_lanes_frontier(
            graph, lanes, lambda ln: propagate_or_lanes(graph, ln, "auto"),
            crossover=frontier_crossover)
    if method == "auto":
        method = "gather" if _gather_ok(graph) else "segment"
    node_lanes = jnp.where(graph.node_mask, jnp.uint32(0xFFFFFFFF),
                           jnp.uint32(0))
    if method == "gather":
        _require_complete_table(graph)

        def word_gather(wl):
            vals = jnp.where(graph.neighbor_mask, wl[graph.neighbors],
                             jnp.uint32(0))
            return jax.lax.reduce(vals, jnp.uint32(0),
                                  jax.lax.bitwise_or, (1,))

        return jax.vmap(word_gather)(lanes) & node_lanes
    if method == "segment":
        from p2pnetwork_tpu.ops import bitset

        def word_segment(wl):
            contrib = jnp.where(graph.edge_mask, wl[graph.senders],
                                jnp.uint32(0))
            planes = jax.ops.segment_max(
                bitset.expand_lanes(contrib).astype(jnp.uint8),
                graph.receivers, num_segments=graph.n_nodes_padded,
                indices_are_sorted=True,
            )
            return bitset.collapse_lanes(planes > 0)

        return jax.vmap(word_segment)(lanes) & node_lanes
    raise ValueError(
        f"propagate_or_lanes supports method 'segment', 'gather', "
        f"'frontier' or 'auto', got {method!r} (the skew/MXU lowerings "
        f"have no word-level form)"
    )


def propagate_sum(graph: Graph, signal: jax.Array, method: str = "auto",
                  exact: bool = True) -> jax.Array:
    """Per-node sum over incoming neighbors: ``out[v] = sum(signal[u], u->v)``.
    Dynamic edges (sim/topology.py) are folded in for every method.

    ``exact=False`` lets the MXU-kernel methods run single-pass (inputs
    rounded to bf16). Safe whenever the signal's values are exactly
    representable in bf16 — 0/1 indicators (SIR infection pressure) and
    small integers: products stay exact and the accumulator is f32 either
    way, so the result is bit-identical at ~3x less MXU work.
    """
    if graph.dyn_senders is not None:
        static = dataclasses.replace(graph, dyn_senders=None,
                                     dyn_receivers=None, dyn_mask=None)
        return (propagate_sum(static, signal, method, exact)
                + _dynamic_sum(graph, signal))
    if method == "auto":
        method = _auto_method(graph)
    if method == "gather":
        _require_complete_table(graph)
        vals = signal[graph.neighbors] * graph.neighbor_mask.astype(signal.dtype)
        return jnp.sum(vals, axis=1) * graph.node_mask.astype(signal.dtype)
    if method == "skew":
        from p2pnetwork_tpu.ops import skew as SK

        _require_skew(graph)
        agg = SK.sum_skew(graph.skew, signal, graph.n_nodes_padded)
        return agg * graph.node_mask.astype(signal.dtype)
    if method in ("blocked", "pallas"):
        from p2pnetwork_tpu.ops import blocked as B
        from p2pnetwork_tpu.ops import pallas_edge as PK

        if graph.blocked is None:
            raise ValueError(f"method={method!r} requires graph.with_blocked()")
        if method == "blocked":
            return B.propagate_sum_blocked(graph.blocked, signal, graph.node_mask)
        return PK.propagate_sum_pallas(graph.blocked, signal, graph.node_mask,
                                       exact=exact)
    if method in ("hybrid", "hybrid-blocked"):
        from p2pnetwork_tpu.ops import diag as D

        if graph.hybrid is None:
            raise ValueError(f"method={method!r} requires graph.with_hybrid()")
        kernel = "pallas" if method == "hybrid" else "blocked"
        return D.propagate_sum_hybrid(graph.hybrid, signal, graph.node_mask,
                                      exact=exact, kernel=kernel)
    contrib = signal[graph.senders] * graph.edge_mask.astype(signal.dtype)
    agg = jax.ops.segment_sum(
        contrib,
        graph.receivers,
        num_segments=graph.n_nodes_padded,
        indices_are_sorted=True,
    )
    return agg * graph.node_mask.astype(signal.dtype)


def neutral_min(dtype) -> jax.Array:
    """The max-aggregation identity for ``dtype`` (-inf / int min)."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    if jnp.issubdtype(dtype, jnp.bool_):
        raise ValueError(
            "max-aggregation over bool signals is just OR — use "
            "propagate_or / sharded.propagate(op='or') instead"
        )
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def _dynamic_max(graph: Graph, signal: jax.Array) -> jax.Array:
    """Max-aggregate the dynamic edge region (sim/topology.py), if any."""
    neutral = neutral_min(signal.dtype)
    contrib = jnp.where(graph.dyn_mask, signal[graph.dyn_senders], neutral)
    return jax.ops.segment_max(
        contrib, graph.dyn_receivers, num_segments=graph.n_nodes_padded
    )


def propagate_max(graph: Graph, signal: jax.Array,
                  method: str = "auto", *,
                  frontier_crossover=None) -> jax.Array:
    """Per-node max over incoming neighbors: ``out[v] = max(signal[u], u->v)``.

    Nodes with no (live) incoming edges get the dtype's max-identity
    (-inf / int min); dead nodes likewise — callers typically fold the
    result with their own value (``jnp.maximum(value, incoming)``), which
    makes both neutral. Methods: ``"segment"`` or ``"gather"`` (``"auto"``
    picks gather when a complete, not-too-padded neighbor table exists —
    see ``_GATHER_WASTE_BOUND``). The blocked /
    pallas / hybrid lowerings do not apply — they ride one-hot MXU
    matmuls, which compute sums, not maxima.
    """
    neutral = neutral_min(signal.dtype)
    if graph.dyn_senders is not None:
        static = dataclasses.replace(graph, dyn_senders=None,
                                     dyn_receivers=None, dyn_mask=None)
        return jnp.maximum(
            propagate_max(static, signal, method,
                          frontier_crossover=frontier_crossover),
            _dynamic_max(graph, signal))
    if method == "frontier":
        from p2pnetwork_tpu.ops import frontier as FR

        return FR.propagate_max_frontier(
            graph, signal, neutral,
            lambda sig: propagate_max(graph, sig, "auto"),
            crossover=frontier_crossover)
    if method == "auto":
        method = _auto_method(graph)
    if method == "gather":
        _require_complete_table(graph)
        vals = jnp.where(graph.neighbor_mask, signal[graph.neighbors],
                         neutral)
        agg = jnp.max(vals, axis=1)
    elif method == "skew":
        from p2pnetwork_tpu.ops import skew as SK

        _require_skew(graph)
        agg = SK.max_skew(graph.skew, signal, graph.n_nodes_padded, neutral)
    elif method == "segment":
        contrib = jnp.where(graph.edge_mask, signal[graph.senders], neutral)
        agg = jax.ops.segment_max(
            contrib,
            graph.receivers,
            num_segments=graph.n_nodes_padded,
            indices_are_sorted=True,
        )
    else:
        raise ValueError(
            f"propagate_max supports method 'segment', 'gather', 'skew' or "
            f"'frontier', got {method!r} (max does not ride the "
            f"one-hot-matmul lowerings)"
        )
    return jnp.where(graph.node_mask, agg, neutral)


#: Cost of a dynamic runtime link (sim/topology.py connect) in weighted
#: propagation — the dynamic region has no weight channel, so new links
#: enter at unit cost until topology.consolidate folds them in (where
#: they keep that cost as a static weight).
DYNAMIC_LINK_COST = 1.0


def _dynamic_min_plus(graph: Graph, dist: jax.Array) -> jax.Array:
    """Min-plus over the dynamic edge region (unit link cost)."""
    contrib = jnp.where(graph.dyn_mask,
                        dist[graph.dyn_senders] + DYNAMIC_LINK_COST,
                        jnp.inf)
    return jax.ops.segment_min(
        contrib, graph.dyn_receivers, num_segments=graph.n_nodes_padded
    )


def propagate_min_plus(graph: Graph, dist: jax.Array,
                       method: str = "auto", *,
                       frontier_crossover=None) -> jax.Array:
    """Per-node min-plus relaxation: ``out[v] = min(dist[u] + w(u, v))``
    over live incoming edges — one Bellman-Ford round over the whole
    population, the tropical-semiring sibling of :func:`propagate_max`.

    Weights come from ``graph.edge_weight`` (``from_edges(weights=...)``
    / ``Graph.with_weights``); an unweighted graph costs 1 per hop, so
    the fixpoint is BFS hop distance. Nodes with no live in-edge — and
    dead nodes — get ``+inf``; callers fold with their own value
    (``jnp.minimum``), which makes that neutral. ``dist`` is ``f32``.
    Methods as in propagate_max: ``"segment"`` / ``"gather"`` (gather
    needs the aligned ``neighbor_weight`` view on weighted graphs; auto
    falls back to segment when it is absent).
    """
    if graph.dyn_senders is not None:
        static = dataclasses.replace(graph, dyn_senders=None,
                                     dyn_receivers=None, dyn_mask=None)
        return jnp.minimum(
            propagate_min_plus(static, dist, method,
                               frontier_crossover=frontier_crossover),
            _dynamic_min_plus(graph, dist))
    if method == "frontier":
        from p2pnetwork_tpu.ops import frontier as FR

        return FR.propagate_min_plus_frontier(
            graph, dist, lambda d: propagate_min_plus(graph, d, "auto"),
            crossover=frontier_crossover)
    weighted = graph.edge_weight is not None
    if method == "auto":
        method = _auto_method(graph)
        if method == "gather" and weighted and graph.neighbor_weight is None:
            method = "segment"
        if method == "skew" and weighted and graph.skew.weight is None:
            method = "segment"
    if method == "gather":
        _require_complete_table(graph)
        if weighted and graph.neighbor_weight is None:
            raise ValueError(
                "method='gather' on a weighted graph needs the aligned "
                "neighbor_weight view — build with from_edges(weights=...) "
                "or Graph.with_weights, or use method='segment'"
            )
        w = graph.neighbor_weight if weighted else 1.0
        vals = jnp.where(graph.neighbor_mask, dist[graph.neighbors] + w,
                         jnp.inf)
        agg = jnp.min(vals, axis=1)
    elif method == "skew":
        from p2pnetwork_tpu.ops import skew as SK

        _require_skew(graph)
        if weighted and graph.skew.weight is None:
            raise ValueError(
                "method='skew' on a weighted graph needs the aligned "
                "weight view — build via from_edges(weights=..., "
                "skew_table=True) or Graph.with_weights, or use "
                "method='segment'"
            )
        agg = SK.min_plus_skew(graph.skew, dist, graph.n_nodes_padded)
    elif method == "segment":
        w = graph.edge_weight if weighted else 1.0
        contrib = jnp.where(graph.edge_mask, dist[graph.senders] + w,
                            jnp.inf)
        agg = jax.ops.segment_min(
            contrib,
            graph.receivers,
            num_segments=graph.n_nodes_padded,
            indices_are_sorted=True,
        )
    else:
        raise ValueError(
            f"propagate_min_plus supports method 'segment', 'gather', "
            f"'skew' or 'frontier', got {method!r} (min does not ride the "
            f"one-hot-matmul lowerings)"
        )
    return jnp.where(graph.node_mask, agg, jnp.inf)


def frontier_messages(graph: Graph, frontier: jax.Array) -> jax.Array:
    """Number of point-to-point sends this round: every node holding the
    frontier flag sends to each of its outgoing edges — the batched
    equivalent of the reference's per-edge ``send_to_nodes`` loop and its
    ``message_count_send`` counter [ref: node.py:110-116]."""
    return jnp.sum(jnp.where(frontier, graph.out_degree, 0))

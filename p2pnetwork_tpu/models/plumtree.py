"""Plumtree — epidemic broadcast trees (Leitão, Pereira, Rodrigues 2007).

THE self-optimizing broadcast of the gossip literature (the ancestor of
libp2p's gossipsub): flood the first message over every link, and let
the duplicates teach the overlay a spanning tree — each node keeps only
its FIRST deliverer as an *eager* link and demotes the rest to *lazy*
(PRUNE); lazy links carry only message-id digests (IHAVE), and a node
that misses a message GRAFTs a lazy link back into the tree. Broadcast
cost drops from O(E) messages to O(N−1) while the lazy mesh keeps the
reliability of the full flood. Reference users would build exactly this
on ``node_message`` to stop duplicate storms [ref: README.md:20 — the
library ships broadcast but no dedup at all, node.py:106-112].

Batched, round-synchronous form — one :meth:`step` is ONE broadcast
from ``source`` over the current eager set, run to completion
device-side:

- a BFS ``while_loop`` over the eager-masked edge set delivers the
  message and records arrival layers;
- PRUNE: each reached node keeps one eager in-edge from the previous
  layer (lowest edge id — the deterministic stand-in for "first
  arrival", which a synchronous round cannot distinguish); every other
  in-edge goes lazy. After one broadcast on a static overlay the eager
  set IS a spanning tree rooted at the source.
- GRAFT: when the eager wave dies with live nodes unreached (the tree
  was broken — e.g. by churn since the last broadcast), the repair that
  Plumtree drives off IHAVE timeouts fires inside the same loop: every
  unreached node with a reached lazy in-neighbor grafts its lowest-id
  such edge back to eager, and the wave continues. ``grafts`` counts
  the healed links.

Stats per broadcast: ``messages`` (eager payload sends), ``ihave``
(lazy digest sends — the price of the repair channel), ``duplicates``
(eager deliveries beyond the first — 0 once the tree has formed),
``eager_edges``, ``grafts``, ``coverage``. The headline contrast:
broadcast 1 costs ~E messages with ~E−N duplicates, broadcast 2 costs
N−1 with 0, and after ``fail_nodes`` the next broadcast pays a few
grafts to heal (see ``tests/test_plumtree.py`` for all three pinned).

Directed-edge note: the eager set lives on the stored directed edges;
on the symmetric graphs the builders produce the pruned tree is a
directed arborescence away from the source, matching Plumtree's
per-direction eager flags.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from p2pnetwork_tpu.models import base
from p2pnetwork_tpu.ops import bitset
from p2pnetwork_tpu.sim.graph import Graph


def _eager_mask(graph: Graph, eager: jax.Array) -> jax.Array:
    """Live eager edges, computed device-side (tree_graph's compaction
    must not pull the E-slot arrays to host just to mask them)."""
    s, r = graph.senders, graph.receivers
    return graph.edge_mask & eager & graph.node_mask[s] & graph.node_mask[r]


def _compact_edges(graph: Graph, idx: jax.Array) -> jax.Array:
    """``[2, K]`` (senders, receivers) at ``idx`` — one stacked gather,
    one device->host transfer for the caller."""
    return jnp.stack([jnp.take(graph.senders, idx),
                      jnp.take(graph.receivers, idx)])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlumtreeState:
    eager: jax.Array  # bool[E_pad] — payload-carrying links
    round: jax.Array  # i32[] — broadcasts completed


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlumtreeBitState:
    """PlumtreeState with the per-EDGE eager flags bit-packed
    (ops/bitset.py): the carried eager set shrinks 32x — at 1M nodes /
    ~10M directed edges that is ~10 MB -> ~0.3 MB of per-broadcast carry.
    The broadcast loop unpacks transiently; results are bit-identical."""

    eager: jax.Array  # u32[E_pad // 32]
    round: jax.Array  # i32[]


@dataclasses.dataclass(frozen=True, unsafe_hash=True)
class Plumtree:
    """Self-optimizing broadcast: flood once, then tree + lazy repair.

    ``bitset=True`` carries the eager edge set bit-packed
    (:class:`PlumtreeBitState`) — same pruned trees, same stats."""

    source: int = 0
    bitset: bool = False

    def init(self, graph: Graph, key: jax.Array):
        base.validate_source(graph, self.source)
        if graph.dyn_senders is not None:
            # The eager flags live on the STATIC edge slots; a runtime
            # link would be silently invisible to broadcasts (flood folds
            # the dynamic region in, so a flood->Plumtree switch would
            # quietly lose coverage). Refuse rather than mislead —
            # consolidate (sim/topology.py) to fold runtime links into
            # static slots first.
            raise ValueError(
                "Plumtree does not track the dynamic edge region; "
                "consolidate the graph first")
        eager = jnp.ones(graph.n_edges_padded, dtype=bool)
        if self.bitset:
            return PlumtreeBitState(eager=bitset.pack_bits(eager),
                                    round=jnp.int32(0))
        return PlumtreeState(eager=eager, round=jnp.int32(0))

    @staticmethod
    def _eager_bool(graph: Graph, state) -> jax.Array:
        """The eager set as bool[E_pad], whichever state carries it."""
        if isinstance(state, PlumtreeBitState):
            return bitset.unpack_bits(state.eager, graph.n_edges_padded)
        return state.eager

    def tree_graph(self, graph: Graph, state: PlumtreeState,
                   **from_edges_kwargs) -> Graph:
        """Extract the learned eager set as its own compact :class:`Graph`.

        The per-layer cost of :meth:`step` is O(E_pad) however sparse the
        eager set is (a dynamic per-edge mask fits none of the static
        fast layouts); once the tree is stable, the cheap repeated
        broadcast is Flood over THIS graph — same ~N−1 edges, but padded
        to ~N slots instead of E (measured 3.8 s → 0.13 s per 1M-node
        broadcast; see BENCH.md).

        The eager-edge COMPACTION runs device-side (mask, count, one
        ``nonzero``), so only the ~N surviving tree edges ever cross
        device->host — not the full E-slot edge arrays (~120 MB at 1M
        nodes vs ~8 MB compacted). The host then only sorts/pads ~N
        edges (``from_edges`` rides the native radix path,
        native/graphcore.cpp). Pass ``source_csr=True`` etc. through
        ``from_edges_kwargs`` to pick layouts."""
        import numpy as np

        from p2pnetwork_tpu.sim.graph import from_edges

        if graph.dyn_senders is not None:
            # Same refuse-rather-than-mislead rule as init: runtime
            # links would silently vanish from the extracted tree.
            raise ValueError(
                "Plumtree does not track the dynamic edge region; "
                "consolidate the graph first")
        em = _eager_mask(graph, self._eager_bool(graph, state))
        count = int(jnp.sum(em))
        idx = jnp.nonzero(em, size=max(count, 1), fill_value=0)[0]
        picked = np.asarray(_compact_edges(graph, idx))[:, :count]
        s, r = picked[0], picked[1]
        if graph.edge_weight is not None:
            # Carry link costs through the extraction (the same rule as
            # topology.consolidate): a weighted overlay's tree must not
            # silently decay to unit costs for weighted protocols.
            from_edges_kwargs.setdefault(
                "weights",
                np.asarray(jnp.take(graph.edge_weight, idx))[:count])
        # Pad to the source graph's node extent: ids and masks then line
        # up slot-for-slot whatever pad multiple the source was built
        # with (n_nodes <= n_nodes_padded makes the round-up exact).
        from_edges_kwargs.setdefault("node_pad_multiple",
                                     graph.n_nodes_padded)
        m = from_edges_kwargs["node_pad_multiple"]
        if -(-graph.n_nodes // m) * m != graph.n_nodes_padded:
            # A caller-supplied multiple that disagrees would only
            # surface as a cryptic shape error after the full build.
            raise ValueError(
                f"node_pad_multiple={m} pads to a different node extent "
                f"than the source graph's {graph.n_nodes_padded}")
        g = from_edges(s, r, graph.n_nodes, **from_edges_kwargs)
        return dataclasses.replace(g,
                                   node_mask=graph.node_mask & g.node_mask)

    def step(self, graph: Graph, state, key: jax.Array):
        eager0 = self._eager_bool(graph, state)
        n_pad = graph.n_nodes_padded
        e_pad = graph.n_edges_padded
        s, r = graph.senders, graph.receivers
        eids = jnp.arange(e_pad, dtype=jnp.int32)
        big = jnp.int32(2**31 - 1)
        live_edge = graph.edge_mask & graph.node_mask[s] & graph.node_mask[r]

        seed = jnp.zeros(n_pad, dtype=bool).at[self.source].set(True)
        seed = seed & graph.node_mask
        dist0 = jnp.where(seed, 0, -1).astype(jnp.int32)

        def seg_or(signal, emask):
            contrib = signal[s] & emask
            return jax.ops.segment_max(
                contrib.astype(jnp.int32), r, num_segments=n_pad,
                indices_are_sorted=True) > 0

        # One device-side loop runs the whole broadcast: BFS rounds over
        # the eager set; when the wave dies with live nodes unreached,
        # graft one batch of lazy links (IHAVE repair) and keep going.
        def cond(carry):
            dist, frontier, eager, layer, grafts, stop = carry
            return ~stop

        def body(carry):
            dist, frontier, eager, layer, grafts, stop = carry
            emask = live_edge & eager
            delivered = seg_or(frontier, emask)
            new = delivered & (dist < 0) & graph.node_mask
            any_new = jnp.any(new)

            # Wave died: graft lowest-id lazy edges from reached senders
            # into unreached receivers (the IHAVE->GRAFT repair). Behind
            # a lax.cond so the O(E) scatter-min is paid ONLY on dead
            # layers — on a healthy tree each broadcast hits it once, at
            # the final (empty) wave, not per layer (measured 10.9 s ->
            # ~flood-cost per 1M-node tree broadcast without the gate).
            def _graft(args):
                dist, eager = args
                unreached = graph.node_mask & (dist < 0)
                lazy_cand = (live_edge & ~eager & (dist[s] >= 0)
                             & unreached[r])
                tgt = jnp.where(lazy_cand, r, n_pad)
                best = jnp.full(n_pad, big).at[tgt].min(
                    jnp.where(lazy_cand, eids, big), mode="drop")
                graft_edge = lazy_cand & (best[jnp.where(lazy_cand, r, 0)]
                                          == eids)
                regrow = jnp.zeros(n_pad, dtype=bool).at[
                    jnp.where(graft_edge, s, n_pad)].set(True, mode="drop")
                return graft_edge, jnp.sum(graft_edge), regrow

            def _no_graft(args):
                return (jnp.zeros(e_pad, dtype=bool), jnp.int32(0),
                        jnp.zeros(n_pad, dtype=bool))

            graft_edge, n_graft, regrow = jax.lax.cond(
                any_new, _no_graft, _graft, (dist, eager))
            do_graft = ~any_new & (n_graft > 0)
            eager = jnp.where(do_graft, eager | graft_edge, eager)
            # Grafted edges deliver immediately next iteration: their
            # senders rejoin the frontier.
            frontier_next = jnp.where(do_graft, (dist >= 0) & regrow, new)

            dist = jnp.where(new, layer + 1, dist)
            stop = ~any_new & ~do_graft
            return (dist, frontier_next, eager,
                    jnp.where(any_new, layer + 1, layer),
                    grafts + jnp.where(do_graft, n_graft, 0), stop)

        dist, _, eager, _, grafts, _ = jax.lax.while_loop(
            cond, body, (dist0, seed, eager0, jnp.int32(0),
                         jnp.int32(0), jnp.array(False)))

        reached = dist >= 0
        emask = live_edge & eager
        # Every eager edge with a reached sender delivers the payload
        # (the sender fires once when the message reaches it); a reached
        # node's deliveries beyond the first are Plumtree's duplicates.
        fired = emask & reached[s]
        arrivals = jax.ops.segment_sum(
            fired.astype(jnp.int32), r, num_segments=n_pad,
            indices_are_sorted=True)
        duplicates = jnp.sum(jnp.maximum(arrivals - 1, 0)
                             * reached.astype(jnp.int32))
        messages = jnp.sum(fired)
        ihave = jnp.sum(live_edge & ~eager & reached[s])

        # PRUNE: each reached non-source node keeps its lowest-id in-edge
        # from any STRICTLY EARLIER layer (strictness keeps the parent
        # pointers acyclic; "previous layer only" would orphan nodes
        # delivered through a graft, whose sender can sit many layers
        # up). Everything else incident-in to reached nodes goes lazy;
        # edges into unreached nodes keep their flag.
        parent_cand = emask & (dist[s] >= 0) & (dist[r] >= 1) \
            & (dist[s] < dist[r])
        tgt = jnp.where(parent_cand, r, n_pad)
        best = jnp.full(n_pad, big).at[tgt].min(
            jnp.where(parent_cand, eids, big), mode="drop")
        is_parent = parent_cand & (best[jnp.where(parent_cand, r, 0)]
                                   == eids)
        into_reached = live_edge & reached[r]
        eager = jnp.where(into_reached, is_parent, eager)

        n_live = jnp.maximum(jnp.sum(graph.node_mask), 1)
        if isinstance(state, PlumtreeBitState):
            new_state = PlumtreeBitState(eager=bitset.pack_bits(eager),
                                         round=state.round + 1)
        else:
            new_state = PlumtreeState(eager=eager, round=state.round + 1)
        stats = {
            "messages": messages,
            "ihave": ihave,
            "duplicates": duplicates,
            "grafts": grafts,
            "eager_edges": jnp.sum(live_edge & eager),
            "coverage": jnp.sum(reached & graph.node_mask) / n_live,
        }
        return new_state, stats

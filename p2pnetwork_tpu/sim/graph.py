"""Peer-graph representation for the simulation backend.

The reference keeps the peer topology as Python lists of live socket threads
(`nodes_inbound`/`nodes_outbound` [ref: p2pnetwork/node.py:46-49]) and
"broadcast" is a sequential Python loop over them [ref: node.py:110-112].
Here the whole population's topology is device-resident arrays with static
shapes, so one propagation round is one batched XLA computation (SURVEY.md
section 7 step 2):

- **COO edges sorted by receiver** (``senders``/``receivers``/``edge_mask``),
  the general representation, feeding segment reductions;
- an optional **padded neighbor table** (``neighbors``/``neighbor_mask``,
  shape ``[N, max_degree]``), the gather-friendly representation that maps
  well onto TPU vector loads for quasi-regular graphs (WS/ER).

Static shapes everywhere: node count and edge count are padded (capacity
padding + active masks), which is how dynamic topology (connect/disconnect,
SURVEY.md section 7 "hard parts" 4) fits XLA's compile-once model — adding or
dropping a peer flips mask bits, it does not recompile.

Generators (Erdős–Rényi, Barabási–Albert, Watts–Strogatz, ring, complete)
run host-side in numpy: graph construction is one-off setup, the hot path is
propagation.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from p2pnetwork_tpu import native, telemetry


def _round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


# ------------------------------------------------------ build-phase timing
#
# Host-side graph construction is the scale bottleneck (BENCH_r02:
# graph_build_s 40x the 1M headline), so where the build time goes —
# dedup, radix sort, neighbor tables, CSR, kernel layouts, reordering —
# is first-class telemetry: per-phase wall seconds accumulate into the
# registry (`sim_graph_build_seconds_total{phase}`) and the most recent
# build's breakdown is readable via :func:`last_build_phases` (bench.py
# publishes it as ``build_phases`` in BENCH_TELEMETRY.json).

_phases_tls = threading.local()


def _phases_dict() -> dict:
    d = getattr(_phases_tls, "d", None)
    if d is None:
        d = _phases_tls.d = {}
    return d


def _reset_phases() -> None:
    """Start a fresh per-build phase record, folding in any dedup time a
    generator accumulated just before calling :func:`from_edges` (the
    generators dedup BEFORE building, so the pending value belongs to the
    build that follows)."""
    d = _phases_dict()
    d.clear()
    pending = getattr(_phases_tls, "pending_dedup", 0.0)
    if pending:
        d["dedup_s"] = round(pending, 6)
        _phases_tls.pending_dedup = 0.0


def _note_dedup(seconds: float) -> None:
    """Accumulate generator-side dedup/sample time for the NEXT build."""
    _phases_tls.pending_dedup = getattr(
        _phases_tls, "pending_dedup", 0.0) + seconds
    telemetry.default_registry().counter(
        "sim_graph_build_seconds_total",
        "Host-side graph construction wall seconds by build phase.",
        ("phase",)).labels("dedup").inc(seconds)


def last_build_phases() -> dict:
    """Per-phase wall-second breakdown of the most recent graph build
    (``from_edges`` or ``apply_delta``) on this thread."""
    return dict(_phases_dict())


def _note_phase(name: str, seconds: float) -> None:
    d = _phases_dict()
    d[name + "_s"] = round(d.get(name + "_s", 0.0) + seconds, 6)
    telemetry.default_registry().counter(
        "sim_graph_build_seconds_total",
        "Host-side graph construction wall seconds by build phase.",
        ("phase",)).labels(name).inc(seconds)


class _phase:
    """Context manager: time one build phase into the thread-local record
    and the ``sim_graph_build_seconds_total{phase}`` counter."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _note_phase(self.name, time.perf_counter() - self._t0)
        return False


def _padded_row_fill(starts: np.ndarray, counts: np.ndarray, width: int):
    """Vectorized ragged-rows-to-padded-matrix fill.

    Row ``i`` owns ``counts[i]`` consecutive items beginning at ``starts[i]``
    in some flat pool array. Returns ``(take, valid)`` of shape
    ``[rows, width]``: flat pool indices (0 where padded) and the padding
    mask. Shared by the neighbor-table and blocked-edge builders — one fancy
    index instead of a per-row Python loop.
    """
    # int32 halves the temporaries for the (rows x width) tables, but only
    # when every index fits — beyond 2^31 edge slots int32 would wrap to
    # negative fancy indices and silently build a wrong table.
    big = starts.size and int(starts.max()) + width >= 2**31
    dtype = np.int64 if big else np.int32
    slot = np.arange(width, dtype=dtype)
    starts = starts.astype(dtype, copy=False)
    counts = counts.astype(dtype, copy=False)
    valid = slot[None, :] < counts[:, None]
    take = np.where(valid, starts[:, None] + slot[None, :], dtype(0))
    return take, valid


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """A static-shape peer graph on device.

    An edge ``(senders[e], receivers[e])`` means messages flow sender ->
    receiver (undirected topologies store both directions). Edges are sorted
    by receiver so segment reductions can assume sorted segment ids.
    """

    senders: jax.Array  # i32[E_pad]
    receivers: jax.Array  # i32[E_pad], non-decreasing
    edge_mask: jax.Array  # bool[E_pad]
    node_mask: jax.Array  # bool[N_pad]
    in_degree: jax.Array  # i32[N_pad]  (active incoming edges per node)
    out_degree: jax.Array  # i32[N_pad] (active outgoing edges per node)
    # Gather representation: incoming neighbor list per node, or None.
    neighbors: Optional[jax.Array]  # i32[N_pad, max_degree]
    neighbor_mask: Optional[jax.Array]  # bool[N_pad, max_degree]
    # Static metadata.
    n_nodes: int = dataclasses.field(metadata=dict(static=True))
    n_edges: int = dataclasses.field(metadata=dict(static=True))
    # Whether the neighbor table holds EVERY incoming edge. False when
    # from_edges(max_degree=...) capped the width — then each over-degree
    # node's row is a uniform random subset of its in-edges (fine for
    # Gossip's random partner draw, wrong for exact OR/sum aggregation,
    # which must not silently drop edges).
    neighbors_complete: bool = dataclasses.field(
        default=True, metadata=dict(static=True)
    )
    # The from_edges(max_degree=...) cap as given, or None. Distinct from
    # the table width: a cap WIDER than the build-time max in-degree
    # leaves the table complete at the narrower width, but must still
    # bound it when churn (apply_delta) or consolidation later grows a
    # hub past it. None on graphs from old checkpoints (pre-cap format).
    max_degree_cap: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )
    # The from_edges(edge_pad_multiple=) value, recorded so apply_delta
    # re-pads to the SAME multiple — a base built with a coarse multiple
    # to hold shapes stable across churn must not snap back to 128 (and
    # recompile every jitted consumer) on the first delta.
    edge_pad_multiple: int = dataclasses.field(
        default=128, metadata=dict(static=True)
    )
    # Widest contiguous run of one receiver id among the LIVE (unpadded)
    # COO entries — i.e. the max static in-degree at build. The padding
    # tail (receiver n_pad-1) can extend that id's physical run far wider;
    # consumers must mask with edge_mask, as the membership probe does.
    # Static so runtime probes (sim/topology.py connect) can scan a
    # [B, max_in_span] window instead of comparing against all E edges.
    max_in_span: int = dataclasses.field(default=0, metadata=dict(static=True))
    # Optional blocked-edge representation (ops/blocked.py) feeding the
    # matmul/Pallas aggregation paths; attach via with_blocked().
    blocked: Optional[object] = None
    # Optional diagonal+remainder representation (ops/diag.py) feeding the
    # gather-free "hybrid" aggregation path; attach via with_hybrid().
    hybrid: Optional[object] = None
    # Optional two-level (virtual-row) neighbor table (ops/skew.py) feeding
    # the hub-proof "skew" aggregation path for degree-skewed families;
    # attach via with_skew_table() or from_edges(skew_table=True).
    skew: Optional[object] = None
    # Dynamic edge region (sim/topology.py): unsorted COO slots for links
    # added at runtime; folded into every aggregation method.
    dyn_senders: Optional[jax.Array] = None  # i32[K]
    dyn_receivers: Optional[jax.Array] = None  # i32[K]
    dyn_mask: Optional[jax.Array] = None  # bool[K]
    # Source-CSR (out-edge) view for frontier-sparse traversal
    # (models/adaptive_flood.py): edge ids permuted sender-sorted —
    # ``src_eid[src_offsets[v] : src_offsets[v+1]]`` are node ``v``'s
    # out-edges as indices into senders/receivers/edge_mask. Row extents
    # are BUILD-time; runtime edge liveness is re-checked through
    # ``edge_mask[src_eid[...]]``, so failures need no rebuild. Attach via
    # ``from_edges(source_csr=True)`` or :meth:`with_source_csr`.
    src_eid: Optional[jax.Array] = None  # i32[E_pad]
    src_offsets: Optional[jax.Array] = None  # i32[N_pad + 1]
    #: Widest build-time out-edge row (static slot width for the sparse
    #: frontier gather), 0 when no CSR is attached.
    max_out_span: int = dataclasses.field(default=0, metadata=dict(static=True))
    # Optional per-edge weights (latency / link cost — f32[E_pad], aligned
    # with senders/receivers; padded slots masked like everything else).
    # None means the unweighted graph every propagate treats as cost-1.
    # Attach via ``from_edges(weights=...)`` or :meth:`with_weights`.
    edge_weight: Optional[jax.Array] = None  # f32[E_pad]
    # Gather-layout view of edge_weight ([N_pad, max_degree], aligned with
    # the neighbor table rows); built alongside the table when weights are
    # present so propagate_min_plus's gather lowering has aligned costs.
    neighbor_weight: Optional[jax.Array] = None  # f32[N_pad, max_degree]
    # IO-aware build-time node relabeling (sim/layout.py, from_edges
    # ``reorder=``): ``layout_perm[old] = new`` and ``layout_inv[new] =
    # old`` over the padded id space, or None when the graph keeps caller
    # order. Every runtime id (protocol sources, failures, deltas) speaks
    # the RELABELED space; map per-node results back with
    # ``layout.to_original_order``.
    layout_perm: Optional[jax.Array] = None  # i32[N_pad]
    layout_inv: Optional[jax.Array] = None  # i32[N_pad]
    # Host-side ring layout for an S-shard mesh (parallel/ringlayout.py,
    # ``from_edges(ring_shards=S)``): what parallel/sharded.shard_graph
    # derives from the edges, built once with the graph and kept in the
    # layout cache. Static (host arrays never ride a jitted call); edge
    # changes (apply_delta, grow) drop it, and shard_graph uses it only
    # while exactly the edges it was built from are live.
    ring: Optional[object] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def n_nodes_padded(self) -> int:
        return self.node_mask.shape[0]

    @property
    def n_edges_padded(self) -> int:
        return self.senders.shape[0]

    @property
    def max_degree(self) -> int:
        return 0 if self.neighbors is None else self.neighbors.shape[1]

    def with_blocked(self, block: int = 128) -> "Graph":
        """Return a copy carrying the blocked-edge representation used by the
        ``"blocked"`` (XLA einsum) and ``"pallas"`` aggregation methods."""
        from p2pnetwork_tpu.ops.blocked import build_blocked

        return dataclasses.replace(self, blocked=build_blocked(self, block))

    def with_source_csr(self) -> "Graph":
        """Return a copy carrying the source-CSR out-edge view used by the
        frontier-sparse rounds of models/adaptive_flood.py. Pulls the edge
        arrays to host; prefer ``from_edges(source_csr=True)`` at
        construction for large graphs."""
        senders = np.asarray(self.senders)
        emask = np.asarray(self.edge_mask)
        eid, offsets, span = _build_source_csr(
            senders, emask, self.n_nodes_padded, self.n_edges_padded
        )
        return dataclasses.replace(
            self, src_eid=jnp.asarray(eid), src_offsets=jnp.asarray(offsets),
            max_out_span=span,
        )

    def gather_row_slots(self, start, end, width: int):
        """``[K, width]`` out-edge slot gather through the source-CSR view:
        ``(eid, valid)`` for slots ``start[i] + j`` while ``< end[i]``.

        This is THE place the ``e_pad - 1`` padding sentinel of
        ``_build_source_csr`` is masked — that slot can name a LIVE edge
        (whenever the edge count is an exact pad multiple), so every
        consumer of the gathered ``eid`` must AND with the returned
        ``valid`` (and its own liveness masks) before trusting it. Used
        by the frontier-sparse wave rounds (models/adaptive_flood.py)
        and the walker cohort (models/walk.py)."""
        slot = start[:, None] + jnp.arange(width)[None, :]
        valid = slot < end[:, None]
        eid = self.src_eid[jnp.where(valid, slot, self.n_edges_padded - 1)]
        return eid, valid

    def with_weights(self, weights) -> "Graph":
        """Return a copy carrying per-edge costs.

        ``weights`` is either a callable ``(senders, receivers) -> f32``
        evaluated on the padded edge arrays (deterministic link-cost
        models, e.g. id-hash latency), or an array aligned with the
        receiver-sorted padded edge slots. When a complete neighbor table
        exists its aligned weight view is rebuilt host-side (the same
        one-off cost as ``with_hybrid``); a width-capped table cannot be
        re-aligned post hoc — pass ``weights=`` to ``from_edges`` instead.
        """
        if callable(weights):
            w = jnp.asarray(weights(self.senders, self.receivers),
                            dtype=jnp.float32)
        else:
            w = jnp.asarray(weights, dtype=jnp.float32)
        if w.shape != self.senders.shape:
            raise ValueError("weights must align with the padded edge slots")
        nw = None
        if self.neighbors is not None:
            if not self.neighbors_complete:
                raise ValueError(
                    "cannot re-align weights to a width-capped neighbor "
                    "table; rebuild via from_edges(weights=..., "
                    "max_degree=...)"
                )
            # Complete-table rows are the contiguous receiver runs of the
            # BUILD-time (unpadded) edge list, in order — recompute the
            # slot -> edge map the builder used. Build-time extents, not
            # in_degree: liveness re-masking since build changes degrees
            # but not slot layout (failures re-mask neighbor_mask, which
            # still guards every consumer of these values).
            rh = np.asarray(self.receivers)[: self.n_edges]
            ids = np.arange(self.n_nodes_padded)
            starts = np.searchsorted(rh, ids)
            counts = np.searchsorted(rh, ids, side="right") - starts
            width = self.neighbors.shape[1]
            take, valid = _padded_row_fill(
                starts, np.minimum(counts, width), width)
            wh = np.asarray(w)
            nw = jnp.asarray(np.where(
                valid, wh[np.minimum(take, max(self.n_edges - 1, 0))], 0.0
            ).astype(np.float32))
        sk = self.skew
        if sk is not None:
            # The virtual rows keep their COO slot map (SkewTable
            # .edge_slots), so the aligned weight view is one gather.
            sk = dataclasses.replace(
                sk,
                weight=jnp.where(
                    sk.mask, w[sk.edge_slots(self.n_edges_padded)], 0.0))
        return dataclasses.replace(self, edge_weight=w, neighbor_weight=nw,
                                   skew=sk)

    def with_skew_table(self, width: int = 0) -> "Graph":
        """Return a copy carrying the two-level (virtual-row) neighbor
        table used by the ``"skew"`` aggregation method — fixed-width row
        slices a hub cannot widen, combined by a per-row sorted segment
        reduction (ops/skew.py). ``width=0`` picks the width from the
        degree histogram. Pulls edge arrays to host; prefer
        ``from_edges(skew_table=True)`` at construction for large
        graphs."""
        from p2pnetwork_tpu.ops.skew import build_skew

        return dataclasses.replace(self, skew=build_skew(self, width))

    def apply_delta(self, delta: "GraphDelta", *,
                    edge_pad_multiple: Optional[int] = None,
                    donate: bool = False) -> "Graph":
        """Apply an add/remove edge batch incrementally — see
        :func:`apply_delta` (O(delta + touched rows) host work instead of
        a from-scratch rebuild, bit-identical results; ``donate=True``
        updates the neighbor table in place, invalidating this graph's
        copy)."""
        return apply_delta(self, delta, edge_pad_multiple=edge_pad_multiple,
                           donate=donate)

    def grow(self, n_new_nodes: int, *,
             node_capacity: Optional[int] = None) -> "Graph":
        """Grow the overlay by ``n_new_nodes`` fresh node ids — see
        :func:`grow` (amortized geometric capacity repad; bit-identical
        to a from-scratch :func:`from_edges` at the grown capacity)."""
        return grow(self, n_new_nodes, node_capacity=node_capacity)

    def with_hybrid(self, block: int = 512, max_diags: int = 64) -> "Graph":
        """Return a copy carrying the diagonal+remainder representation used
        by the ``"hybrid"`` aggregation method — circular-shift passes for
        the graph's dominant diagonals (gather-free), the Pallas kernel for
        the unstructured rest (ops/diag.py)."""
        from p2pnetwork_tpu.ops.diag import build_hybrid

        return dataclasses.replace(
            self, hybrid=build_hybrid(self, block, max_diags)
        )


def _build_source_csr(senders: np.ndarray, edge_mask: np.ndarray,
                      n_pad: int, e_pad: int):
    """Sender-sorted edge-id permutation + row offsets (host-side).

    Only edges active in ``edge_mask`` enter rows. Padding slots of
    ``src_eid`` hold ``e_pad - 1`` merely to stay in bounds — that edge CAN
    be live (whenever the edge count is an exact pad multiple), so
    consumers must mask out-of-row slots themselves before trusting the
    gathered edge (models/adaptive_flood.py's ``svalid``)."""
    from p2pnetwork_tpu import native

    active = np.flatnonzero(edge_mask).astype(np.int32)
    # Radix sort (native/graphcore.cpp, numpy fallback) — the same sorter
    # the receiver sort uses; np.argsort doubles the host cost at 100M
    # edges.
    _, sorted_eids = native.sort_pairs(senders[active], active)
    eid = np.full(e_pad, e_pad - 1, dtype=np.int32)
    eid[: active.size] = sorted_eids
    counts = np.bincount(senders[active], minlength=n_pad).astype(np.int32)
    offsets = np.zeros(n_pad + 1, dtype=np.int32)
    np.cumsum(counts, out=offsets[1:])
    span = int(counts.max()) if active.size else 0
    return eid, offsets, span


# ------------------------------------------------------- incremental builds


def _as_edge_array(x, dtype=np.int32) -> np.ndarray:
    return (np.zeros(0, dtype=dtype) if x is None
            else np.asarray(x, dtype=dtype).reshape(-1))


class EdgeEndpointError(ValueError):
    """A delta edge names a node id outside ``[0, n_nodes)``.

    Raised at :func:`apply_delta` / :func:`grow` entry, BEFORE any array
    is touched — an out-of-range id would otherwise surface as an index
    error or a silent scatter into capacity padding depending on which
    derived view met it first. ``pairs`` carries up to 16 offending
    ``(sender, receiver)`` tuples and ``n_nodes`` the valid id bound.
    Subclasses :class:`ValueError` (and keeps the historical
    "edge endpoint out of range" message prefix) so existing handlers
    keep working.
    """

    def __init__(self, pairs, n_nodes: int):
        self.pairs = [(int(s), int(r)) for s, r in pairs]
        self.n_nodes = int(n_nodes)
        shown = ", ".join(f"({s}, {r})" for s, r in self.pairs[:5])
        more = ("" if len(self.pairs) <= 5
                else f", +{len(self.pairs) - 5} more")
        super().__init__(
            f"edge endpoint out of range: edge(s) name a node id outside "
            f"[0, {self.n_nodes}) as (sender, receiver): {shown}{more}")


def _check_endpoints(senders: np.ndarray, receivers: np.ndarray,
                     n_nodes: int) -> None:
    """Raise :class:`EdgeEndpointError` for any edge naming an id outside
    ``[0, n_nodes)``."""
    if not senders.size:
        return
    bad = ((senders < 0) | (senders >= n_nodes)
           | (receivers < 0) | (receivers >= n_nodes))
    if bad.any():
        idx = np.flatnonzero(bad)[:16]
        raise EdgeEndpointError(
            list(zip(senders[idx], receivers[idx])), n_nodes)


@dataclasses.dataclass(frozen=True)
class GraphDelta:
    """A host-side add/remove edge batch for :func:`apply_delta`.

    Directed edges, like :func:`from_edges` — for the usual undirected
    overlay semantics build via :meth:`undirected`, which stores both
    directions of every pair. ``add_weights`` is required exactly when the
    target graph carries ``edge_weight``. Removals name (sender, receiver)
    pairs; every named pair must match at least one live edge (removing an
    absent edge is an error, not a no-op), and removal drops ALL live
    copies of the pair.
    """

    add_senders: Optional[np.ndarray] = None  # i32[A]
    add_receivers: Optional[np.ndarray] = None  # i32[A]
    add_weights: Optional[np.ndarray] = None  # f32[A]
    remove_senders: Optional[np.ndarray] = None  # i32[R]
    remove_receivers: Optional[np.ndarray] = None  # i32[R]

    def __post_init__(self):
        set_ = object.__setattr__  # frozen dataclass
        set_(self, "add_senders", _as_edge_array(self.add_senders))
        set_(self, "add_receivers",
             _as_edge_array(self.add_receivers))
        set_(self, "remove_senders",
             _as_edge_array(self.remove_senders))
        set_(self, "remove_receivers",
             _as_edge_array(self.remove_receivers))
        if self.add_weights is not None:
            set_(self, "add_weights",
                 np.asarray(self.add_weights, dtype=np.float32).reshape(-1))
            if self.add_weights.shape != self.add_senders.shape:
                raise ValueError("add_weights must align with add_senders")
        if self.add_senders.shape != self.add_receivers.shape:
            raise ValueError("add_senders/add_receivers shape mismatch")
        if self.remove_senders.shape != self.remove_receivers.shape:
            raise ValueError(
                "remove_senders/remove_receivers shape mismatch")

    @classmethod
    def undirected(cls, add_senders=None, add_receivers=None,
                   add_weights=None, remove_senders=None,
                   remove_receivers=None) -> "GraphDelta":
        """Both directions of every pair — the reference's TCP-connection
        semantic, matching what the generators store."""
        a_s = _as_edge_array(add_senders)
        a_r = _as_edge_array(add_receivers)
        r_s = _as_edge_array(remove_senders)
        r_r = _as_edge_array(remove_receivers)
        a_w = None
        if add_weights is not None:
            w = np.asarray(add_weights, dtype=np.float32).reshape(-1)
            a_w = np.concatenate([w, w])
        return cls(
            add_senders=np.concatenate([a_s, a_r]),
            add_receivers=np.concatenate([a_r, a_s]),
            add_weights=a_w,
            remove_senders=np.concatenate([r_s, r_r]),
            remove_receivers=np.concatenate([r_r, r_s]),
        )

    @property
    def n_adds(self) -> int:
        return int(self.add_senders.size)

    @property
    def n_removes(self) -> int:
        return int(self.remove_senders.size)


def _pow2_pad(n: int) -> int:
    """Next power of two — the shape bucket for the donated scatters, so a
    churn storm whose batch sizes vary only compiles log2(N) variants
    instead of one per distinct delta size (the retrace hazard
    analysis/retrace_guard exists to catch)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _pad_repeat_last(arr: np.ndarray, n: int) -> np.ndarray:
    """Pad axis 0 to ``n`` by repeating the last entry — safe filler for
    scatters whose duplicate indices carry identical values."""
    if arr.shape[0] == n:
        return arr
    reps = np.repeat(arr[-1:], n - arr.shape[0], axis=0)
    return np.concatenate([arr, reps])


@functools.partial(jax.jit, donate_argnames=("arr",))
def _scatter_add_donating(arr, idx, deltas):
    """Donated scatter-add: the delta path's in-place degree update —
    O(delta) writes into the existing buffer instead of an O(N) copy.
    The donor graph's degree buffer is invalidated."""
    return arr.at[idx].add(deltas)


@functools.partial(jax.jit, donate_argnames=("table",))
def _scatter_rows_donating(table, rows, vals):
    """Donated row scatter: the delta path's in-place neighbor-table
    update. Donation lets XLA write the touched rows into the EXISTING
    buffer — O(touched) instead of an O(N x width) copy, which is the
    difference between a delta apply and a rebuild at 10M-node table
    sizes. The donor graph's table buffer is invalidated (engine-style
    donation contract)."""
    return table.at[rows].set(vals)


def _delta_neighbor_tables(graph: Graph, out_r, out_s, w_unpadded,
                           static_in, touched, width_old, pristine,
                           donate=False):
    """The neighbor-table piece of :func:`apply_delta`: copy the base
    table, recompute only the touched rows (plus every width-capped row —
    their shared subsample RNG stream is global) — bit-identical to the
    table :func:`from_edges` builds from the merged edge list. With
    ``donate=True`` (and an unchanged width on a pristine base) the
    touched rows scatter into the base table IN PLACE — no copy at
    all."""
    e_new = out_r.size
    n_pad = graph.n_nodes_padded
    true_width = int(static_in.max()) if e_new else 0
    cap = graph.max_degree_cap
    if cap is None and not graph.neighbors_complete:
        # Old-checkpoint graphs predate the recorded cap; an incomplete
        # table's width IS the cap (it bit at build).
        cap = width_old
    if cap is None:
        complete = True
        width = max(true_width, 1)
    else:
        complete = cap >= true_width
        width = max(min(true_width, cap), 1)
    weighted = graph.neighbor_weight is not None
    in_place = donate and pristine and width == width_old
    if not pristine:
        # Liveness-re-masked base (edges dropped by failures): copied rows
        # would keep their holes while a rebuild compacts them — recompute
        # everything.
        touched = np.arange(n_pad, dtype=np.int32)
    if not complete or (not graph.neighbors_complete):
        # Any over-width row's subsample keys come from ONE rng stream over
        # all capped rows, so every capped row recomputes whenever a cap is
        # in play — still O(capped edges), never O(E).
        capped_rows = np.flatnonzero(static_in > width)
        touched = np.union1d(touched, capped_rows)
    # int32 rows keep searchsorted from promoting (and re-copying) the
    # million-element receiver array to int64 per call.
    rows = np.asarray(touched, dtype=np.int32)

    vals = valid = wvals = None
    if rows.size:
        starts = np.searchsorted(out_r, rows)
        ends = np.searchsorted(out_r, rows, side="right")
        deg = ends - starts
        take, valid = _padded_row_fill(starts, np.minimum(deg, width), width)
        capped_local = np.nonzero(deg > width)[0]
        if capped_local.size:
            # The exact from_edges subsample, restricted to the capped rows
            # (which are all present in `rows`, ascending): same rng seed,
            # same draw order, same ranking — bit-identical keep sets.
            cap_rng = np.random.default_rng(0)
            degc = deg[capped_local]
            cap_edge = np.repeat(capped_local, degc)
            offs = np.arange(cap_edge.size) - np.repeat(
                np.cumsum(degc) - degc, degc)
            edge_idx = starts[cap_edge] + offs
            keys = cap_rng.random(edge_idx.size)
            order = np.lexsort((keys, cap_edge))
            rank = np.empty_like(offs)
            rank[order] = offs
            kept = rank < width
            resort = np.lexsort((edge_idx[kept], cap_edge[kept]))
            take[capped_local] = edge_idx[kept][resort].reshape(
                capped_local.size, width)
        pool = out_s if e_new else np.zeros(1, dtype=np.int32)
        take_safe = np.minimum(take, max(e_new - 1, 0))
        vals = np.where(valid, pool[take_safe], 0).astype(np.int32)
        if weighted:
            wpool = w_unpadded if e_new else np.zeros(1, dtype=np.float32)
            wvals = np.where(valid, wpool[take_safe], 0.0).astype(np.float32)

    if in_place:
        # Donated scatter: the touched rows land in the base buffers with
        # no table-sized copy — the donor graph's table is invalidated.
        if rows.size:
            # Bucket the scatter shape (pad by repeating the last row —
            # duplicate indices carry identical values, so the write is
            # idempotent) to bound recompiles across varying batch sizes.
            b = _pow2_pad(rows.size)
            rows_j = jnp.asarray(_pad_repeat_last(rows, b))
            nb = _scatter_rows_donating(graph.neighbors, rows_j,
                                        _pad_repeat_last(vals, b))
            nbm = _scatter_rows_donating(graph.neighbor_mask, rows_j,
                                         _pad_repeat_last(valid, b))
            nw = (_scatter_rows_donating(graph.neighbor_weight, rows_j,
                                         _pad_repeat_last(wvals, b))
                  if weighted else None)
        else:  # nothing touched: the table is exactly the base table
            nb, nbm = graph.neighbors, graph.neighbor_mask
            nw = graph.neighbor_weight
        return nb, nbm, nw, complete

    nw = None
    if width == width_old and pristine:
        nb = np.array(graph.neighbors)  # writable copies
        nbm = np.array(graph.neighbor_mask)
        if weighted:
            nw = np.array(graph.neighbor_weight)
    elif pristine:
        c = min(width, width_old)
        nb = np.zeros((n_pad, width), dtype=np.int32)
        nbm = np.zeros((n_pad, width), dtype=bool)
        nb[:, :c] = np.asarray(graph.neighbors)[:, :c]
        nbm[:, :c] = np.asarray(graph.neighbor_mask)[:, :c]
        if weighted:
            nw = np.zeros((n_pad, width), dtype=np.float32)
            nw[:, :c] = np.asarray(graph.neighbor_weight)[:, :c]
    else:
        nb = np.zeros((n_pad, width), dtype=np.int32)
        nbm = np.zeros((n_pad, width), dtype=bool)
        if weighted:
            nw = np.zeros((n_pad, width), dtype=np.float32)
    if rows.size:
        nb[rows] = vals
        nbm[rows] = valid
        if weighted:
            nw[rows] = wvals
    return nb, nbm, nw, complete


def apply_delta(graph: Graph, delta: GraphDelta, *,
                edge_pad_multiple: Optional[int] = None,
                donate: bool = False) -> Graph:
    """Apply a :class:`GraphDelta` incrementally — bit-identical to a
    from-scratch :func:`from_edges` on the merged edge list, in
    O(delta + touched) host work instead of a full rebuild.

    ``donate=True`` is the churn-storm fast path: when the base is
    pristine (no failure-masked edges) and the table width is unchanged,
    the touched neighbor-table rows scatter into the base graph's
    buffers IN PLACE — O(touched rows) instead of an O(N x width) table
    copy, the difference between a delta and a rebuild at 10M-node
    table sizes. Like the engine's donating run loops, the donor
    graph's table buffers are INVALIDATED (reading them afterwards
    raises); use it in ``g = apply_delta(g, d, donate=True)`` rolling
    form and keep ``donate=False`` (the default) when the pre-delta
    graph must stay usable.

    The base COO is already receiver-sorted, so only the DELTA is
    radix-sorted; linear native merge/anti-merge passes
    (native/graphcore.cpp, numpy fallback under ``force_fallback()``)
    splice it into the base order, degrees and spans update from the
    batch, and only the touched neighbor-table rows recompute. The
    source-CSR view merges the surviving old order with the delta's
    sender-sorted ids — no E-element re-sort anywhere.

    Equivalence contract: the result equals
    ``from_edges(kept + adds, n_nodes, ...)`` with the base's layout
    settings, where ``kept`` is the base's LIVE edges (in sorted order)
    minus the removed pairs. Consequences:

    - edges masked out by failures are dropped for good (the
      ``consolidate`` semantic); node liveness (``node_mask``) is
      preserved as-is;
    - attached blocked/hybrid/skew layouts are REBUILT from the merged
      arrays (full host cost — they bake edge order); the incremental
      win covers COO, degrees, spans, neighbor tables, and the
      source CSR;
    - the dynamic edge region and any layout permutation ride along
      unchanged; delta ids speak the graph's (possibly relabeled) id
      space.
    """
    _reset_phases()
    n_pad = graph.n_nodes_padded
    # Default to the base's recorded multiple: shapes stay stable across
    # churn, so jitted consumers keep their compiled programs.
    pad_mult = edge_pad_multiple or graph.edge_pad_multiple
    add_s, add_r = delta.add_senders, delta.add_receivers
    rem_s, rem_r = delta.remove_senders, delta.remove_receivers
    _check_endpoints(add_s, add_r, graph.n_nodes)
    _check_endpoints(rem_s, rem_r, graph.n_nodes)
    weighted = graph.edge_weight is not None
    if weighted and add_s.size and delta.add_weights is None:
        raise ValueError(
            "graph carries edge weights; GraphDelta adds need add_weights")
    if not weighted and delta.add_weights is not None:
        raise ValueError(
            "add_weights on an unweighted graph — build with "
            "from_edges(weights=...) first")

    with _phase("delta_sort"):
        # Radix-sort only the delta (native sort_pairs): adds stably by
        # receiver — the order a stable from-scratch sort would give the
        # appended batch — removals by (receiver, sender) for the linear
        # anti-merge walk.
        add_w = delta.add_weights
        if add_s.size:
            _, perm = native.sort_pairs(
                add_r, np.arange(add_s.size, dtype=np.int32))
            add_r, add_s = add_r[perm], add_s[perm]
            if weighted:
                add_w = add_w[perm]
        if rem_s.size:
            order = np.lexsort((rem_s, rem_r))
            rem_r, rem_s = rem_r[order], rem_s[order]

    base_s = np.asarray(graph.senders)
    base_r = np.asarray(graph.receivers)
    emask = np.asarray(graph.edge_mask)
    live_count = int(np.count_nonzero(emask))
    # Pristine = every build edge still live: the precondition for
    # copy-then-patch on the neighbor table and CSR (a failure-masked base
    # compacts differently; those fall back to full recomputes of just
    # those two derived views).
    pristine = live_count == graph.n_edges

    with _phase("delta_merge"):
        keep, matched = native.delta_antimerge(
            base_r, base_s, emask, rem_r, rem_s)
        if not bool(matched.all()):
            missing = np.flatnonzero(~matched)[:5]
            pairs = [(int(rem_s[i]), int(rem_r[i])) for i in missing]
            raise ValueError(
                f"{int((~matched).sum())} removal pair(s) match no live "
                f"edge (first few as (sender, receiver): {pairs})")
        e_new = int(np.count_nonzero(keep)) + int(add_s.size)
        e_pad = _round_up(max(e_new, 1), pad_mult)
        # The merge writes straight into the padded target buffers — no
        # second copy pass; only the padding tails are filled after.
        s_arr = np.empty(e_pad, dtype=np.int32)
        r_arr = np.empty(e_pad, dtype=np.int32)
        out_r, out_s, posa, posb = native.delta_merge(
            base_r, base_s, keep, add_r, add_s, out_r=r_arr, out_s=s_arr)
        r_arr[e_new:] = n_pad - 1
        s_arr[e_new:] = 0
        emask_new = np.empty(e_pad, dtype=bool)
        emask_new[:e_new] = True
        emask_new[e_new:] = False
        w_arr = w_unpadded = None
        if weighted:
            w_host = np.asarray(graph.edge_weight)
            w_arr = np.zeros(e_pad, dtype=np.float32)
            kept_slots = posa >= 0
            w_arr[posa[kept_slots]] = w_host[kept_slots]
            if add_s.size:
                w_arr[posb] = add_w
            w_unpadded = w_arr[:e_new]

    with _phase("delta_degrees"):
        # Degrees update from the batch alone (in place, O(delta));
        # dynamic-region contributions (sim/topology.py connect) ride
        # inside in_degree/out_degree already and stay put — only the
        # STATIC views (span, table width, CSR counts) subtract them.
        rm_pos = (np.flatnonzero(emask ^ keep) if rem_s.size  # keep ⊆ emask
                  else np.zeros(0, dtype=np.int64))
        if donate:
            # Donated scatter-add: no O(N) degree-array copies; the donor
            # graph's degree buffers are invalidated.
            if rm_pos.size or add_s.size:
                # Zero-padded to a power-of-two bucket (adding 0 at index
                # 0 is the identity) so varying batch sizes reuse a
                # handful of compiled scatters.
                b = _pow2_pad(rm_pos.size + add_s.size)
                deltas = np.zeros(b, dtype=np.int32)
                deltas[:rm_pos.size] = -1
                deltas[rm_pos.size:rm_pos.size + add_s.size] = 1
                idx_r = np.zeros(b, dtype=np.int32)
                idx_r[:rm_pos.size + add_s.size] = np.concatenate(
                    [base_r[rm_pos], add_r])
                idx_s = np.zeros(b, dtype=np.int32)
                idx_s[:rm_pos.size + add_s.size] = np.concatenate(
                    [base_s[rm_pos], add_s])
                in_deg_new = _scatter_add_donating(
                    graph.in_degree, idx_r, deltas)
                out_deg_new = _scatter_add_donating(
                    graph.out_degree, idx_s, deltas)
            else:
                in_deg_new, out_deg_new = graph.in_degree, graph.out_degree
            in_host = np.asarray(in_deg_new)
            out_host = np.asarray(out_deg_new)
        else:
            in_host = np.asarray(graph.in_degree).copy()
            out_host = np.asarray(graph.out_degree).copy()
            if rm_pos.size:
                np.subtract.at(in_host, base_r[rm_pos], 1)
                np.subtract.at(out_host, base_s[rm_pos], 1)
            if add_s.size:
                np.add.at(in_host, add_r, 1)
                np.add.at(out_host, add_s, 1)
            in_deg_new, out_deg_new = in_host, out_host
        if graph.dyn_mask is not None:
            dm = np.asarray(graph.dyn_mask)
            static_in = in_host - np.bincount(
                np.asarray(graph.dyn_receivers)[dm],
                minlength=n_pad).astype(np.int32)
            static_out = out_host - np.bincount(
                np.asarray(graph.dyn_senders)[dm],
                minlength=n_pad).astype(np.int32)
        else:
            static_in, static_out = in_host, out_host
        max_in_span = max(int(static_in.max()) if e_new else 0, 1)

    nb = nbm = nw = None
    complete = graph.neighbors_complete
    if graph.neighbors is not None:
        with _phase("neighbor_table"):
            touched = np.unique(np.concatenate([rem_r, add_r]))
            nb, nbm, nw, complete = _delta_neighbor_tables(
                graph, out_r, out_s, w_unpadded, static_in, touched,
                graph.max_degree, pristine, donate=donate)

    src_eid = src_offsets = None
    max_out_span = graph.max_out_span
    if graph.src_eid is not None:
        with _phase("source_csr"):
            counts = static_out[:n_pad]
            src_offsets = np.zeros(n_pad + 1, dtype=np.int32)
            np.cumsum(counts, out=src_offsets[1:])
            max_out_span = int(counts.max()) if e_new else 0
            eid_arr = np.empty(e_pad, dtype=np.int32)
            eid_arr[e_new:] = e_pad - 1
            if pristine:
                kept_eids = native.map_filter(
                    np.asarray(graph.src_eid)[:graph.n_edges], posa)
                if add_s.size:
                    # posb ascends along the (receiver-sorted) adds, so a
                    # stable sender sort leaves per-sender ids ascending —
                    # the (sender, eid) order the merge needs.
                    _, add_eids = native.sort_pairs(add_s, posb)
                    native.merge_eids_by_sender(
                        out_s, kept_eids, add_eids, out=eid_arr[:e_new])
                else:
                    eid_arr[:e_new] = kept_eids
            else:
                eid_arr, src_offsets, max_out_span = _build_source_csr(
                    s_arr, emask_new, n_pad, e_pad)
            src_eid = eid_arr

    blocked_rep, hybrid_rep, skew_rep = graph.blocked, graph.hybrid, graph.skew
    if blocked_rep is not None or hybrid_rep is not None \
            or skew_rep is not None:
        with _phase("layouts"):
            # Rebuilds keep the base's RECORDED tuning (blocked/hybrid
            # block size, skew row width); the hybrid diagonal budget
            # (max_diags/min_count) is not recorded on the representation
            # and re-derives at its defaults.
            if blocked_rep is not None:
                from p2pnetwork_tpu.ops.blocked import \
                    build_blocked_from_arrays

                blocked_rep = build_blocked_from_arrays(
                    out_s, out_r, n_pad, blocked_rep.block)
            if hybrid_rep is not None:
                from p2pnetwork_tpu.ops.diag import build_hybrid_from_arrays

                kw = {}
                if hybrid_rep.remainder is not None:
                    kw["block"] = hybrid_rep.remainder.block
                hybrid_rep = build_hybrid_from_arrays(
                    out_s, out_r, graph.n_nodes, n_pad, **kw)
            if skew_rep is not None:
                from p2pnetwork_tpu.ops.skew import build_skew_from_arrays

                skew_rep = build_skew_from_arrays(
                    out_s, out_r, n_pad, e_pad, width=skew_rep.width,
                    weights=w_unpadded)

    arrays = {
        "senders": s_arr,
        "receivers": r_arr,
        "edge_mask": emask_new,
        "in_degree": in_deg_new,
        "out_degree": out_deg_new,
    }
    if nb is not None:
        arrays["neighbors"] = nb
        arrays["neighbor_mask"] = nbm
    if nw is not None:
        arrays["neighbor_weight"] = nw
    if src_eid is not None:
        arrays["src_eid"] = src_eid
        arrays["src_offsets"] = src_offsets
    if w_arr is not None:
        arrays["edge_weight"] = w_arr
    # One batched host->device put for every updated array (a per-array
    # jnp.asarray pays a fixed dispatch cost ~10x over).
    arrays = jax.device_put(arrays)
    return dataclasses.replace(
        graph,
        n_edges=e_new,
        neighbors_complete=complete,
        edge_pad_multiple=pad_mult,
        max_in_span=max_in_span,
        blocked=blocked_rep,
        hybrid=hybrid_rep,
        skew=skew_rep,
        max_out_span=max_out_span,
        ring=None,
        **arrays,
    )


# ----------------------------------------------------------- live growth


def growth_capacity(demand: int, current: int) -> int:
    """Geometric node-capacity schedule: the smallest doubling of
    ``current`` that covers ``demand``.

    Doubling (not rounding up to the next pad multiple) is what makes
    :func:`grow` amortized: a sequence of K single-node growth steps
    crosses only O(log K) capacity boundaries, so the capacity-dependent
    rebuilds — and the recompiles every jitted consumer pays at a new
    ``N_pad`` — are paid O(log K) times, not K times. Doubling a pad
    multiple stays a pad multiple, so XLA tiling assumptions hold at
    every step.
    """
    cap = max(int(current), 1)
    demand = int(demand)
    while cap < demand:
        cap *= 2
    return cap


def grow(graph: Graph, n_new_nodes: int, *,
         node_capacity: Optional[int] = None) -> Graph:
    """Grow the overlay by ``n_new_nodes`` fresh live node ids
    (``n_nodes .. n_nodes + n_new_nodes - 1``), repadding node capacity
    on the geometric schedule of :func:`growth_capacity` when demand
    exceeds the current ``N_pad``.

    The node-capacity counterpart of :func:`apply_delta`'s O(delta) edge
    churn: existing node ids, edges, liveness masks, and the dynamic
    edge region are preserved bit-for-bit; only the capacity-dependent
    leaves are rebuilt (node mask/degrees zero-extended, neighbor-table
    rows zero-extended, the COO padding tail re-aimed at the new
    ``N_pad - 1`` sentinel so the receiver sort order survives, CSR
    offsets extended, layout permutations identity-extended, and the
    blocked/hybrid/skew layouts rebuilt at the new capacity with their
    recorded tuning). The result is bit-identical to a from-scratch
    :func:`from_edges` of the same edge list at
    ``node_pad_multiple=new capacity`` — wire the new nodes' edges with
    the existing :func:`apply_delta` machinery afterwards (its
    ``donate=True`` fast path stays valid: every grown leaf is a fresh
    device buffer).

    ``node_capacity`` pins an explicit target capacity (>= both the
    current capacity and the grown node count) instead of the doubling
    schedule — the repad-resume path uses it to match a checkpoint's
    recorded capacity exactly. When neither the node count nor the
    capacity changes this is a no-op returning ``graph`` itself.
    """
    if n_new_nodes < 0:
        raise ValueError("n_new_nodes must be >= 0")
    n_pad = graph.n_nodes_padded
    new_n = graph.n_nodes + int(n_new_nodes)
    new_pad = growth_capacity(new_n, n_pad)
    if node_capacity is not None:
        if int(node_capacity) < max(new_n, n_pad):
            raise ValueError(
                f"node_capacity {node_capacity} below the grown node "
                f"count {new_n} / current capacity {n_pad}")
        new_pad = int(node_capacity)
    if n_new_nodes == 0 and new_pad == n_pad:
        return graph
    _reset_phases()
    with _phase("grow"):
        g = _grow(graph, new_n, new_pad)
    telemetry.default_registry().counter(
        "sim_graph_grow_total",
        "Live overlay growth steps, split by whether node capacity "
        "repadded.", ("repad",)).labels(
            "true" if new_pad != n_pad else "false").inc()
    return g


def _grow(graph: Graph, new_n: int, new_pad: int) -> Graph:
    n_nodes, n_pad = graph.n_nodes, graph.n_nodes_padded
    e, e_pad = graph.n_edges, graph.n_edges_padded
    hybrid_rep = graph.hybrid
    s_live = r_live = None
    if graph.blocked is not None or hybrid_rep is not None \
            or graph.skew is not None:
        s_live = np.asarray(graph.senders)[:e]
        r_live = np.asarray(graph.receivers)[:e]

    if new_pad == n_pad:
        # Capacity holds: flip the new ids live and bump the static node
        # count. The hybrid layout is the one capacity-independent view
        # that bakes n_nodes (its diagonal census runs over the live
        # block), so it alone rebuilds.
        nm = np.asarray(graph.node_mask).copy()
        nm[n_nodes:new_n] = True
        if hybrid_rep is not None:
            from p2pnetwork_tpu.ops.diag import build_hybrid_from_arrays

            kw = {}
            if hybrid_rep.remainder is not None:
                kw["block"] = hybrid_rep.remainder.block
            hybrid_rep = build_hybrid_from_arrays(
                s_live, r_live, new_n, n_pad, **kw)
        return dataclasses.replace(
            graph, n_nodes=new_n, hybrid=hybrid_rep, ring=None,
            node_mask=jax.device_put(nm))

    # Repad: rebuild exactly the capacity-dependent leaves. Everything
    # edge-shaped except the receiver padding tail is N-independent and
    # carries over untouched (senders pad with 0, src_eid with e_pad-1).
    nm = np.zeros(new_pad, dtype=bool)
    nm[:n_pad] = np.asarray(graph.node_mask)
    nm[n_nodes:new_n] = True
    in_deg = np.zeros(new_pad, dtype=np.int32)
    in_deg[:n_pad] = np.asarray(graph.in_degree)
    out_deg = np.zeros(new_pad, dtype=np.int32)
    out_deg[:n_pad] = np.asarray(graph.out_degree)
    # Padding receivers re-aim at the NEW last padded id — still >= every
    # live id, so the non-decreasing promise behind
    # indices_are_sorted=True survives the repad.
    r_arr = np.asarray(graph.receivers).copy()
    r_arr[e:] = new_pad - 1
    arrays = {"node_mask": nm, "in_degree": in_deg, "out_degree": out_deg,
              "receivers": r_arr}

    if graph.neighbors is not None:
        # Row-extend with empty rows — exactly what from_edges builds for
        # ids with no incoming edges, so capped-row subsampling (whose
        # shared RNG stream depends only on the capped degrees, which
        # growth never changes) stays bit-identical.
        width = graph.neighbors.shape[1]
        nb = np.zeros((new_pad, width), dtype=np.int32)
        nb[:n_pad] = np.asarray(graph.neighbors)
        nbm = np.zeros((new_pad, width), dtype=bool)
        nbm[:n_pad] = np.asarray(graph.neighbor_mask)
        arrays["neighbors"] = nb
        arrays["neighbor_mask"] = nbm
        if graph.neighbor_weight is not None:
            nw = np.zeros((new_pad, width), dtype=np.float32)
            nw[:n_pad] = np.asarray(graph.neighbor_weight)
            arrays["neighbor_weight"] = nw

    if graph.src_offsets is not None:
        # New rows own zero out-edges: the exclusive-prefix-sum tail just
        # repeats the total. src_eid's e_pad-1 padding fill is
        # N-independent and rides along.
        so = np.asarray(graph.src_offsets)
        arrays["src_offsets"] = np.concatenate(
            [so, np.full(new_pad - n_pad, so[-1], dtype=np.int32)])

    if graph.layout_perm is not None:
        # The relabeling extends with the identity over the new capacity
        # range, like from_edges pads it over the padding ids.
        ext = np.arange(n_pad, new_pad, dtype=np.int32)
        arrays["layout_perm"] = np.concatenate(
            [np.asarray(graph.layout_perm), ext])
        arrays["layout_inv"] = np.concatenate(
            [np.asarray(graph.layout_inv), ext])

    blocked_rep, skew_rep = graph.blocked, graph.skew
    if blocked_rep is not None:
        from p2pnetwork_tpu.ops.blocked import build_blocked_from_arrays

        blocked_rep = build_blocked_from_arrays(
            s_live, r_live, new_pad, blocked_rep.block)
    if hybrid_rep is not None:
        from p2pnetwork_tpu.ops.diag import build_hybrid_from_arrays

        kw = {}
        if hybrid_rep.remainder is not None:
            kw["block"] = hybrid_rep.remainder.block
        hybrid_rep = build_hybrid_from_arrays(
            s_live, r_live, new_n, new_pad, **kw)
    if skew_rep is not None:
        from p2pnetwork_tpu.ops.skew import build_skew_from_arrays

        w_unpadded = None
        if graph.edge_weight is not None:
            w_unpadded = np.asarray(graph.edge_weight)[:e]
        skew_rep = build_skew_from_arrays(
            s_live, r_live, new_pad, e_pad, width=skew_rep.width,
            weights=w_unpadded)

    arrays = jax.device_put(arrays)
    return dataclasses.replace(
        graph, n_nodes=new_n, blocked=blocked_rep, hybrid=hybrid_rep,
        skew=skew_rep, ring=None, **arrays)


def from_edges(
    senders,
    receivers,
    n_nodes: int,
    *,
    node_pad_multiple: int = 128,
    edge_pad_multiple: int = 128,
    build_neighbor_table: bool = True,
    max_degree: Optional[int] = None,
    blocked: bool = False,
    hybrid: bool = False,
    skew_table: bool = False,
    skew_width: int = 0,
    source_csr: bool = False,
    weights=None,
    reorder: Optional[str] = None,
    ring_shards: int = 0,
) -> Graph:
    """Build a :class:`Graph` from host-side edge arrays.

    Edges are sorted by receiver and padded to ``edge_pad_multiple``; nodes
    are padded to ``node_pad_multiple`` (lane-friendly sizes keep XLA tiling
    happy). Padded edges point at the LAST padded node index (keeping the
    receiver array non-decreasing — the ``indices_are_sorted=True`` promise
    the segment reductions rely on) and are masked out of every aggregation.
    ``max_degree`` caps the neighbor table width (default: the true maximum
    in-degree).

    ``blocked=True`` / ``hybrid=True`` attach those aggregation
    representations *during* construction — same results as the
    ``with_blocked()`` / ``with_hybrid()`` methods, but built from the
    host-side arrays already in hand instead of pulling device arrays back
    over the wire (a multi-second round trip at BASELINE scale).

    ``reorder`` (opt-in; ``"degree"`` or ``"rcm"``, sim/layout.py) relabels
    node ids through an IO-aware permutation before building, so gathers
    over neighbor rows hit contiguous memory; the mapping is recorded on
    the graph (``layout_perm``/``layout_inv``) and every runtime id then
    speaks the relabeled space — map results back with
    ``layout.to_original_order``.

    ``ring_shards=S`` (S > 0) attaches the host-side ring layout for an
    ``S``-shard mesh (parallel/ringlayout.py: the bucketing, the diagonal
    pieces and the MXU remainder of the default ``hybrid`` attach), which
    ``parallel.sharded.shard_graph`` then only pads and places.
    """
    senders = np.asarray(senders, dtype=np.int32)
    receivers = np.asarray(receivers, dtype=np.int32)
    if senders.shape != receivers.shape:
        raise ValueError("senders and receivers must have the same shape")
    if senders.size and (senders.max() >= n_nodes or receivers.max() >= n_nodes):
        raise ValueError("edge endpoint out of range")

    _reset_phases()
    layout_perm = layout_inv = None
    if reorder is not None:
        with _phase("reorder"):
            from p2pnetwork_tpu.sim import layout

            perm = layout.node_permutation(senders, receivers, n_nodes,
                                           strategy=reorder)
            senders = perm[senders]
            receivers = perm[receivers]
            layout_perm = perm

    with _phase("sort"):
        if weights is not None:
            # Per-edge costs (latency-weighted overlays): permute through
            # the same receiver sort as the endpoints so everything stays
            # aligned.
            weights = np.asarray(weights, dtype=np.float32)
            if weights.shape != senders.shape:
                raise ValueError("weights must align with senders/receivers")
            receivers, perm = native.sort_pairs(
                receivers, np.arange(senders.size, dtype=np.int32))
            senders = senders[perm]
            weights = weights[perm]
        else:
            receivers, senders = native.sort_pairs(receivers, senders)

    n_pad = _round_up(max(n_nodes, 1), node_pad_multiple)
    e = senders.size
    e_pad = _round_up(max(e, 1), edge_pad_multiple)

    s = np.zeros(e_pad, dtype=np.int32)
    # Padding receivers with n_pad-1 (>= every active id) keeps the array
    # sorted; padded contributions are zeroed by edge_mask either way.
    r = np.full(e_pad, n_pad - 1, dtype=np.int32)
    s[:e], r[:e] = senders, receivers
    emask = np.zeros(e_pad, dtype=bool)
    emask[:e] = True
    w = None
    if weights is not None:
        w = np.zeros(e_pad, dtype=np.float32)
        w[:e] = weights
    nmask = np.zeros(n_pad, dtype=bool)
    nmask[:n_nodes] = True

    in_deg = np.bincount(receivers, minlength=n_pad).astype(np.int32)
    out_deg = np.bincount(senders, minlength=n_pad).astype(np.int32)
    # The padding tail (receiver n_pad-1, edge_mask False) extends that id's
    # run but can never match a probe — edge_mask excludes it — so the
    # window only needs to span the widest LIVE run.
    max_in_span = max(int(in_deg.max()) if e else 0, 1)

    if layout_perm is not None:
        # Pad the relabeling with the identity over the padding ids so the
        # recorded mapping covers the full padded id space.
        layout_perm = np.concatenate([
            layout_perm.astype(np.int32),
            np.arange(n_nodes, n_pad, dtype=np.int32)])
        layout_inv = np.empty_like(layout_perm)
        layout_inv[layout_perm] = np.arange(n_pad, dtype=np.int32)

    neighbors = neighbor_mask = neighbor_weight = None
    neighbors_complete = True
    _t_table = time.perf_counter()
    if build_neighbor_table:
        width = int(in_deg.max()) if e else 0
        if max_degree is not None:
            neighbors_complete = max_degree >= width
            width = min(width, max_degree)
        width = max(width, 1)
        # receivers are sorted, so each node's incoming edges are contiguous.
        starts = np.searchsorted(receivers, np.arange(n_pad))
        ends = np.searchsorted(receivers, np.arange(n_pad), side="right")
        take, valid = _padded_row_fill(starts, np.minimum(ends - starts, width), width)
        # Over-degree rows get a uniform random subset of their in-edges
        # (deterministic seed: graph construction stays reproducible). A
        # plain prefix would bias Gossip's partner draw toward whichever
        # senders happen to sort first. Vectorized: rank random keys per
        # edge within its row; an edge is kept iff its rank < width — a
        # uniform width-subset for every capped row in one pass.
        capped = np.nonzero(ends - starts > width)[0]
        if capped.size:
            cap_rng = np.random.default_rng(0)
            deg = ends - starts
            cap_edge = np.repeat(capped, deg[capped])
            offs = np.arange(cap_edge.size) - np.repeat(
                np.cumsum(deg[capped]) - deg[capped], deg[capped]
            )
            edge_idx = starts[cap_edge] + offs
            keys = cap_rng.random(edge_idx.size)
            # rank within row = position after sorting by (row, key)
            order = np.lexsort((keys, cap_edge))
            rank = np.empty_like(offs)
            rank[order] = offs
            kept = rank < width  # exactly `width` uniform survivors per row
            resort = np.lexsort((edge_idx[kept], cap_edge[kept]))
            take[capped] = edge_idx[kept][resort].reshape(capped.size, width)
        # A dummy pool entry keeps the (eagerly evaluated) gather in-bounds
        # for zero-edge graphs; `valid` masks it out.
        pool = senders if e else np.zeros(1, dtype=np.int32)
        take_safe = np.minimum(take, max(e - 1, 0))
        neighbors = np.where(valid, pool[take_safe], 0).astype(np.int32)
        neighbor_mask = valid
        if weights is not None:
            wpool = weights if e else np.zeros(1, dtype=np.float32)
            neighbor_weight = np.where(valid, wpool[take_safe], 0.0).astype(
                np.float32)

    if build_neighbor_table:
        _note_phase("neighbor_table", time.perf_counter() - _t_table)

    blocked_rep = hybrid_rep = skew_rep = None
    _t_layouts = time.perf_counter()
    if blocked:
        from p2pnetwork_tpu.ops.blocked import build_blocked_from_arrays

        blocked_rep = build_blocked_from_arrays(senders, receivers, n_pad)
    if hybrid:
        from p2pnetwork_tpu.ops.diag import build_hybrid_from_arrays

        hybrid_rep = build_hybrid_from_arrays(senders, receivers, n_nodes, n_pad)
    if skew_table:
        from p2pnetwork_tpu.ops.skew import build_skew_from_arrays

        skew_rep = build_skew_from_arrays(
            senders, receivers, n_pad, e_pad, width=skew_width,
            weights=weights,
        )
    if blocked or hybrid or skew_table:
        _note_phase("layouts", time.perf_counter() - _t_layouts)

    ring_rep = None
    if ring_shards:
        with _phase("ring_layout"):
            from p2pnetwork_tpu.parallel import ringlayout

            ring_rep = ringlayout.build(senders, receivers, n_nodes, n_pad,
                                        ring_shards)

    src_eid = src_offsets = None
    max_out_span = 0
    if source_csr:
        with _phase("source_csr"):
            src_eid, src_offsets, max_out_span = _build_source_csr(
                s, emask, n_pad, e_pad
            )
            src_eid = jnp.asarray(src_eid)
            src_offsets = jnp.asarray(src_offsets)

    return Graph(
        senders=jnp.asarray(s),
        receivers=jnp.asarray(r),
        edge_mask=jnp.asarray(emask),
        node_mask=jnp.asarray(nmask),
        in_degree=jnp.asarray(in_deg),
        out_degree=jnp.asarray(out_deg),
        neighbors=None if neighbors is None else jnp.asarray(neighbors),
        neighbor_mask=None if neighbor_mask is None else jnp.asarray(neighbor_mask),
        n_nodes=n_nodes,
        n_edges=e,
        neighbors_complete=neighbors_complete,
        max_degree_cap=max_degree,
        edge_pad_multiple=edge_pad_multiple,
        max_in_span=max_in_span,
        blocked=blocked_rep,
        hybrid=hybrid_rep,
        skew=skew_rep,
        src_eid=src_eid,
        src_offsets=src_offsets,
        max_out_span=max_out_span,
        edge_weight=None if w is None else jnp.asarray(w),
        neighbor_weight=(None if neighbor_weight is None
                         else jnp.asarray(neighbor_weight)),
        layout_perm=(None if layout_perm is None
                     else jnp.asarray(layout_perm)),
        layout_inv=None if layout_inv is None else jnp.asarray(layout_inv),
        ring=ring_rep,
    )


def _undirect(src: np.ndarray, dst: np.ndarray):
    """Duplicate each undirected edge into both directions."""
    return np.concatenate([src, dst]), np.concatenate([dst, src])


def _pair_bits(n: int) -> int:
    """Bits needed to hold an id in ``[0, n)`` — the shift of the packed
    undirected-pair key. Using the minimal width (not a fixed 32) keeps the
    radix sort at the fewest 16-bit passes the key range allows."""
    return max(int(n - 1).bit_length(), 1)


def _dedup_undirected(src: np.ndarray, dst: np.ndarray, n: int):
    """Unique undirected pairs as (lo, hi) int32 arrays.

    Encodes each pair as ``min << b | max`` (``b`` = bits of ``n-1``; int64,
    safe for any int32 id range) and dedups with one native radix sort pass
    — shared by every random generator so each undirected edge enters the
    graph exactly once (duplicates would double-count infection pressure in
    SIR). Shifts/masks, not ``*n`` / ``// n``: the int64 divisions of the
    arithmetic encoding were a measured hotspot of graph build at 10M nodes.
    """
    t0 = time.perf_counter()
    b = _pair_bits(n)
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst)
    keys = native.sort_unique((lo << b) | hi)
    out = (keys >> b).astype(np.int32), (keys & ((1 << b) - 1)).astype(np.int32)
    _note_dedup(time.perf_counter() - t0)
    return out


def erdos_renyi(n: int, p: float, seed: int = 0, **kw) -> Graph:
    """G(n, p) random graph (undirected).

    For scale, the number of undirected edges is drawn from the matching
    binomial and pairs are sampled uniformly (with collision dedup) instead
    of materialising the O(n^2) adjacency — equivalent in distribution up to
    the dedup, and the only tractable construction at millions of nodes.
    """
    rng = np.random.default_rng(seed)
    n_pairs = n * (n - 1) // 2
    m = rng.binomial(n_pairs, p) if n_pairs < 2**63 else int(p * n_pairs)
    if m == 0:
        return from_edges(np.zeros(0), np.zeros(0), n, **kw)
    # Accumulate unique pairs until we have at least m, then subsample to
    # exactly m uniformly — truncating the (sorted) unique keys instead would
    # bias edges toward low-index nodes.
    t0 = time.perf_counter()
    b = _pair_bits(n)
    keys = np.zeros(0, dtype=np.int64)
    draw = int(m * 1.2) + 16
    while keys.size < m:
        src = rng.integers(0, n, size=draw, dtype=np.int64)
        dst = rng.integers(0, n, size=draw, dtype=np.int64)
        keep = src != dst
        lo, hi = np.minimum(src[keep], dst[keep]), np.maximum(src[keep], dst[keep])
        keys = native.sort_unique(np.concatenate([keys, (lo << b) | hi]))
        draw *= 2
    keys = rng.permutation(keys)[:m]
    _note_dedup(time.perf_counter() - t0)
    lo = (keys >> b).astype(np.int32)
    hi = (keys & ((1 << b) - 1)).astype(np.int32)
    return from_edges(*_undirect(lo, hi), n, **kw)


def barabasi_albert(n: int, m: int, seed: int = 0, **kw) -> Graph:
    """Barabási–Albert preferential attachment via the Bollobás linearized
    chord diagram (LCD) construction — the rigorous formulation of the BA
    process, chosen because it vectorizes exactly.

    Sequential BA ("attach proportionally to current degree") looks
    inherently serial: each attachment changes the degrees the next one
    samples from. In the LCD form, mini-vertex ``i``'s target is a uniform
    draw ``u_i`` over ``2i+1`` endpoint slots whose *layout* is fixed in
    advance — slot ``2j`` holds mini-vertex ``j``, slot ``2j+1`` holds
    ``j``'s (yet unresolved) target, slot ``2i`` means a self-loop — so a
    node's appearance count equals its degree and the draw is exactly
    degree-proportional. All draws happen up front; odd slots form pointer
    chains to earlier draws, resolved in O(log chain) pointer-doubling
    passes. ``m > 1`` contracts groups of ``m`` consecutive mini-vertices;
    self-loops and duplicate pairs are dropped (so a node can end with
    fewer than ``m`` attachments, as in the standard construction).
    """
    if m < 1 or m >= n:
        raise ValueError("barabasi_albert requires 1 <= m < n")
    rng = np.random.default_rng(seed)
    N = n * m  # mini-vertices of the m=1 process
    i = np.arange(N, dtype=np.int64)
    u = (rng.random(N) * (2 * i + 1)).astype(np.int64)  # uniform on [0, 2i]
    # Even slot -> resolved node id (slot 2i is the self-loop, = i). Odd
    # slot -> the target of an earlier draw: follow the chain.
    targets = np.where(u % 2 == 0, u // 2, np.int64(-1))
    parent = np.where(u % 2 == 1, (u - 1) // 2, i)
    unresolved = targets < 0
    while unresolved.any():
        targets = np.where(unresolved, targets[parent], targets)
        parent = parent[parent]  # pointer doubling
        unresolved = targets < 0
    src = i // m
    dst = targets // m
    keep = src != dst  # drop self-loops (LCD produces them by design)
    lo, hi = _dedup_undirected(src[keep], dst[keep], n)
    return from_edges(*_undirect(lo, hi), n, **kw)


def watts_strogatz(n: int, k: int, p: float, seed: int = 0, **kw) -> Graph:
    """Watts–Strogatz small world: ring lattice with ``k`` neighbors per node
    (k/2 each side), each edge rewired with probability ``p``. Vectorized —
    this is the generator used for the million-node benchmark configs."""
    if k % 2 != 0:
        raise ValueError("watts_strogatz requires even k")
    if k >= n:
        # The ring lattice needs k distinct neighbors per node; the wrap
        # arithmetic below folds base+off past n at most once, which only
        # covers offsets < n.
        raise ValueError("watts_strogatz requires k < n")
    rng = np.random.default_rng(seed)
    base = np.arange(n, dtype=np.int32)
    srcs, dsts = [], []
    for off in range(1, k // 2 + 1):
        src = base
        # base + off wraps at most once past n, so a conditional subtract
        # replaces the (per-element integer division) modulo.
        ring_dst = base + np.int32(off)
        ring_dst = np.where(ring_dst >= n, ring_dst - np.int32(n), ring_dst)
        rewire = rng.random(n) < p
        new_dst = rng.integers(0, n, size=n, dtype=np.int32)
        dst = np.where(rewire, new_dst, ring_dst)
        dst = np.where(dst == src, ring_dst, dst)
        srcs.append(src)
        dsts.append(dst)
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    # A rewired target can collide with another (lattice or rewired) edge of
    # the same node; dedup so each undirected pair appears once.
    lo, hi = _dedup_undirected(src, dst, n)
    return from_edges(*_undirect(lo, hi), n, **kw)


def ring(n: int, **kw) -> Graph:
    """Simple bidirectional ring."""
    base = np.arange(n, dtype=np.int32)
    return from_edges(*_undirect(base, (base + 1) % n), n, **kw)


def chord(n: int, **kw) -> Graph:
    """Chord-style structured overlay: the identifier ring plus a finger
    to ``(v + 2^i) mod n`` for every ``i`` with ``2^i < n`` — the DHT
    topology (successor lists + finger tables) that P2P deployments build
    on top of unstructured libraries like the reference. O(log n) degree,
    O(log n) diameter: greedy/BFS routing here is the batched form of a
    Chord lookup. Edges are undirected (the reference's TCP-connection
    semantic: traffic flows both ways)."""
    if n < 2:
        raise ValueError("chord requires n >= 2 (no fingers exist below that)")
    base = np.arange(n, dtype=np.int64)
    srcs, dsts = [], []
    i = 0
    while (1 << i) < n:
        srcs.append(base)
        dsts.append((base + (1 << i)) % n)
        i += 1
    lo, hi = _dedup_undirected(np.concatenate(srcs), np.concatenate(dsts), n)
    return from_edges(*_undirect(lo, hi), n, **kw)


def kademlia(n: int, k: int = 1, **kw) -> Graph:
    """Kademlia-style structured overlay: for every node ``v`` and every
    XOR-distance bucket ``[2^i, 2^(i+1))`` with ``2^i < n``, edges to the
    ``k`` CLOSEST ids in that bucket — ``v ^ d`` for ``d = 2^i ..
    2^i + k - 1`` (the k smallest XOR distances the band contains), kept
    when the partner id exists. The other classic DHT geometry beside
    :func:`chord`: XOR-metric buckets instead of modular fingers, so at
    ``k = 1`` on a fully-populated (power-of-two) id space this is
    exactly the binary hypercube Kademlia lookups walk, with O(log n)
    degree and O(log n) diameter; larger ``k`` is the bucket width
    (Kademlia's replication parameter) adding redundancy per band. Ids
    above ``n - 1`` don't exist (a partially-populated id space): when
    the rank-``j`` closest partner ``v ^ (2^i + j)`` is such a ghost,
    the edge falls back to the ``j``-th LOWEST id of the bucket's live
    range — farther by XOR but a legitimate bucket contact, so every
    populated bucket gets at least its ``j = 0`` edge (a bucket holding
    fewer than ``j + 1`` live ids simply has no rank-``j`` contact, like
    a real routing table's short bucket). Deterministic; edges
    undirected (the reference's TCP-connection semantic)."""
    if n < 2:
        raise ValueError("kademlia requires n >= 2 (no buckets below that)")
    if k < 1:
        raise ValueError("k must be >= 1")
    v = np.arange(n, dtype=np.int64)
    srcs, dsts = [], []
    i = 0
    while (1 << i) < n:
        width = 1 << i
        # The bucket's id range: v's prefix above bit i with bit i flipped.
        bucket_base = ((v >> i) ^ 1) << i
        v_low = v & (width - 1)
        for j in range(min(k, width)):
            ideal = bucket_base + (v_low ^ j)  # XOR distance 2^i + j
            fallback = bucket_base + j  # always exists when the bucket does
            cand = np.where(ideal < n, ideal, fallback)
            keep = cand < n
            srcs.append(v[keep])
            dsts.append(cand[keep])
        i += 1
    lo, hi = _dedup_undirected(np.concatenate(srcs), np.concatenate(dsts), n)
    return from_edges(*_undirect(lo, hi), n, **kw)


def complete(n: int, **kw) -> Graph:
    """Complete graph (every pair connected) — small n only."""
    src, dst = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    keep = src != dst
    return from_edges(src[keep].astype(np.int32), dst[keep].astype(np.int32), n, **kw)


def build(topology) -> Graph:
    """Build a graph from a :class:`p2pnetwork_tpu.config.TopologyConfig`."""
    kind = topology.kind
    if kind == "erdos_renyi":
        return erdos_renyi(topology.n_nodes, topology.p, topology.seed)
    if kind == "barabasi_albert":
        return barabasi_albert(topology.n_nodes, topology.k, topology.seed)
    if kind == "watts_strogatz":
        return watts_strogatz(topology.n_nodes, topology.k, topology.p, topology.seed)
    if kind == "ring":
        return ring(topology.n_nodes)
    if kind == "chord":
        return chord(topology.n_nodes)
    if kind == "kademlia":
        return kademlia(topology.n_nodes, topology.k)
    if kind == "complete":
        return complete(topology.n_nodes)
    raise ValueError(f"unknown topology kind: {kind!r}")

"""Hybrid diagonal + blocked edge aggregation — the gather-free fast path.

XLA's TPU gather costs ~8 cycles per element regardless of source-array
size or index order (measured: 11M-element gathers take ~90 ms whether the
source is 4 KB or 4 MB, sorted or random) — it is the entire cost of a
propagation round at BASELINE scale. This module removes the gather for the
structured part of the graph.

Most peer topologies that arise from ring/lattice construction (the
Watts–Strogatz small-world benchmark family, rings, k-regular lattices)
concentrate their edges on a few **circular diagonals**: edge sets of the
form ``{(v + off) mod n -> v : mask[v]}``. Aggregating one diagonal is a
circular shift plus an elementwise mask — pure VPU traffic, no gather, no
matmul, and XLA fuses all diagonals into one pass over the node arrays:

    out[v] |= signal[(v + off) mod n] & mask[v]        (flood OR)
    out[v] += signal[(v + off) mod n] * mask[v]        (gossip/SIR sum)

Edges off the kept diagonals (e.g. the rewired ~p fraction of a WS graph)
fall back to the blocked one-hot-matmul representation (ops/blocked.py /
ops/pallas_edge.py), so the expensive per-edge machinery only pays for the
unstructured remainder. Graphs with no diagonal structure (Erdős–Rényi,
Barabási–Albert) degrade gracefully: every edge lands in the remainder and
the hybrid path equals the blocked path.

The reference has no analog — its "aggregation" is one Python ``send`` per
edge per 10 ms poll tick [ref: p2pnetwork/node.py:110-112,
nodeconnection.py:220]; diagonal extraction is a TPU-side representation
choice, chosen because shifts are free on the VPU and gathers are not.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from p2pnetwork_tpu.ops.blocked import BlockedEdges, build_blocked_from_arrays


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HybridEdges:
    """Graph edges split into circular diagonals + unstructured remainder.

    ``masks[d, v]`` is True iff the edge ``(v + offsets[d]) mod n -> v``
    exists. ``remainder`` holds every other edge in blocked form (None when
    the diagonals cover the whole graph). ``n_diag_edges`` is the number
    of edges the diagonals took at build time (static, so ``auto`` can
    read it while tracing; node failures re-mask without changing it).
    ``remainder_kernel`` names the remainder lowering the layout supports:
    ``"pallas"``, or ``"blocked"`` once placed on a GSPMD mesh
    (parallel/auto.py), where a ``pallas_call`` cannot be partitioned.
    """

    masks: jax.Array  # bool[D, n] (D may be 0)
    remainder: Optional[BlockedEdges]
    offsets: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    n_diag_edges: int = dataclasses.field(metadata=dict(static=True))
    remainder_kernel: str = dataclasses.field(
        default="pallas", metadata=dict(static=True))


def build_hybrid(
    graph,
    block: int = 512,
    max_diags: int = 64,
    min_count: Optional[int] = None,
) -> HybridEdges:
    """Extract the dominant circular diagonals of ``graph`` (host-side).

    An offset is kept when it carries at least ``min_count`` edges (default
    ``max(n // 256, 128)`` — roughly where one fused VPU pass over the node
    array beats per-edge gather cost) and at most ``max_diags`` offsets are
    kept (compile-time unroll bound).
    """
    emask = np.asarray(graph.edge_mask)
    return build_hybrid_from_arrays(
        np.asarray(graph.senders)[emask],
        np.asarray(graph.receivers)[emask],
        graph.n_nodes,
        graph.n_nodes_padded,
        block=block,
        max_diags=max_diags,
        min_count=min_count,
    )


def kept_offsets(off: np.ndarray, n: int, max_diags: int = 64,
                 min_count: Optional[int] = None) -> list:
    """The circular offsets ``off`` (one per edge, in ``[0, n)``; 0 for
    edges that are no candidates) keeps as diagonals: those carrying at
    least ``min_count`` edges (default ``max(n // 256, 128)``), by
    descending edge count, at most ``max_diags``. Shared by
    :func:`select_diagonals` and the ring layout (parallel/ringlayout.py),
    so both keep the same offsets in the same order."""
    if min_count is None:
        min_count = max(n // 256, 128)
    if not off.size:
        return []
    counts = np.bincount(off)
    # Filter (self-loops, below-threshold) BEFORE truncating to
    # max_diags — a frequent self-loop offset ranking in the top
    # max_diags must not displace a qualifying real diagonal into the
    # per-edge remainder. Vectorized: `counts` has up to n entries.
    ok = counts >= min_count
    ok[0] = False
    cand = np.flatnonzero(ok)
    return [int(o) for o in cand[np.argsort(counts[cand])[::-1]][:max_diags]]


def select_diagonals(
    senders: np.ndarray,
    receivers: np.ndarray,
    n: int,
    max_diags: int = 64,
    min_count: Optional[int] = None,
):
    """Pick the dominant circular offsets of an edge list (host-side).

    Returns ``(kept_offsets, per_offset_sel, diag_sel)``: the chosen
    offsets (by descending edge count), for each one the indices of its
    covered edges — deduplicated to ONE edge per receiver, so sums count
    every edge instance exactly once — and the overall covered bitmap.
    The single-chip hybrid build uses it; the sharded ring's layout
    (parallel/ringlayout.py) shares its :func:`kept_offsets` and its rule
    of one edge per (offset, receiver), so selection tuning cannot
    silently diverge the two paths. Edges touching padded ids (``>= n``,
    possible when folded-in dynamic links involve spare nodes) are never
    candidates — their offsets-mod-n would alias real diagonals.
    """
    if min_count is None:
        min_count = max(n // 256, 128)
    diag_sel = np.zeros(senders.shape[0], dtype=bool)
    senders = np.asarray(senders)
    receivers = np.asarray(receivers)
    real = np.flatnonzero((senders < n) & (receivers < n))
    kept: list = []
    per_sel: list = []
    if real.size:
        # (s - r) mod n without the modulo: ids are < n so the difference
        # is in (-n, n) and one conditional add folds it into [0, n). The
        # per-element int64 division of `% n` was a measured hotspot of
        # graph build at BASELINE scale.
        d = senders[real].astype(np.int32) - receivers[real].astype(np.int32)
        off = np.where(d < 0, d + np.int32(n), d)
        kept = kept_offsets(off, n, max_diags, min_count)
        # One sort pass gives every diagonal's edge set as a contiguous
        # slice (instead of a full O(E) scan per kept offset), through the
        # native radix kernel: on low-cardinality offset distributions
        # (WS lattices) it matches numpy's comparison sort, and on
        # high-entropy ones (heavily rewired / scale-free graphs) it is
        # ~5x faster at 20M edges (measured; numpy fallback built in).
        from p2pnetwork_tpu import native

        sorted_off, by_off = native.sort_pairs(
            off, np.arange(off.shape[0], dtype=np.int32)
        )
        lo = np.searchsorted(sorted_off, kept)
        hi = np.searchsorted(sorted_off, kept, side="right")
        # Both sorters are STABLE (native LSD radix; numpy fallback uses
        # kind="stable"), so when the input edges arrive receiver-sorted —
        # the documented precondition of both call sites — each offset's
        # slice keeps its receivers non-decreasing and first-per-receiver
        # is one neighbor compare instead of an np.unique sort per offset.
        rsorted = bool(receivers.size == 0 or
                       (receivers[1:] >= receivers[:-1]).all())
        for d, o in enumerate(kept):
            sel = real[by_off[lo[d]:hi[d]]]
            # A mask slot holds ONE edge; duplicate (offset, receiver)
            # pairs beyond the first stay in the remainder.
            rs = receivers[sel]
            if rsorted:
                first = np.empty(rs.shape[0], dtype=bool)
                if rs.shape[0]:
                    first[0] = True
                    np.not_equal(rs[1:], rs[:-1], out=first[1:])
                sel = sel[first]
            else:
                _, first = np.unique(rs, return_index=True)
                sel = sel[first]
            per_sel.append(sel)
            diag_sel[sel] = True
    return kept, per_sel, diag_sel


def build_hybrid_from_arrays(
    senders: np.ndarray,
    receivers: np.ndarray,
    n: int,
    n_pad: int,
    *,
    block: int = 512,
    max_diags: int = 64,
    min_count: Optional[int] = None,
) -> HybridEdges:
    """:func:`build_hybrid` on host edge arrays (``receivers`` sorted
    non-decreasing, active edges only) — lets graph construction build the
    representation before anything is transferred to device."""
    senders = np.asarray(senders)
    receivers = np.asarray(receivers)

    kept, per_sel, diag_sel = select_diagonals(
        senders, receivers, n, max_diags, min_count
    )
    offsets: Tuple[int, ...] = ()
    masks = np.zeros((0, n), dtype=bool)
    if kept:
        offsets = tuple(kept)
        masks = np.zeros((len(kept), n), dtype=bool)
        for d, sel in enumerate(per_sel):
            masks[d, receivers[sel]] = True

    rem_s = senders[~diag_sel].astype(np.int32, copy=False)
    rem_r = receivers[~diag_sel].astype(np.int32, copy=False)
    remainder = None
    if rem_s.size:
        # The remainder inherits receiver-sortedness from the graph's edges.
        remainder = build_blocked_from_arrays(rem_s, rem_r, n_pad, block)

    return HybridEdges(
        masks=jnp.asarray(masks),
        remainder=remainder,
        offsets=offsets,
        n=n,
        n_diag_edges=int(sum(sel.size for sel in per_sel)),
    )


def _diag_or(hybrid: HybridEdges, core: jax.Array) -> jax.Array:
    """OR-aggregate the diagonal edges. ``core`` is bool[n] (unpadded)."""
    acc = jnp.zeros(hybrid.n, dtype=bool)
    for d, off in enumerate(hybrid.offsets):
        acc = acc | (jnp.roll(core, -off) & hybrid.masks[d])
    return acc


def _diag_sum(hybrid: HybridEdges, core: jax.Array) -> jax.Array:
    """Sum-aggregate the diagonal edges. ``core`` is f32[n] (unpadded)."""
    acc = jnp.zeros(hybrid.n, dtype=core.dtype)
    for d, off in enumerate(hybrid.offsets):
        acc = acc + jnp.roll(core, -off) * hybrid.masks[d].astype(core.dtype)
    return acc


def propagate_or_hybrid(
    hybrid: HybridEdges, signal: jax.Array, node_mask: jax.Array,
    kernel: str = "pallas",
) -> jax.Array:
    """Per-node OR over incoming edges: diagonals by shift, rest by kernel.

    ``kernel="pallas"`` (default) runs the remainder through the fused
    Pallas bucket kernel — the single-chip fast path. ``kernel="blocked"``
    uses the pure-jnp one-hot einsum (ops/blocked.py) instead: same
    result, but every op is partitionable, so the GSPMD auto path
    (parallel/auto.py) can shard it — a pallas_call is an opaque custom
    call the partitioner would have to replicate."""
    n_pad = node_mask.shape[0]
    out = jnp.pad(_diag_or(hybrid, signal[: hybrid.n]), (0, n_pad - hybrid.n))
    if hybrid.remainder is not None:
        if kernel == "pallas":
            from p2pnetwork_tpu.ops import pallas_edge as PK

            rem = PK.propagate_or_pallas(hybrid.remainder, signal, node_mask)
        else:
            from p2pnetwork_tpu.ops import blocked as B

            rem = B.propagate_or_blocked(hybrid.remainder, signal, node_mask)
        out = out | rem
    return out & node_mask


def propagate_sum_hybrid(
    hybrid: HybridEdges, signal: jax.Array, node_mask: jax.Array,
    exact: bool = True, kernel: str = "pallas",
) -> jax.Array:
    """Per-node sum over incoming edges: diagonals by shift, rest by kernel.
    ``exact=False``: single-pass MXU for the remainder (see ops/segment.py).
    ``kernel`` as in :func:`propagate_or_hybrid` (the blocked einsum is
    always exact)."""
    n_pad = node_mask.shape[0]
    out = jnp.pad(_diag_sum(hybrid, signal[: hybrid.n]), (0, n_pad - hybrid.n))
    if hybrid.remainder is not None:
        if kernel == "pallas":
            from p2pnetwork_tpu.ops import pallas_edge as PK

            rem = PK.propagate_sum_pallas(hybrid.remainder, signal,
                                          node_mask, exact=exact)
        else:
            from p2pnetwork_tpu.ops import blocked as B

            rem = B.propagate_sum_blocked(hybrid.remainder, signal,
                                          node_mask)
        out = out + rem
    return out * node_mask.astype(out.dtype)

"""Sharded graph propagation: ring ``ppermute`` over a device mesh.

This is the TPU-native replacement for the reference's only scaling story
(one OS thread per peer, O(E) sequential socket sends, SURVEY.md section
2.4). Design (SURVEY.md sections 5 "long-context" and 7 step 4):

- **Node-partitioned state**: node ``v`` lives on shard ``v // block``;
  per-node arrays (seen flags, values, statuses) are sharded on their
  leading axis.
- **Edge-partitioned adjacency, bucketed by source shard**: shard ``d``
  holds every edge whose *receiver* it owns, grouped into ``S`` buckets by
  the *sender*'s shard, ordered by ring distance (bucket ``t`` holds edges
  from shard ``(d - t) mod S``).
- **Ring exchange**: one propagation round runs ``S`` steps. At step ``t``
  each shard holds the frontier block of shard ``(d - t) mod S`` (rotated by
  ``lax.ppermute`` each step — neighbor traffic over ICI, the ring-attention
  communication shape) and applies exactly the edge bucket that consumes it.
  After ``S`` steps every cross-shard edge has been resolved with no
  all-gather and no DCN hot spot; per-round stats come back via ``psum``.

The whole multi-round propagation (scan over rounds, ring scan inside) is
one ``shard_map``-ped, jitted XLA program — zero host round-trips;
:func:`flood_until_coverage` adds the device-side early-exit
``lax.while_loop`` so the north-star run-to-99% measurement runs multi-chip.

**Topology churn is first-class here too** — the reference's identity is
mutating a live network (connects add peers [ref: p2pnetwork/node.py:122],
errors tear connections down [ref: nodeconnection.py:123-126]), and at the
scale this path targets that must work on the sharded representation:

- :func:`with_node_liveness` / :func:`fail_nodes` /
  :func:`random_node_failures` re-mask ``bkt_mask`` / ``node_mask`` /
  ``out_degree`` device-side — same shapes, no recompile, mirroring
  sim/failures.py.
- :func:`with_capacity` reserves a **dynamic edge region**: per-(dst-shard,
  ring-step) unsorted COO slots ``[S, S, K]`` that every ring pass folds in
  alongside the static buckets, so :func:`connect`-ed links carry traffic
  immediately — no re-shard, no recompile (mirroring sim/topology.py).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2pnetwork_tpu.parallel.mesh import DEFAULT_AXIS
from p2pnetwork_tpu.sim import flightrec
from p2pnetwork_tpu.sim.graph import Graph, _round_up
from p2pnetwork_tpu.telemetry import spans
from p2pnetwork_tpu.utils import accum


# ------------------------------------------------------ halo-exchange seam

#: The ring's swappable halo-exchange backends. ``"ppermute"`` is the XLA
#: collective-permute formulation; ``"pallas"`` moves the same block as a
#: ``pltpu.make_async_remote_copy`` issued from a Pallas kernel
#: (ops/pallas_ring.py) — the DMA engine carries the halo while the
#: shard-local bucket compute runs. Both are bit-identical peers
#: (tests/test_ring.py parity sweep); ``"auto"`` routes via
#: parallel/auto.resolve_comm (pallas on TPU, ppermute elsewhere — on CPU
#: the pallas backend runs the interpreter, kept for parity CI).
COMM_BACKENDS = ("ppermute", "pallas")
DEFAULT_COMM = "ppermute"


def _resolve_comm(comm):
    # Non-string comm values are spec OBJECTS (chaos/device.FaultSpec):
    # hashable, already carrying a concrete backend, and built into a
    # comm object by _make_ring_comm — they pass through untouched.
    if not isinstance(comm, str):
        return comm
    from p2pnetwork_tpu.parallel.auto import resolve_comm

    return resolve_comm(comm)


class CommPayloadMismatch(TypeError):
    """A halo payload's shape/dtype diverged from the template its ring
    established on first shift — raised at trace time, where the caller
    can read it, instead of failing deep inside the pallas kernel or the
    XLA collective-permute lowering."""


class _RingComm:
    """One ring's halo-exchange backend: ``shift`` moves a per-shard block
    to the NEXT ring shard (``_ring_perm``), ``shift_back`` to the
    previous (the remask Horner accumulation). The ring bodies issue the
    shift BEFORE the bucket compute that consumes the resident block —
    both only read it — so the transfer's issue point precedes the
    overlap window on either backend (XLA's async collective-permute
    scheduling for ppermute; the in-kernel DMA for pallas).

    ``fused_segment_sum`` is non-None on backends that can carry the halo
    UNDER the blocked one-hot segment sum itself
    (ops/pallas_ring.ring_segment_sum: DMA started at grid step 0, the
    whole MXU edge aggregation in flight, recv-semaphore wait at the
    last step) — the fully fused ring step the MXU bucket path rides.

    Overlap honesty: on the SEGMENT bucket layouts the pallas backend's
    hop is the bare ``ring_shift`` kernel, whose start+wait both live
    inside one opaque pallas_call — no overlap with the XLA bucket
    compute outside it (ppermute, which XLA can split into
    cp-start/cp-done around independent work, can overlap there). The
    in-flight window the issue-before-compute ordering buys is real for
    ppermute everywhere and for pallas on the fused MXU path; a
    split-phase / double-buffered pallas hop for the segment layouts is
    the on-device follow-up (ROADMAP item 1).
    """

    __slots__ = ("backend", "axis_name", "axis_size", "_tpl_fwd",
                 "_tpl_back")

    #: graftquake context seam: _ring_pass threads its scan's step index
    #: through set_context only for comms that ask (chaos/device
    #: FaultyComm); the bare backends stay byte-identical to before.
    wants_step = False

    def __init__(self, backend: str, axis_name: str, axis_size: int):
        if backend not in COMM_BACKENDS:
            raise ValueError(
                f"comm must be one of {COMM_BACKENDS} (or 'auto'), got "
                f"{backend!r}")
        self.backend = backend
        self.axis_name = axis_name
        self.axis_size = axis_size
        self._tpl_fwd = None
        self._tpl_back = None

    @property
    def fuses(self) -> bool:
        """Whether this backend carries the halo UNDER the blocked
        segment sum (``fused_segment_sum`` returns non-None)."""
        return self.backend == "pallas"

    def set_context(self, round=None, step=None) -> None:
        """Fault-injection context hook (round/step of the next hops) —
        a no-op on the bare backends; chaos/device.FaultyComm records
        the tracers for its site keying."""

    def _check_payload(self, x, direction: str) -> None:
        """Validate the payload against the template this ring
        established on its first hop in ``direction`` (forward shifts
        and the reverse Horner hops legitimately carry different
        payloads — liveness masks vs degree counts — so each direction
        owns a template). Shapes are static at trace time, so the check
        is free at runtime and the error surfaces at the call site."""
        sig = (tuple(x.shape), str(x.dtype))
        slot = "_tpl_fwd" if direction == "shift" else "_tpl_back"
        tpl = getattr(self, slot)
        if tpl is None:
            setattr(self, slot, sig)
        elif tpl != sig:
            raise CommPayloadMismatch(
                f"halo payload {sig[0]}/{sig[1]} does not match the "
                f"template {tpl[0]}/{tpl[1]} this ring established on "
                f"its first {direction} — one ring moves one payload "
                "shape per direction (build a separate pass for a "
                "different payload)")

    def shift(self, x):
        self._check_payload(x, "shift")
        if self.backend == "pallas":
            from p2pnetwork_tpu.ops import pallas_ring as PR

            return PR.ring_shift(x, self.axis_name, self.axis_size)
        return jax.lax.ppermute(x, self.axis_name,
                                perm=_ring_perm(self.axis_size))

    def shift_back(self, x):
        self._check_payload(x, "shift_back")
        if self.backend == "pallas":
            from p2pnetwork_tpu.ops import pallas_ring as PR

            return PR.ring_shift(x, self.axis_name, self.axis_size,
                                 reverse=True)
        S = self.axis_size
        return jax.lax.ppermute(x, self.axis_name,
                                perm=[((i + 1) % S, i) for i in range(S)])

    def fused_segment_sum(self, rot, contrib, local_dst, block, exact):
        """``(rot_next, out)`` — the halo hop fused under the blocked
        segment sum, or None when this backend has no fused form (the
        caller then shifts and applies separately)."""
        if self.backend != "pallas":
            return None
        self._check_payload(rot, "shift")
        from p2pnetwork_tpu.ops import pallas_ring as PR

        return PR.ring_segment_sum(rot, contrib, local_dst, self.axis_name,
                                   self.axis_size, block, exact=exact)


def _make_ring_comm(comm, axis_name: str, S: int):
    """Build one ring's comm object: a backend name builds the bare
    :class:`_RingComm`; a spec object (chaos/device.FaultSpec — anything
    with ``make``) builds its wrapper. Specs are hashable, so they ride
    the same lru-cached loop factories the backend strings do."""
    if isinstance(comm, str):
        return _RingComm(comm, axis_name, S)
    return comm.make(axis_name, S)

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """A :class:`Graph` partitioned for an ``S``-shard ring.

    ``bkt_*`` have global shape ``[S, S, E_bkt]`` — leading axis sharded
    (one row per destination shard), second axis the ring step. Local edge
    indices: ``bkt_src`` into the *rotating* frontier block, ``bkt_dst`` into
    the shard's own node block. Within a bucket, edges are sorted by
    destination so segment reductions see sorted ids.

    ``dyn_*`` (optional, via :func:`with_capacity`) is the dynamic edge
    region: same ``[S, S, K]`` bucket layout, but unsorted — runtime
    :func:`connect` fills free slots and every ring pass applies the
    dynamic bucket of the resident step alongside the static one.
    """

    bkt_src: jax.Array  # i32[S, S, E_bkt]
    bkt_dst: jax.Array  # i32[S, S, E_bkt]
    bkt_mask: jax.Array  # bool[S, S, E_bkt]
    node_mask: jax.Array  # bool[S, B]
    out_degree: jax.Array  # i32[S, B]
    in_degree: jax.Array  # i32[S, B]
    n_nodes: int = dataclasses.field(metadata=dict(static=True))
    n_shards: int = dataclasses.field(metadata=dict(static=True))
    block: int = dataclasses.field(metadata=dict(static=True))
    dyn_src: Optional[jax.Array] = None  # i32[S, S, K]
    dyn_dst: Optional[jax.Array] = None  # i32[S, S, K]
    dyn_mask: Optional[jax.Array] = None  # bool[S, S, K]
    # Partner-sampling table for Gossip: GLOBAL neighbor ids per node
    # (present when the source Graph carried a neighbor table). The mask is
    # re-masked by liveness, like the single-device table.
    neighbors: Optional[jax.Array] = None  # i32[S, B, W]
    neighbors_mask: Optional[jax.Array] = None  # bool[S, B, W]
    # MXU bucket layout (shard_graph(..., mxu=True)): each static bucket's
    # edges regrouped by 128-destination block (ops/blocked.py scheme), so
    # the ring pass applies buckets as batched one-hot matmuls instead of
    # segment reductions — XLA's TPU scatter lowering is the ring path's
    # bottleneck. ``mxu_dst`` is the destination index WITHIN its 128-block.
    # Under ``hybrid=True`` these hold only the non-diagonal REMAINDER.
    mxu_src: Optional[jax.Array] = None  # i32[S, S, NB, W]
    mxu_dst: Optional[jax.Array] = None  # i32[S, S, NB, W]
    mxu_mask: Optional[jax.Array] = None  # bool[S, S, NB, W]
    # Ring-decomposed circular diagonals (shard_graph(..., hybrid=True)):
    # a global diagonal ``u = (v + off) mod n`` splits into at most two
    # STATIC (ring_step, local_shift) pieces — identical on every shard —
    # with per-shard validity masks. Applying a piece is one static
    # ``jnp.roll`` of the resident block plus a mask: pure VPU traffic,
    # the sharded mirror of ops/diag.py's gather-free fast path.
    diag_masks: Optional[jax.Array] = None  # bool[S, P, B]
    diag_pieces: Tuple[Tuple[int, int], ...] = dataclasses.field(
        default=(), metadata=dict(static=True)
    )  # ((ring_step, local_shift), ...) per mask row
    #: Destination-block width of the MXU layout (512 cuts Poisson padding
    #: waste vs 128 at the cost of a wider one-hot, like ops/diag.py).
    mxu_block: int = dataclasses.field(default=128,
                                       metadata=dict(static=True))
    # Per-shard sender-CSR view for frontier-sparse traversal
    # (shard_graph(source_csr=True)): for this shard's edges (dst-owned),
    # positions into the FLATTENED bucket arrays (``ring_step * E_bkt +
    # slot``) grouped by GLOBAL sender id — ``csr_pos[d,
    # csr_offsets[d, u] : csr_offsets[d, u + 1]]`` are sender ``u``'s edges
    # into shard ``d``. Gathering bkt_mask/bkt_dst through these positions
    # inherits liveness re-masks and disconnects with no rebuild. Row
    # extents are build-time; out-of-row slots must be masked by the
    # consumer (padding entries stay in bounds but can alias live slots).
    csr_pos: Optional[jax.Array] = None  # i32[S, E_s]
    csr_offsets: Optional[jax.Array] = None  # i32[S, S*block + 1]
    #: Widest per-(sender, dst-shard) build-time row, 0 without the view.
    csr_span: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def n_nodes_padded(self) -> int:
        return self.n_shards * self.block

    @property
    def dyn_capacity(self) -> int:
        return 0 if self.dyn_src is None else self.dyn_src.shape[-1]


def _dyn_or_empty(sg: ShardedGraph):
    """The dynamic bucket triple, or zero-width placeholders (K == 0 makes
    the ring pass skip the dynamic group at trace time — one code path,
    no extra compile-cache key)."""
    if sg.dyn_src is not None:
        return sg.dyn_src, sg.dyn_dst, sg.dyn_mask
    S = sg.n_shards
    return (
        jnp.zeros((S, S, 0), jnp.int32),
        jnp.zeros((S, S, 0), jnp.int32),
        jnp.zeros((S, S, 0), bool),
    )


def _diag_masks_or_empty(sg: ShardedGraph):
    """The diagonal piece masks, or a zero-piece placeholder (``P == 0``
    pairs with the empty static ``diag_pieces`` tuple)."""
    if sg.diag_masks is not None:
        return sg.diag_masks
    return jnp.zeros((sg.n_shards, 0, sg.block), bool)


def _mxu_or_empty(sg: ShardedGraph):
    """The MXU bucket triple, or zero-width placeholders (W == 0 selects
    the segment static group at trace time)."""
    if sg.mxu_src is not None:
        return sg.mxu_src, sg.mxu_dst, sg.mxu_mask
    S = sg.n_shards
    return (
        jnp.zeros((S, S, 1, 0), jnp.int32),
        jnp.zeros((S, S, 1, 0), jnp.int32),
        jnp.zeros((S, S, 1, 0), bool),
    )


def _extract_ring_diagonals(senders, receivers, n, S, block, max_diags,
                            min_count):
    """Select dominant circular diagonals and decompose each into static
    ring pieces (host-side; see ShardedGraph.diag_pieces).

    Returns ``(pieces, masks [S, P, block], diag_sel)`` where ``diag_sel``
    flags the edges covered (the rest go to the bucket remainder). Edges
    whose signed offset wraps the real-node boundary (``v + off_s`` outside
    ``[0, n)``) stay in the remainder — only the uniform no-wrap body of a
    diagonal has the shard-invariant piece structure.
    """
    from p2pnetwork_tpu.ops.diag import select_diagonals

    kept, per_sel, diag_sel = select_diagonals(
        senders, receivers, n, max_diags, min_count
    )
    pieces = []
    mask_rows = []
    for o, sel in zip(kept, per_sel):
        off_s = o if o <= n // 2 else o - n
        v = receivers[sel].astype(np.int64)
        nowrap = (v + off_s >= 0) & (v + off_s < n)
        dropped = sel[~nowrap]
        diag_sel[dropped] = False  # wrap edges ride the remainder
        sel = sel[nowrap]
        if not sel.size:
            continue
        dmask = np.zeros(S * block, dtype=bool)
        dmask[receivers[sel]] = True
        dmask = dmask.reshape(S, block)
        q, r = divmod(off_s, block)  # floor division: r in [0, block)
        j = np.arange(block)
        piece_a = dmask & (j + r < block)[None, :]
        piece_b = dmask & (j + r >= block)[None, :]
        t_a = (-q) % S
        t_b = (-q - 1) % S
        if S == 1 or t_a == t_b:
            if piece_a.any() or piece_b.any():
                pieces.append((t_a, int(r)))  # graftlint: ignore[host-sync-in-loop] -- r is a host int from divmod
                mask_rows.append(dmask)
        else:
            if piece_a.any():
                pieces.append((t_a, int(r)))  # graftlint: ignore[host-sync-in-loop] -- host int
                mask_rows.append(piece_a)
            if piece_b.any():
                pieces.append((t_b, int(r)))  # graftlint: ignore[host-sync-in-loop] -- host int
                mask_rows.append(piece_b)
    if not pieces:
        return (), None, diag_sel
    masks = np.stack(mask_rows, axis=1)  # [S, P, block]
    return tuple(pieces), masks, diag_sel


def shard_graph(graph: Graph, mesh: Mesh, axis_name: str = DEFAULT_AXIS,
                edge_pad_multiple: int = 128, mxu: bool = False,
                hybrid: bool = False, max_diags: int = 64,
                min_count: Optional[int] = None,
                source_csr: bool = False) -> ShardedGraph:
    """Partition ``graph`` for ``mesh`` (host-side; one-off setup).

    Nodes are split into ``S`` contiguous blocks. Every active edge lands in
    bucket ``(dst_shard, ring_step)`` where ``ring_step = (dst_shard -
    src_shard) mod S`` — the step of the ring rotation at which the sender's
    frontier block is resident on the receiver's shard.

    A graph carrying live dynamic edges (sim/topology.py) is sharded
    losslessly: its runtime links are folded into the static buckets (this
    IS the documented consolidation path — re-shard when churn accumulates).

    ``mxu=True`` additionally builds the per-bucket one-hot-matmul layout
    (see ``ShardedGraph.mxu_src``) — on TPU the ring pass then runs on the
    MXU instead of XLA's scatter lowering of segment reductions (~2x per
    chip at 1M nodes; measured in benchmarks/ladder.py).

    ``source_csr=True`` additionally builds the per-shard sender-CSR view
    (``csr_pos``/``csr_offsets``) that the frontier-adaptive coverage loop
    gathers small frontiers through (see :func:`flood_until_coverage`'s
    ``adaptive_k``).
    """
    S = mesh.shape[axis_name]
    emask = np.asarray(graph.edge_mask)
    senders = np.asarray(graph.senders)[emask]
    receivers = np.asarray(graph.receivers)[emask]
    if graph.dyn_mask is not None:
        dmask = np.asarray(graph.dyn_mask)
        senders = np.concatenate([senders, np.asarray(graph.dyn_senders)[dmask]])
        receivers = np.concatenate([receivers, np.asarray(graph.dyn_receivers)[dmask]])

    block = _round_up(graph.n_nodes_padded, S) // S

    # Diagonal extraction must precede bucketing (the selection indexes the
    # unsorted edge arrays); the covered edges leave the APPLIED remainder
    # but stay in the bkt_* truth arrays below (degrees, probe, remask).
    diag_pieces: Tuple[Tuple[int, int], ...] = ()
    diag_masks = None
    if hybrid:
        diag_pieces, diag_masks, diag_sel = _extract_ring_diagonals(
            senders, receivers, graph.n_nodes, S, block, max_diags, min_count
        )
        mxu = True  # the remainder rides the MXU buckets
    else:
        diag_sel = np.zeros(senders.shape[0], dtype=bool)

    def _bucketize(s_arr, r_arr):
        """Sort edges by (bucket, local dst); return sorted arrays, bucket
        offsets (bucket = dst_shard * S + ring_step), sorted bucket ids,
        and the sort order."""
        flat = (r_arr // block) * S + ((r_arr // block) - (s_arr // block)) % S
        order = np.lexsort((r_arr, flat))
        s_arr, r_arr, flat = s_arr[order], r_arr[order], flat[order]
        offs = np.zeros(S * S + 1, dtype=np.int64)
        np.cumsum(np.bincount(flat, minlength=S * S), out=offs[1:])
        return s_arr, r_arr, offs, flat, order

    senders_b, receivers_b, offsets, flat_b, order_b = _bucketize(
        senders, receivers
    )
    e_bkt = _round_up(
        max(int(np.diff(offsets).max()), 1), edge_pad_multiple
    )
    bkt_src = np.zeros((S, S, e_bkt), dtype=np.int32)
    # Pad destinations with block-1 so each bucket stays dst-sorted — the
    # segment reductions in the ring body promise indices_are_sorted=True.
    bkt_dst = np.full((S, S, e_bkt), block - 1, dtype=np.int32)
    bkt_mask = np.zeros((S, S, e_bkt), dtype=bool)
    for d in range(S):
        for t in range(S):
            b = d * S + t
            lo, hi = offsets[b], offsets[b + 1]
            cnt = hi - lo
            bkt_src[d, t, :cnt] = senders_b[lo:hi] % block
            bkt_dst[d, t, :cnt] = receivers_b[lo:hi] % block
            bkt_mask[d, t, :cnt] = True

    mxu_src = mxu_dst = mxu_mask = None
    mxu_block = 512  # ops/diag.py's remainder block: less padding waste
    if mxu:
        from p2pnetwork_tpu.ops.blocked import build_blocked_arrays_np

        # A subset of the already-bucket-sorted arrays stays sorted — no
        # second O(E log E) lexsort for the remainder.
        ks = ~diag_sel[order_b]
        rem_s, rem_r = senders_b[ks], receivers_b[ks]
        rem_offs = np.zeros(S * S + 1, dtype=np.int64)
        np.cumsum(np.bincount(flat_b[ks], minlength=S * S), out=rem_offs[1:])
        per_bucket = []
        for d in range(S):
            for t in range(S):
                b = d * S + t
                lo_, hi_ = rem_offs[b], rem_offs[b + 1]
                per_bucket.append(build_blocked_arrays_np(
                    (rem_s[lo_:hi_] % block).astype(np.int32),
                    (rem_r[lo_:hi_] % block).astype(np.int32),
                    block, mxu_block,
                ))
        nb = max(bs.shape[0] for bs, _, _ in per_bucket)
        w = max(bs.shape[1] for bs, _, _ in per_bucket)
        mxu_src = np.zeros((S, S, nb, w), np.int32)
        mxu_dst = np.zeros((S, S, nb, w), np.int32)
        mxu_mask = np.zeros((S, S, nb, w), bool)
        for d in range(S):
            for t in range(S):
                bs, bd, bm = per_bucket[d * S + t]
                r, c = bs.shape
                mxu_src[d, t, :r, :c] = bs
                mxu_dst[d, t, :r, :c] = bd
                mxu_mask[d, t, :r, :c] = bm

    csr_pos = csr_offsets = None
    csr_span = 0
    if source_csr:
        from p2pnetwork_tpu import native

        n_g = S * block
        rows_pos = []
        counts = np.zeros((S, n_g), dtype=np.int64)
        for d in range(S):
            # This shard's live bucket slots, flattened (t * e_bkt + slot),
            # keyed by the GLOBAL sender id reconstructed from the ring
            # step: step t holds senders of shard (d - t) mod S.
            t_idx, slot_idx = np.nonzero(bkt_mask[d])
            g_send = (
                ((d - t_idx) % S) * block + bkt_src[d, t_idx, slot_idx]
            ).astype(np.int32)
            pos = (t_idx * e_bkt + slot_idx).astype(np.int32)
            _, pos_sorted = native.sort_pairs(g_send, pos)
            rows_pos.append(pos_sorted)
            counts[d] = np.bincount(g_send, minlength=n_g)
        e_s = _round_up(max(max(p.size for p in rows_pos), 1),
                        edge_pad_multiple)
        csr_pos = np.zeros((S, e_s), dtype=np.int32)
        for d in range(S):
            csr_pos[d, : rows_pos[d].size] = rows_pos[d]
        csr_offsets = np.zeros((S, n_g + 1), dtype=np.int32)
        np.cumsum(counts, axis=1, out=csr_offsets[:, 1:])
        csr_span = int(counts.max()) if counts.size else 0

    pad_n = S * block - graph.n_nodes_padded
    node_mask = np.pad(np.asarray(graph.node_mask), (0, pad_n))
    out_degree = np.pad(np.asarray(graph.out_degree), (0, pad_n))
    in_degree = np.pad(np.asarray(graph.in_degree), (0, pad_n))
    neighbors = neighbors_mask = None
    if graph.neighbors is not None:
        neighbors = np.pad(np.asarray(graph.neighbors), ((0, pad_n), (0, 0)))
        neighbors_mask = np.pad(
            np.asarray(graph.neighbor_mask), ((0, pad_n), (0, 0))
        )

    shard = NamedSharding(mesh, P(axis_name))
    dev = lambda x: jax.device_put(x, shard)  # noqa: E731
    return ShardedGraph(
        bkt_src=dev(bkt_src),
        bkt_dst=dev(bkt_dst),
        bkt_mask=dev(bkt_mask),
        node_mask=dev(node_mask.reshape(S, block)),
        out_degree=dev(out_degree.reshape(S, block).astype(np.int32)),
        in_degree=dev(in_degree.reshape(S, block).astype(np.int32)),
        n_nodes=graph.n_nodes,
        n_shards=S,
        block=block,
        neighbors=None if neighbors is None else dev(
            neighbors.reshape(S, block, -1)
        ),
        neighbors_mask=None if neighbors_mask is None else dev(
            neighbors_mask.reshape(S, block, -1)
        ),
        mxu_src=None if mxu_src is None else dev(mxu_src),
        mxu_dst=None if mxu_dst is None else dev(mxu_dst),
        mxu_mask=None if mxu_mask is None else dev(mxu_mask),
        diag_masks=None if diag_masks is None else dev(diag_masks),
        diag_pieces=diag_pieces,
        mxu_block=mxu_block,
        csr_pos=None if csr_pos is None else dev(csr_pos),
        csr_offsets=None if csr_offsets is None else dev(csr_offsets),
        csr_span=csr_span,
    )


# --------------------------------------------------------------- churn ops


def with_capacity(sg: ShardedGraph, extra_edges: int) -> ShardedGraph:
    """Reserve ``extra_edges`` dynamic slots per (dst-shard, ring-step)
    bucket — any distribution of that many directed links is guaranteed to
    fit whichever bucket it lands in. Host-side, one-off; growing an
    existing region preserves every runtime link."""
    K = _round_up(max(extra_edges, 1), 8)
    S = sg.n_shards
    # Commit the region to the mesh up front — uncommitted/single-device
    # arrays mixed with sharded operands are rejected under shard_map.
    shard = NamedSharding(_mesh_of(sg), P(_mesh_of(sg).axis_names[0]))
    if sg.dyn_src is not None:
        grow = K
        pad = lambda x: jax.device_put(  # noqa: E731
            jnp.pad(x, ((0, 0), (0, 0), (0, grow))), shard)
        return dataclasses.replace(
            sg,
            dyn_src=pad(sg.dyn_src),
            dyn_dst=pad(sg.dyn_dst),
            dyn_mask=pad(sg.dyn_mask),
        )
    return dataclasses.replace(
        sg,
        dyn_src=jax.device_put(jnp.zeros((S, S, K), jnp.int32), shard),
        dyn_dst=jax.device_put(jnp.zeros((S, S, K), jnp.int32), shard),
        dyn_mask=jax.device_put(jnp.zeros((S, S, K), bool), shard),
    )


def _mesh_of(sg: ShardedGraph) -> Mesh:
    """The mesh the graph's arrays live on (set by shard_graph's
    device_put; churn ops run shard_map programs over it)."""
    mesh = sg.bkt_src.sharding.mesh
    if isinstance(mesh, jax.sharding.AbstractMesh):  # pragma: no cover
        raise ValueError("ShardedGraph arrays carry an abstract mesh; "
                         "device_put them on a concrete mesh first")
    return mesh


def _remask_body(axis_name, S, block, pieces, mxu_block, comm,
                 bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                 mxu_src, mxu_dst, mxu_mask, diag_masks,
                 neighbors, neighbors_mask, node_mask, alive):
    """Per-shard liveness re-mask: an edge survives iff both endpoints do.

    Runs under shard_map. The source block of bucket ``t`` is the block
    resident after ``t`` ring rotations, so the per-step source liveness is
    collected with the same halo-exchange ring the propagation uses
    (``comm`` seam — ppermute or the Pallas DMA kernel). Out-degree
    counts are computed per bucket on the receiver's shard, then carried
    back to the sender's shard with a reverse-rotating Horner accumulation:
    ``out[s] = sum_t cnt[(s+t) mod S, t]``.
    """
    comm_obj = _make_ring_comm(comm, axis_name, S)
    nm = node_mask[0] & alive[0]  # [B]

    # masks_by_t[t] = liveness of the block resident at ring step t
    # (= shard (d - t) mod S's block, exactly what bkt_src[t] indexes).
    def collect(rot, _):
        return comm_obj.shift(rot), rot

    _, masks_by_t = jax.lax.scan(collect, nm, None, length=S)

    def remask_group(src, dst, mask):  # [S, W] each
        if src.shape[-1] == 0:
            zero = jnp.zeros((S, block), jnp.int32)
            return mask, zero, zero[0]
        src_alive = jnp.take_along_axis(masks_by_t, src, axis=1)
        dst_alive = nm[dst]
        mask = mask & src_alive & dst_alive
        cnt = jax.vmap(
            lambda m, s: jax.ops.segment_sum(
                m.astype(jnp.int32), s, num_segments=block
            )
        )(mask, src)  # [S_t, B] — counts for the sender block of each step
        # In-degrees are local: every bucket's receivers are this shard's.
        cnt_in = jax.vmap(
            lambda m, r: jax.ops.segment_sum(
                m.astype(jnp.int32), r, num_segments=block
            )
        )(mask, dst).sum(axis=0)  # [B]
        return mask, cnt, cnt_in

    bkt_mask_b, cnt_s, in_s = remask_group(bkt_src[0], bkt_dst[0], bkt_mask[0])
    dyn_mask_b, cnt_d, in_d = remask_group(dyn_src[0], dyn_dst[0], dyn_mask[0])
    cnt = cnt_s + cnt_d  # [S_t, B]
    in_degree = in_s + in_d  # [B]

    # Horner: acc <- cnt_t + rot_back(acc), t = S-1 .. 0, where rot_back
    # moves each block one shard backward along the ring.
    def horner(acc, cnt_t):
        return cnt_t + comm_obj.shift_back(acc), None

    if S > 1:
        out_degree, _ = jax.lax.scan(horner, cnt[S - 1], cnt[: S - 1],
                                     reverse=True)
    else:
        out_degree = cnt[0]

    # MXU bucket re-mask (mirrors sim/failures._remask_blocked): sources by
    # ring-step liveness, destinations by the local mxu_block layout.
    if mxu_src.shape[-1] > 0:
        _, nb, w = mxu_src.shape[1:]
        src_alive = jnp.take_along_axis(
            masks_by_t, mxu_src[0].reshape(S, nb * w), axis=1
        ).reshape(S, nb, w)
        gd = jnp.minimum(
            jnp.arange(nb, dtype=jnp.int32)[None, :, None] * mxu_block
            + mxu_dst[0],
            block - 1,
        )
        mxu_mask_b = mxu_mask[0] & src_alive & nm[gd]
    else:
        mxu_mask_b = mxu_mask[0]

    # Diagonal-piece re-mask: a piece edge u -> v needs v alive (nm) and
    # u alive — u sits at local (j + r) % B of the block resident at the
    # piece's ring step, i.e. the same static roll the apply uses.
    if pieces:
        dm = diag_masks[0]
        rows = [dm[pi] & nm & jnp.roll(masks_by_t[tp], -r)
                for pi, (tp, r) in enumerate(pieces)]
        diag_masks_b = jnp.stack(rows, axis=0)
    else:
        diag_masks_b = diag_masks[0]

    # Partner-table re-mask (mirrors sim/failures.py's
    # `neighbor_mask & node_mask[:, None] & node_mask[neighbors]`): the
    # neighbor ids are global, so their liveness comes from the collected
    # ring blocks — neighbor p lives on shard p // block, resident at ring
    # step (my - p // block) mod S.
    my = jax.lax.axis_index(axis_name)
    if neighbors.shape[-1] > 0:
        p_shard = neighbors[0] // block  # [B, W]
        p_local = neighbors[0] % block
        nbr_alive = masks_by_t[(my - p_shard) % S, p_local]
        nbr_mask = neighbors_mask[0] & nm[:, None] & nbr_alive
    else:
        nbr_mask = neighbors_mask[0]
    return (bkt_mask_b[None], dyn_mask_b[None], mxu_mask_b[None],
            diag_masks_b[None], nm[None], out_degree[None], in_degree[None],
            nbr_mask[None])


@functools.lru_cache(maxsize=64)
def _remask_fn(mesh: Mesh, axis_name: str, S: int, block: int, pieces=(),
               mxu_block: int = 128, comm: str = DEFAULT_COMM):
    body = functools.partial(_remask_body, axis_name, S, block, pieces,
                             mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False under the pallas backend: see the note on the
    # ring-body factories (the DMA kernel's lowering and vma typing).
    kw = {} if comm == "ppermute" else {"check_vma": False}
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * 14,
        out_specs=(spec,) * 8,
        **kw,
    )
    return jax.jit(fn)


def with_node_liveness(sg: ShardedGraph, alive: jax.Array, *,
                       comm: str = DEFAULT_COMM) -> ShardedGraph:
    """Apply a liveness mask (False = failed) to the sharded graph —
    the sharded mirror of sim/failures.with_node_liveness. ``alive`` is
    bool, global ``[S*block]`` or already-blocked ``[S, block]``.

    Entirely device-side, shapes unchanged: the compiled flood/SIR/coverage
    programs are NOT recompiled, the next round simply routes around the
    damage — same no-recompile property as the single-device path.
    ``comm`` selects the halo-exchange backend of the liveness-collection
    ring (see :data:`COMM_BACKENDS`); the re-masked graph is backend-
    independent, so churn and propagation may mix backends freely.
    """
    alive = jnp.asarray(alive).reshape(sg.n_shards, sg.block)
    mesh = _mesh_of(sg)
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    if sg.neighbors is not None:
        neighbors, neighbors_mask = sg.neighbors, sg.neighbors_mask
    else:
        neighbors = jnp.zeros((sg.n_shards, sg.block, 0), jnp.int32)
        neighbors_mask = jnp.zeros((sg.n_shards, sg.block, 0), bool)
    fn = _remask_fn(mesh, mesh.axis_names[0], sg.n_shards, sg.block,
                    sg.diag_pieces, sg.mxu_block, _resolve_comm(comm))
    (bkt_mask, dyn_mask, mxu_mask, diag_masks, node_mask, out_degree,
     in_degree, nbr_mask) = fn(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask,
        dyn_src, dyn_dst, dyn_mask, mxu_src, mxu_dst, mxu_mask,
        _diag_masks_or_empty(sg),
        neighbors, neighbors_mask, sg.node_mask, alive,
    )
    return dataclasses.replace(
        sg,
        bkt_mask=bkt_mask,
        node_mask=node_mask,
        out_degree=out_degree,
        in_degree=in_degree,
        dyn_mask=dyn_mask if sg.dyn_mask is not None else None,
        neighbors_mask=nbr_mask if sg.neighbors_mask is not None else None,
        mxu_mask=mxu_mask if sg.mxu_mask is not None else None,
        diag_masks=diag_masks if sg.diag_masks is not None else None,
    )


def fail_nodes(sg: ShardedGraph, node_ids) -> ShardedGraph:
    """Fail-stop the given global node ids (sharded mirror of
    sim/failures.fail_nodes)."""
    ids = np.asarray(node_ids, dtype=np.int64)
    if ids.size and (ids.min() < 0 or ids.max() >= sg.n_nodes_padded):
        raise ValueError(f"node id out of range [0, {sg.n_nodes_padded})")
    alive = jnp.ones(sg.n_nodes_padded, bool).at[
        jnp.asarray(ids, dtype=jnp.int32)].set(False)
    return with_node_liveness(sg, alive)


def random_node_failures(sg: ShardedGraph, key: jax.Array,
                         frac: float) -> ShardedGraph:
    """Fail each live node independently with probability ``frac``. Draws
    over the full padded population, so when ``S*block == n_pad`` the
    failure set is bit-identical to sim/failures.random_node_failures with
    the same key."""
    alive = ~(
        jax.random.bernoulli(key, frac, (sg.n_nodes_padded,)).reshape(
            sg.n_shards, sg.block
        )
        & sg.node_mask
    )
    return with_node_liveness(sg, alive)


def _pad_queries(S, *arrays, multiple=16):
    """Pad query vectors to a length multiple (fewer retraces across call
    sites). Padding rows get dst shard ``S`` — matching no shard, they are
    inert in every probe/scatter body."""
    q = arrays[0].size
    q_pad = _round_up(max(q, 1), multiple)
    out = []
    for i, a in enumerate(arrays):
        fill = S if i == 0 else 0  # first array is the dst-shard vector
        out.append(np.pad(a, (0, q_pad - q), constant_values=fill))
    return out


def _member_body(axis_name, S,
                 bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                 d, t, sl, rl):
    """Replicated queries in, replicated answers out: each shard probes the
    buckets it owns (d == my shard); a psum ORs the per-shard verdicts."""
    my = jax.lax.axis_index(axis_name)
    mine = d == my

    def probe(src, dst, m):  # [S_t, W] locals
        if src.shape[-1] == 0:
            return jnp.zeros(d.shape, bool)
        rows_s = src[0][t]  # [Q, W] — t is a local (unsharded) axis
        rows_d = dst[0][t]
        rows_m = m[0][t]
        return ((rows_s == sl[:, None]) & (rows_d == rl[:, None]) & rows_m
                ).any(axis=1)

    hit = (probe(bkt_src, bkt_dst, bkt_mask)
           | probe(dyn_src, dyn_dst, dyn_mask)) & mine
    return jax.lax.psum(hit.astype(jnp.int32), axis_name) > 0


@functools.lru_cache(maxsize=64)
def _member_fn(mesh: Mesh, axis_name: str, S: int):
    body = functools.partial(_member_body, axis_name, S)
    spec = P(axis_name)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * 6 + (P(),) * 4,
        out_specs=P(),
    )
    return jax.jit(fn)


def _scatter_body(axis_name, S, block,
                  dyn_src, dyn_dst, dyn_mask, out_degree, in_degree,
                  d, t, k, sl, rl):
    """Write new dynamic edges into the owning shard's bucket slots and bump
    the sender shard's out-degrees / receiver shard's in-degrees. Non-owned
    queries route to an out-of-bounds row and are dropped by the scatter."""
    my = jax.lax.axis_index(axis_name)
    mine = d == my
    tt = jnp.where(mine, t, S)  # OOB row -> dropped
    ds = dyn_src[0].at[tt, k].set(sl, mode="drop")
    dd = dyn_dst[0].at[tt, k].set(rl, mode="drop")
    dm = dyn_mask[0].at[tt, k].set(True, mode="drop")
    sender_mine = ((d - t) % S == my) & (d < S)
    bb = jnp.where(sender_mine, sl, block)  # OOB -> dropped
    od = out_degree[0].at[bb].add(1, mode="drop")
    ii = jnp.where(mine, rl, block)
    ideg = in_degree[0].at[ii].add(1, mode="drop")
    return ds[None], dd[None], dm[None], od[None], ideg[None]


@functools.lru_cache(maxsize=64)
def _scatter_fn(mesh: Mesh, axis_name: str, S: int, block: int):
    body = functools.partial(_scatter_body, axis_name, S, block)
    spec = P(axis_name)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * 5 + (P(),) * 5,
        out_specs=(spec,) * 5,
    )
    return jax.jit(fn)


def _host_fetch(x) -> np.ndarray:
    """Host copy of a possibly multi-process-sharded array.

    ``np.asarray`` on an array whose shards live on OTHER processes is an
    error by design; the cross-process case all-gathers first (every
    process calls this at the same program point — connect's host-side
    orchestration is SPMD like everything else)."""
    if x.is_fully_addressable:
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def connect(sg: ShardedGraph, senders, receivers, *,
            undirected: bool = True) -> ShardedGraph:
    """Add links between global node ids at runtime (sharded mirror of
    sim/topology.connect; the population analog of ``connect_with_node``
    [ref: p2pnetwork/node.py:122]).

    Each new directed edge lands in its (dst-shard, ring-step) dynamic
    bucket; already-existing pairs (static or dynamic) are dropped, like
    the reference's duplicate-connect no-op [ref: node.py:136-139]. The
    existence probe and the slot writes are shard_map programs (each shard
    handles the queries it owns); only slot allocation is orchestrated
    host-side over the small ``[S, S, K]`` occupancy mask — connect is an
    event, not the hot path.
    """
    if sg.dyn_src is None:
        raise ValueError(
            "no dynamic edge capacity: reserve slots with "
            "sharded.with_capacity(sg, extra_edges=...) first"
        )
    S, B, K = sg.n_shards, sg.block, sg.dyn_capacity
    mesh = _mesh_of(sg)
    axis = mesh.axis_names[0]
    s = np.asarray(senders, np.int64).reshape(-1)
    r = np.asarray(receivers, np.int64).reshape(-1)
    if s.size and (min(s.min(), r.min()) < 0
                   or max(s.max(), r.max()) >= sg.n_nodes_padded):
        raise ValueError(f"node id out of range [0, {sg.n_nodes_padded})")
    if undirected:
        s, r = np.concatenate([s, r]), np.concatenate([r, s])

    # Drop duplicates within the batch (first occurrence wins).
    _, first = np.unique(s * np.int64(sg.n_nodes_padded) + r, return_index=True)
    keep = np.zeros(s.size, bool)
    keep[first] = True

    # Dead endpoints reject the link (sim/topology.connect parity — the
    # reference's connect to a crashed peer fails [ref: node.py:173-176]).
    alive = _host_fetch(sg.node_mask).reshape(-1)
    keep &= alive[s] & alive[r]

    # Drop pairs that already exist — each shard probes the exact bucket
    # the pair would occupy (O(Q * E_bkt) on its own rows, not O(Q * E)).
    d = (r // B).astype(np.int32)
    t = ((d - s // B) % S).astype(np.int32)
    sl = (s % B).astype(np.int32)
    rl = (r % B).astype(np.int32)
    dp, tp, slp, rlp = _pad_queries(S, d, t, sl, rl)
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    exists = np.asarray(_member_fn(mesh, axis, S)(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        jnp.asarray(dp), jnp.asarray(tp), jnp.asarray(slp), jnp.asarray(rlp),
    ))[: d.size]
    keep &= ~exists
    if not keep.any():
        return sg

    d, t, sl, rl = d[keep], t[keep], sl[keep], rl[keep]
    # Free-slot allocation per bucket (host-side; dyn_mask is S*S*K bools).
    dmask = _host_fetch(sg.dyn_mask).copy()  # mutable copy
    slots = np.empty(d.size, np.int32)
    for i in range(d.size):
        free = np.nonzero(~dmask[d[i], t[i]])[0]
        if not free.size:
            raise ValueError(
                f"dynamic bucket ({d[i]}, {t[i]}) full ({K} slots); "
                f"re-shard via shard_graph (consolidation) or reserve more "
                f"via with_capacity"
            )
        slots[i] = free[0]
        dmask[d[i], t[i], free[0]] = True

    dp, tp, kp, slp, rlp = _pad_queries(S, d, t, slots, sl, rl)
    dyn_src, dyn_dst, dyn_mask, out_degree, in_degree = _scatter_fn(
        mesh, axis, S, B
    )(
        sg.dyn_src, sg.dyn_dst, sg.dyn_mask, sg.out_degree, sg.in_degree,
        jnp.asarray(dp), jnp.asarray(tp), jnp.asarray(kp),
        jnp.asarray(slp), jnp.asarray(rlp),
    )
    return dataclasses.replace(
        sg, dyn_src=dyn_src, dyn_dst=dyn_dst, dyn_mask=dyn_mask,
        out_degree=out_degree, in_degree=in_degree,
    )


def _unscatter_body(axis_name, S, block,
                    dyn_src, dyn_dst, dyn_mask, out_degree, in_degree,
                    d, t, sl, rl):
    """Clear matching dynamic edges on the owning shard; psum the removal
    verdicts so the sender's shard can decrement its out-degrees."""
    my = jax.lax.axis_index(axis_name)
    mine = d == my
    rows_s = dyn_src[0][t]  # [Q, K]
    rows_d = dyn_dst[0][t]
    rows_m = dyn_mask[0][t]
    hit = (rows_s == sl[:, None]) & (rows_d == rl[:, None]) & rows_m
    hit = hit & mine[:, None]
    tt = jnp.where(mine, t, S)
    dm = dyn_mask[0].at[tt].min(~hit, mode="drop")
    removed = jax.lax.psum(hit.any(axis=1).astype(jnp.int32), axis_name)
    sender_mine = ((d - t) % S == my) & (d < S)
    bb = jnp.where(sender_mine, sl, block)
    od = out_degree[0].at[bb].add(-removed, mode="drop")
    ii = jnp.where(mine, rl, block)
    ideg = in_degree[0].at[ii].add(-removed, mode="drop")
    return dm[None], od[None], ideg[None]


@functools.lru_cache(maxsize=64)
def _unscatter_fn(mesh: Mesh, axis_name: str, S: int, block: int):
    body = functools.partial(_unscatter_body, axis_name, S, block)
    spec = P(axis_name)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * 5 + (P(),) * 4,
        out_specs=(spec,) * 3,
    )
    return jax.jit(fn)


def disconnect(sg: ShardedGraph, senders, receivers, *,
               undirected: bool = True) -> ShardedGraph:
    """Remove runtime links (matched by endpoint pair; static edges are
    removed with :func:`fail_nodes` / a re-shard)."""
    if sg.dyn_src is None:
        raise ValueError("graph has no dynamic edge region")
    S, B = sg.n_shards, sg.block
    mesh = _mesh_of(sg)
    s = np.asarray(senders, np.int64).reshape(-1)
    r = np.asarray(receivers, np.int64).reshape(-1)
    if undirected:
        s, r = np.concatenate([s, r]), np.concatenate([r, s])
    # Dedup queries: a pair listed twice must decrement degrees once.
    _, first = np.unique(s * np.int64(sg.n_nodes_padded) + r, return_index=True)
    s, r = s[np.sort(first)], r[np.sort(first)]
    d = (r // B).astype(np.int32)
    t = ((d - s // B) % S).astype(np.int32)
    sl = (s % B).astype(np.int32)
    rl = (r % B).astype(np.int32)
    dp, tp, slp, rlp = _pad_queries(S, d, t, sl, rl)
    dyn_mask, out_degree, in_degree = _unscatter_fn(
        mesh, mesh.axis_names[0], S, B
    )(
        sg.dyn_src, sg.dyn_dst, sg.dyn_mask, sg.out_degree, sg.in_degree,
        jnp.asarray(dp), jnp.asarray(tp), jnp.asarray(slp), jnp.asarray(rlp),
    )
    return dataclasses.replace(sg, dyn_mask=dyn_mask, out_degree=out_degree,
                               in_degree=in_degree)


def init_state(sg: ShardedGraph, protocol, key: jax.Array):
    """The sharded initial state for a protocol — what ``protocol.init``
    produces on the engine path, laid out ``[S, block]``. Flood ->
    ``(seen, frontier)``; SIR -> ``status``; Gossip -> ``values``;
    HopDistance -> ``(dist, frontier, round)``; PageRank -> ``ranks``;
    PushSum -> ``(s, w)``."""
    from p2pnetwork_tpu.models.flood import Flood
    from p2pnetwork_tpu.models.gossip import Gossip
    from p2pnetwork_tpu.models.hopdist import HopDistance
    from p2pnetwork_tpu.models.pagerank import PageRank
    from p2pnetwork_tpu.models.pushsum import PushSum
    from p2pnetwork_tpu.models.sir import SIR

    S, block = sg.n_shards, sg.block
    if isinstance(protocol, Flood):
        seed = _flood_seed(sg, protocol.source)
        return (seed, seed)
    if isinstance(protocol, SIR):
        source = protocol.source
        return (
            jnp.zeros((S, block), dtype=jnp.int32)
            .at[source // block, source % block].set(1)
        ) * sg.node_mask
    if isinstance(protocol, Gossip):
        vals = jax.random.normal(key, (sg.n_nodes_padded,), dtype=jnp.float32)
        return vals.reshape(S, block) * sg.node_mask
    if isinstance(protocol, HopDistance):
        seed = _flood_seed(sg, protocol.source)
        dist = jnp.where(seed, 0, -1).astype(jnp.int32)
        return (dist, seed, jnp.int32(0))
    if isinstance(protocol, PageRank):
        mask_f = sg.node_mask.astype(jnp.float32)
        return mask_f / jnp.maximum(jnp.sum(mask_f), 1.0)
    if isinstance(protocol, PushSum):
        vals = jax.random.normal(key, (sg.n_nodes_padded,), dtype=jnp.float32)
        mask_f = sg.node_mask.astype(jnp.float32)
        return (vals.reshape(S, block) * mask_f, mask_f)
    raise ValueError(
        f"the sharded path implements Flood, SIR, Gossip, HopDistance, "
        f"PageRank and PushSum; got {type(protocol).__name__} — run it on "
        f"the single-device engine, or write its round body around "
        f"sharded.propagate"
    )


def topology_state(sg: ShardedGraph) -> dict:
    """The sharded graph's runtime-mutable leaves as a checkpointable
    pytree — the multi-chip mirror of sim/checkpoint.topology_state. Leaves
    keep their shardings, so ``sim.checkpoint.save_orbax`` writes each
    process's shards in parallel and a restore lands them back on the mesh.
    """
    ts = {
        "bkt_mask": sg.bkt_mask,
        "node_mask": sg.node_mask,
        "out_degree": sg.out_degree,
        "in_degree": sg.in_degree,
    }
    if sg.dyn_src is not None:
        ts["dyn_src"] = sg.dyn_src
        ts["dyn_dst"] = sg.dyn_dst
        ts["dyn_mask"] = sg.dyn_mask
    if sg.neighbors_mask is not None:
        ts["neighbors_mask"] = sg.neighbors_mask
    if sg.mxu_mask is not None:
        ts["mxu_mask"] = sg.mxu_mask
    if sg.diag_masks is not None:
        ts["diag_masks"] = sg.diag_masks
    return ts


def apply_topology_state(sg: ShardedGraph, ts: dict) -> ShardedGraph:
    """Re-apply a :func:`topology_state` onto a structurally-equal sharded
    graph (same shard count, capacity, and neighbor table presence)."""
    expected = set(topology_state(sg).keys())
    if expected != set(ts.keys()):
        raise ValueError(
            f"sharded topology state keys mismatch: checkpoint has "
            f"{sorted(ts.keys())}, graph expects {sorted(expected)} — shard "
            f"the same construction (capacity, neighbor table) it came from"
        )
    for name in expected:
        saved, cur = np.shape(ts[name]), tuple(getattr(sg, name).shape)
        if tuple(saved) != cur:
            raise ValueError(
                f"sharded topology state mismatch for {name!r}: saved shape "
                f"{tuple(saved)}, graph has {cur}"
            )
    # Place every restored leaf on the graph's mesh explicitly: a leaf that
    # came back host-side (npz) or committed to one device would otherwise
    # be rejected when mixed with sharded operands under shard_map.
    shard = NamedSharding(_mesh_of(sg), P(_mesh_of(sg).axis_names[0]))
    kw = {k: jax.device_put(jnp.asarray(v), shard) for k, v in ts.items()}
    return dataclasses.replace(sg, **kw)


# --------------------------------------------------------------- ring pass


def _ring_perm(S: int):
    """Send block to the next shard: after t applications, shard d holds the
    block originally on shard (d - t) mod S."""
    return [(i, (i + 1) % S) for i in range(S)]




def _ring_pass_unrolled(axis_name, S, rot, groups, diag, acc0, combine,
                        comm: _RingComm):
    """Unrolled ring rotation (used when diagonal pieces are present: each
    piece applies at a STATIC step with a STATIC shift, which a lax.scan
    body cannot express). S is small; the unroll is the same structure the
    single-chip hybrid uses for its diagonal stack. The halo hop is issued
    through the comm seam BEFORE the step's applies — transfer and
    shard-local compute both only read the resident block, so the hop is
    in flight across the whole step on overlap-capable backends."""
    pieces, masks, apply_diag = diag
    wants_step = bool(getattr(comm, "wants_step", False))
    acc = acc0
    for t in range(S):
        if wants_step and t < S - 1:
            comm.set_context(step=t)
        rot_next = comm.shift(rot) if t < S - 1 else rot
        for fn, *arrs in groups:
            acc = combine(acc, fn(rot, *(a[t] for a in arrs)))
        for pi, (tp, r) in enumerate(pieces):
            if tp == t:
                acc = combine(acc, apply_diag(rot, r, masks[pi]))
        rot = rot_next
    return acc


def _diag_or_piece(rot, r, mask):
    """out[j] |= rot[(j + r) % B] & mask[j] — a static circular shift."""
    return jnp.roll(rot, -r) & mask


def _diag_sum_piece(rot, r, mask):
    return jnp.roll(rot, -r) * mask


def _diag_max_piece(rot, r, mask):
    from p2pnetwork_tpu.ops.segment import neutral_min

    return jnp.where(mask, jnp.roll(rot, -r), neutral_min(rot.dtype))


def _diag_minplus_piece(rot, r, mask):
    return jnp.where(mask, jnp.roll(rot, -r) + 1.0, jnp.inf)


def _ring_pass(axis_name, S, frontier, groups, acc0, combine, diag=None,
               comm: Optional[_RingComm] = None):
    """One full ring rotation. ``groups`` is a sequence of ``(apply_fn,
    *arrays)`` bucket groups, every array carrying a leading ring-step axis
    ``[S, ...]`` — static (dst-sorted segment or MXU-blocked) and dynamic
    (unsorted) edges ride the same rotation; at step ``t`` each group's
    bucket ``t`` consumes the resident block, folding results with
    ``combine``.

    The halo hop rides the comm seam (``_RingComm``): it is ISSUED before
    the step's bucket applies — hop and applies both only read the
    resident block — so the transfer overlaps the shard-local compute on
    overlap-capable backends. When the static group is the MXU one-hot
    layout and the backend has a fused form (pallas), the hop and the
    bucket's blocked segment sum run as ONE kernel
    (ops/pallas_ring.ring_segment_sum): DMA started at grid step 0, the
    whole edge aggregation as the in-flight window, recv wait at the
    last grid step.

    The last bucket is peeled out of the scan: after it is applied there is
    nothing left to rotate, so running its hop would be one wasted ICI
    transfer per pass. Zero-width groups (unused dynamic capacity,
    absent MXU layout) are skipped at trace time.
    """
    comm = comm or _make_ring_comm(DEFAULT_COMM, axis_name, S)
    groups = [g for g in groups if g[1].shape[-1] > 0]
    if diag is not None and diag[0]:
        return _ring_pass_unrolled(axis_name, S, frontier, groups, diag,
                                   acc0, combine, comm)
    meta = []
    arrays = []
    for fn, *arrs in groups:
        meta.append((fn, len(arrs)))
        arrays += arrs

    def apply_all(acc, rot, xs, skip_first=False):
        i = 0
        for gi, (fn, n) in enumerate(meta):
            if not (skip_first and gi == 0):
                acc = combine(acc, fn(rot, *xs[i: i + n]))
            i += n
        return acc

    # The MXU static group's fused form (contrib gather, post-process,
    # exact flag, kernel block) — present only on the one-hot bucket
    # appliers (_bucket_*_mxu), consumed only by fusing backends.
    # `comm.fuses` (not a backend-name check) is the gate: a wrapping
    # comm (chaos/device.FaultyComm) carries its inner backend's name
    # but declines the fused form so the hop payload stays exposed.
    fused = getattr(meta[0][0], "fused", None) if meta else None
    use_fused = fused is not None and comm.fuses
    # graftquake seam: comms that key faults on the ring step ask for
    # the scan's step index via set_context; the bare backends
    # (wants_step=False) keep the exact pre-fault scan structure.
    wants_step = bool(getattr(comm, "wants_step", False))

    def ring_step(rc, xs):
        rot, acc = rc  # rot: frontier block resident this step
        if wants_step:
            bkt_arrays, t = xs
            comm.set_context(step=t)
        else:
            bkt_arrays = xs
        if use_fused:
            contrib_fn, post, exact, kblock = fused
            arrs0 = bkt_arrays[: meta[0][1]]
            rot_next, out = comm.fused_segment_sum(
                rot, contrib_fn(rot, *arrs0), arrs0[1], kblock, exact)
            acc = combine(acc, post(out))
            acc = apply_all(acc, rot, bkt_arrays, skip_first=True)
        else:
            rot_next = comm.shift(rot)
            acc = apply_all(acc, rot, bkt_arrays)
        return (rot_next, acc), None

    if S > 1:
        xs = tuple(a[: S - 1] for a in arrays)
        if wants_step:
            xs = (xs, jnp.arange(S - 1, dtype=jnp.int32))
        (rot, acc), _ = jax.lax.scan(ring_step, (frontier, acc0), xs)
    else:
        rot, acc = frontier, acc0
    return apply_all(acc, rot, tuple(a[S - 1] for a in arrays))


def _bucket_or(block, sorted_dst=True):
    def apply(rot, src, dst, m):
        contrib = (rot[src] & m).astype(jnp.int32)
        return jax.ops.segment_max(
            contrib, dst, num_segments=block, indices_are_sorted=sorted_dst
        ) > 0

    return apply


def _bucket_sum(block, sorted_dst=True):
    def apply(rot, src, dst, m):
        contrib = rot[src] * m
        return jax.ops.segment_sum(
            contrib, dst, num_segments=block, indices_are_sorted=sorted_dst
        )

    return apply


def _bucket_max(block, sorted_dst=True):
    def apply(rot, src, dst, m):
        from p2pnetwork_tpu.ops.segment import neutral_min

        contrib = jnp.where(m, rot[src], neutral_min(rot.dtype))
        return jax.ops.segment_max(
            contrib, dst, num_segments=block, indices_are_sorted=sorted_dst
        )

    return apply


def _bucket_minplus(block, sorted_dst=True):
    """Unit-hop min-plus bucket: ``out[v] = min(rot[u] + 1)`` over the
    bucket's live edges — the sharded ring layouts carry no weight
    channel, so every hop costs 1, exactly
    ops/segment.propagate_min_plus on an unweighted graph (and its
    ``DYNAMIC_LINK_COST`` for the dynamic region)."""

    def apply(rot, src, dst, m):
        contrib = jnp.where(m, rot[src] + 1.0, jnp.inf)
        return jax.ops.segment_min(
            contrib, dst, num_segments=block, indices_are_sorted=sorted_dst
        )

    return apply


def _bucket_or_mxu(block, mxu_block):
    """Bucket OR via the fused Pallas one-hot-matmul kernel
    (ops/pallas_edge.py — the one-hot never touches HBM); 0/1
    contributions are exact in the single-pass MXU mode."""
    from p2pnetwork_tpu.ops.pallas_edge import segment_sum_pallas_impl

    def contrib_of(rot, src, dst, m):
        return (rot[src] & m).astype(jnp.float32)

    def post(out):
        return out.reshape(-1)[:block] > 0

    def apply(rot, src, dst, m):  # [NB, W] each
        out = segment_sum_pallas_impl(contrib_of(rot, src, dst, m), dst,
                                      mxu_block, exact=False)
        return post(out)

    # Fused-ring form (comm="pallas"): same gather, same kernel math, the
    # halo DMA carried under the segment-sum grid (_ring_pass).
    apply.fused = (contrib_of, post, False, mxu_block)
    return apply


def _bucket_sum_mxu(block, mxu_block):
    from p2pnetwork_tpu.ops.pallas_edge import segment_sum_pallas_impl

    def contrib_of(rot, src, dst, m):
        return rot[src] * m  # 0/1 pressure: exact in single-pass mode

    def post(out):
        return out.reshape(-1)[:block]

    def apply(rot, src, dst, m):  # rot f32[B]; src/dst i32[NB, W]
        out = segment_sum_pallas_impl(contrib_of(rot, src, dst, m), dst,
                                      mxu_block, exact=False)
        return post(out)

    apply.fused = (contrib_of, post, False, mxu_block)
    return apply


def _groups_or(block, mxu_block, buckets, dyn_buckets, mxu_buckets):
    static = (
        (_bucket_or_mxu(block, mxu_block), *mxu_buckets)
        if mxu_buckets[0].shape[-1] > 0
        else (_bucket_or(block, sorted_dst=True), *buckets)
    )
    return [static, (_bucket_or(block, sorted_dst=False), *dyn_buckets)]


def _groups_sum(block, mxu_block, buckets, dyn_buckets, mxu_buckets):
    static = (
        (_bucket_sum_mxu(block, mxu_block), *mxu_buckets)
        if mxu_buckets[0].shape[-1] > 0
        else (_bucket_sum(block, sorted_dst=True), *buckets)
    )
    return [static, (_bucket_sum(block, sorted_dst=False), *dyn_buckets)]


# -------------------------------------------------------------------- flood


def _ring_rounds_or(axis_name, S, block, pieces, mxu_block, comm,
                    bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                    mxu_src, mxu_dst, mxu_mask, diag_masks,
                    node_mask, out_degree, seen0, frontier0, rounds):
    """Per-shard body (runs under shard_map): ``rounds`` flood rounds, each a
    full ring pass. All blocks carry a leading length-1 shard axis."""
    pass_ = _make_or_pass(axis_name, S, block, pieces, mxu_block, comm,
                          bkt_src, bkt_dst, bkt_mask,
                          dyn_src, dyn_dst, dyn_mask,
                          mxu_src, mxu_dst, mxu_mask, diag_masks)
    node_mask_b, out_degree_b = node_mask[0], out_degree[0]
    # Live-count denominator, like models/flood.py — under failures the
    # coverage must be of SURVIVORS, or dead-but-seen nodes push it past 1.
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )

    def one_round(carry, _):
        seen, frontier = carry  # [block] bool each
        delivered = pass_(frontier)
        new = delivered & ~seen & node_mask_b
        seen = seen | new
        msgs = jax.lax.psum(
            jnp.sum(jnp.where(frontier, out_degree_b, 0)), axis_name
        )
        covered = jax.lax.psum(
            jnp.sum((seen & node_mask_b).astype(jnp.int32)), axis_name
        )
        return (seen, new), {"messages": msgs, "coverage": covered / n_live}

    (seen, frontier), stats = jax.lax.scan(
        one_round, (seen0[0], frontier0[0]), None, length=rounds
    )
    return seen[None], frontier[None], stats


@functools.lru_cache(maxsize=64)
def _flood_fn(mesh: Mesh, axis_name: str, S: int, block: int, rounds: int,
              pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    """Build (and cache) the compiled sharded flood program for this shape."""
    body = functools.partial(_ring_rounds_or, axis_name, S, block, pieces,
                             mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False: the body may invoke the Pallas bucket kernel, whose
    # vma-typed lowering trips a cache bug in current JAX (see
    # ops/pallas_edge.py); scoped to the ring-body programs only.
    fn = shard_map(
        lambda *args: body(*args, rounds=rounds),
        mesh=mesh, check_vma=False,
        in_specs=(spec,) * 14,
        out_specs=(spec, spec, P()),
    )
    return jax.jit(fn)


def _flood_seed(sg: ShardedGraph, source: int):
    S, block = sg.n_shards, sg.block
    seed = jnp.zeros((S, block), dtype=bool).at[
        source // block, source % block].set(True)
    return seed & sg.node_mask  # dead source seeds nothing (Flood.init parity)


def flood(sg: ShardedGraph, mesh: Mesh, source: int, rounds: int,
          axis_name: str = DEFAULT_AXIS, state0=None,
          return_state: bool = False, comm: str = DEFAULT_COMM):
    """Run ``rounds`` of single-source flood on the sharded graph.

    Returns ``(seen [S, block] bool, stats dict of [rounds] arrays)`` — the
    sharded equivalent of ``engine.run(graph, Flood(source), ...)``, and
    bit-identical to it (tests/test_sharded.py), including under runtime
    failures and connects.

    Resume path (the mesh-backed JaxSimNode's stepper): pass ``state0 =
    (seen, frontier)`` to continue a run (``source`` is then ignored) and
    ``return_state=True`` to get ``((seen, frontier), stats)`` back.
    """
    from p2pnetwork_tpu.models.flood import Flood

    S, block = sg.n_shards, sg.block
    if state0 is None:
        state0 = init_state(sg, Flood(source=source), None)
    seen0, frontier0 = state0
    fn = _flood_fn(mesh, axis_name, S, block, rounds, sg.diag_pieces,
                   sg.mxu_block, _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    seen, frontier, stats = fn(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree, seen0, frontier0,
    )
    if return_state:
        return (seen, frontier), stats
    return seen, stats


# --------------------------------------------------- flood-to-coverage


def _ring_coverage_or(axis_name, S, block, pieces, mxu_block, comm,
                      coverage_target,
                      max_rounds,
                      bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                      mxu_src, mxu_dst, mxu_mask, diag_masks,
                      node_mask, out_degree, seen0, frontier0,
                      ring0=None, ici_round=None, fault_round0=None):
    """Per-shard body: flood until the psum'd live coverage reaches the
    target — the device-side early-exit ``lax.while_loop`` of
    engine.run_until_coverage, multi-chip. The psum makes ``covered``
    identical on every shard, so the loop condition is replicated-consistent
    by construction. Messages accumulate in the two-limb counter
    (utils/accum.py) — multi-chip totals wrap int32 even sooner.

    ``ring0``/``ici_round`` (both or neither — the flight-recorder
    variant) append the per-round ring to the carry: every row is built
    from the psum'd replicated scalars, so the ring is replicated too
    and rides back as a fourth output. Results are bit-identical either
    way — the ring never feeds the loop's math.

    ``fault_round0`` (fault-spec comms only) is the GLOBAL round of this
    call's first round: the graftquake comm keys its fault sites on
    ``fault_round0 + r``, so a resumed/healed chunk hits exactly the
    sites an unchunked run would."""
    pass_ = _make_or_pass(axis_name, S, block, pieces, mxu_block, comm,
                          bkt_src, bkt_dst, bkt_mask,
                          dyn_src, dyn_dst, dyn_mask,
                          mxu_src, mxu_dst, mxu_mask, diag_masks)
    wire_faults = (fault_round0 is not None
                   and getattr(pass_.comm, "wants_step", False))
    node_mask_b, out_degree_b = node_mask[0], out_degree[0]
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )
    rec = ring0 is not None

    def cond(carry):
        rounds, covered = carry[2], carry[3]
        return (covered / n_live < coverage_target) & (rounds < max_rounds)

    def body(carry):
        seen, frontier, rounds, prev_covered, hi, lo, occ = carry[:7]
        if wire_faults:
            pass_.comm.set_context(round=fault_round0 + rounds)
        delivered = pass_(frontier)
        new = delivered & ~seen & node_mask_b
        seen = seen | new
        msgs = jax.lax.psum(
            jnp.sum(jnp.where(frontier, out_degree_b, 0)), axis_name
        )
        hi, lo = accum.add((hi, lo), msgs)
        covered = jax.lax.psum(jnp.sum((seen & node_mask_b).astype(jnp.int32)),
                               axis_name)
        # Per-round frontier occupancy, the engine's ints exactly
        # (ops/frontier.py occupancy: live-new count / live-node count as
        # f32) so the packed mean matches the single-chip summary
        # bit-for-bit — run-summary parity the mesh JaxSimNode tests pin.
        # `new` is disjoint from the prior seen and pre-masked, so its
        # live count IS the coverage delta — no extra psum per round.
        occ_delta = ((covered - prev_covered) / n_live).astype(jnp.float32)
        occ = occ + occ_delta
        out = (seen, new, rounds + 1, covered, hi, lo, occ)
        if not rec:
            return out
        return out + (flightrec.write_row(
            carry[7], rounds, occupancy=occ_delta, new=msgs,
            total=flightrec.total_f32(hi, lo), coverage=covered,
            active_lanes=1, ici_bytes=ici_round),)

    seen0_b = seen0[0]
    covered0 = jax.lax.psum(
        jnp.sum((seen0_b & node_mask_b).astype(jnp.int32)), axis_name
    )
    init = (seen0_b, frontier0[0], jnp.int32(0), covered0, *accum.zero(),
            jnp.float32(0.0))
    if rec:
        init = init + (ring0,)
    final = jax.lax.while_loop(cond, body, init)
    seen, frontier, rounds, covered, hi, lo, occ = final[:7]
    # One packed i32[5] (replicated) carries the whole summary back — the
    # engine's single-transfer trick; separate scalars would each cost a
    # device->host round trip. The fifth slot is the
    # mean per-round frontier occupancy (engine _stat_while parity).
    packed = accum.pack_summary(
        rounds, covered / n_live, (hi, lo),
        extra=occ / jnp.maximum(rounds, 1)
    )
    if rec:
        return seen[None], frontier[None], packed, final[7]
    return seen[None], frontier[None], packed


@functools.lru_cache(maxsize=64)
def _flood_cov_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                  max_rounds: int, pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM, rec: bool = False):
    body = functools.partial(_ring_coverage_or, axis_name, S, block, pieces,
                             mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factory.
    # The recorder variant (rec=True) appends the replicated flight ring
    # and the static per-round ICI byte estimate to the arguments and the
    # ring to the outputs. A fault-spec comm (graftquake) appends one
    # more replicated scalar — the global round of the chunk's first
    # round — LAST, so string-comm programs keep their exact signature.
    faulty = not isinstance(comm, str)
    if faulty:
        wrapped = lambda target, *args: body(  # noqa: E731
            target, max_rounds, *args[:-1], fault_round0=args[-1])
    else:
        wrapped = lambda target, *args: body(target, max_rounds, *args)  # noqa: E731
    fn = shard_map(
        wrapped,
        mesh=mesh, check_vma=False,
        in_specs=(P(),) + (spec,) * 14 + ((P(), P()) if rec else ())
        + ((P(),) if faulty else ()),
        out_specs=(spec, spec, P()) + ((P(),) if rec else ()),
    )
    return jax.jit(fn)


#: Cached per-round ICI byte estimates for the flight recorder's
#: ``ici_bytes`` column, keyed on the compiled-shape config — the commviz
#: census is an abstract trace (tens of ms), not something to pay per
#: recorded run.
_REC_ICI_CACHE: dict = {}  # graftlint: ignore[unbounded-cache] -- keyed on compiled-shape config; one entry per distinct (ws, ba, shards) lowering, a finite vocabulary per process


def _rec_ici_round_bytes(key: tuple, build) -> int:
    """The per-round ICI byte estimate of one compiled loop config:
    ``commviz.ici_bytes_estimate`` of the loop fn (while-loop bodies are
    censused once = per round, ring passes scan-trip-weighted — the
    same pricing the bench multichip column publishes). ``build()``
    returns ``(fn, args, axis_size)``; the result is cached under
    ``key`` (shape-config identity — the estimate depends on block
    sizes and mesh width, not on graph contents)."""
    est = _REC_ICI_CACHE.get(key)
    if est is None:
        from p2pnetwork_tpu.parallel import commviz

        fn, args, axis_size = build()
        est = _REC_ICI_CACHE[key] = int(
            commviz.ici_bytes_estimate(fn, args, axis_size))
    return est


def _record_comm_faults(comm, rounds, S, *, round0: int = 0) -> None:
    """After a fault-spec run (graftquake): count the faults the executed
    round window actually hit into ``chaos_device_faults_total{kind}`` —
    a host replay of the schedule, exact by construction (the compiled
    loop carries no counter). No-op for backend-string comms, empty
    schedules, hop-free rings (S == 1) and zero-round runs."""
    if isinstance(comm, str) or S <= 1 or not rounds:
        return
    schedule = getattr(comm, "schedule", None)
    if schedule is None or not schedule.active:
        return
    from p2pnetwork_tpu.chaos import device as chaos_device

    chaos_device.record_faults(schedule, rounds=int(rounds),
                               n_steps=S - 1, n_shards=S,
                               round0=int(round0))


def flood_until_coverage(sg: ShardedGraph, mesh: Mesh, source: int, *,
                         coverage_target: float = 0.99,
                         max_rounds: int = 1024,
                         axis_name: str = DEFAULT_AXIS,
                         state0=None, return_state: bool = False,
                         adaptive_k: int = 0, comm: str = DEFAULT_COMM,
                         recorder=None, fault_round0: int = 0):
    """Flood until coverage of the LIVE population reaches the target —
    the north-star run-to-99% measurement (engine.run_until_coverage), on
    the multi-chip path. One XLA program, zero host round-trips per round.

    ``adaptive_k > 0`` (requires ``shard_graph(source_csr=True)``) runs
    rounds whose global frontier is small through the frontier-sparse
    path: the frontier rides as a replicated index list and each shard
    gathers only its edges from those senders, chunked into W-wide work
    items — O(k·W) work plus one tiny all-gather instead of the full ring
    pass. The budget is out-edge MASS (largest per-shard item count must
    fit ``adaptive_k``), so degree-skewed graphs get the win too: a hub
    costs ceil(row/W) items instead of widening every gather to its
    degree. Results are bit-identical to the dense loop (the multi-chip
    mirror of models/adaptive_flood.py).

    Returns ``(seen [S, block] bool, dict(rounds, coverage, messages))``
    with ``messages`` an exact Python int. Resume path (same contract as
    :func:`flood`): pass ``state0 = (seen, frontier)`` to continue a run
    (``source`` is then ignored) and ``return_state=True`` to get the full
    ``((seen, frontier), dict)`` back.

    ``recorder`` (a :class:`~p2pnetwork_tpu.sim.flightrec.FlightRecorder`,
    default off; dense loop only — the adaptive path refuses it) rides
    the per-round flight ring in the replicated carry and attaches
    ``out["flight_record"]``; the ``ici_bytes`` column carries this
    config's static per-round comm-census estimate (the same pricing the
    bench multichip column publishes, per backend). Results stay
    bit-identical to recorder-off runs on BOTH comm backends.

    ``comm`` also accepts a :class:`~p2pnetwork_tpu.chaos.device.FaultSpec`
    (graftquake): the ring runs on the spec's backend with its seeded
    fault schedule injected at the halo hops, keyed on the GLOBAL round
    ``fault_round0 + r`` (chunked/resumed drivers pass ``fault_round0``
    so every chunk hits the sites an unchunked run would); the faults the
    executed window hit are counted into
    ``chaos_device_faults_total{kind}`` after the run (dense loop only —
    the adaptive path refuses fault specs like it refuses the recorder).
    """
    from p2pnetwork_tpu.models.flood import Flood

    S, block = sg.n_shards, sg.block
    if state0 is None:
        state0 = init_state(sg, Flood(source=source), None)
    seen0, frontier0 = state0
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    common = (
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree,
    )
    ring = None
    if adaptive_k > 0:
        if recorder is not None:
            raise ValueError(
                "the flight recorder is not supported on the adaptive "
                "frontier-sparse path — record the dense loop "
                "(adaptive_k=0)")
        if not isinstance(_resolve_comm(comm), str):
            raise ValueError(
                "fault-spec comms are not supported on the adaptive "
                "frontier-sparse path — inject on the dense loop "
                "(adaptive_k=0)")
        if sg.csr_pos is None:
            raise ValueError(
                "adaptive_k requires a sender-CSR sharded graph — build "
                "with shard_graph(source_csr=True)"
            )
        fn = _flood_adaptive_cov_fn(
            mesh, axis_name, S, block, max_rounds, adaptive_k,
            max(sg.csr_span, 1), sg.diag_pieces, sg.mxu_block,
            _resolve_comm(comm),
        )
        seen, frontier, packed = fn(
            jnp.float32(coverage_target), *common,
            sg.csr_pos, sg.csr_offsets, seen0, frontier0,
        )
    else:
        resolved = _resolve_comm(comm)
        # Fault-spec comms (graftquake) take the global first-round
        # index as one extra trailing replicated scalar — traced, so
        # chunked drivers advance it without recompiling.
        ftail = () if isinstance(resolved, str) \
            else (jnp.int32(fault_round0),)
        if recorder is None:
            fn = _flood_cov_fn(mesh, axis_name, S, block, max_rounds,
                               sg.diag_pieces, sg.mxu_block, resolved)
            seen, frontier, packed = fn(
                jnp.float32(coverage_target), *common, seen0, frontier0,
                *ftail,
            )
        else:
            fn = _flood_cov_fn(mesh, axis_name, S, block, max_rounds,
                               sg.diag_pieces, sg.mxu_block, resolved,
                               rec=True)
            base_fn = _flood_cov_fn(mesh, axis_name, S, block, max_rounds,
                                    sg.diag_pieces, sg.mxu_block, resolved)
            ici = _rec_ici_round_bytes(
                ("flood", mesh, axis_name, S, block, resolved,
                 sg.diag_pieces, sg.mxu_block),
                lambda: (base_fn,
                         (jnp.float32(coverage_target), *common, seen0,
                          frontier0, *ftail), S))
            seen, frontier, packed, ring = fn(
                jnp.float32(coverage_target), *common, seen0, frontier0,
                recorder.init(), jnp.float32(ici), *ftail,
            )
            packed, ring = jax.device_get((packed, ring))
    out = accum.unpack_summary(packed)
    _record_comm_faults(comm, out["rounds"], S, round0=fault_round0)
    if ring is not None:
        out["flight_record"] = flightrec.trim(ring, out["rounds"])
    # The packed fifth slot is the mean per-round frontier occupancy —
    # surface it under the engine's summary key (run-summary parity:
    # engine.run_until_coverage on a flood returns the same dict).
    occ = out.pop("extra", None)
    if occ is not None:
        out["frontier_occupancy_mean"] = occ
    if return_state:
        return (seen, frontier), out
    return seen, out


# ------------------------------------------------------------------- gossip


def _ring_rounds_gossip(axis_name, S, block, rng, comm,
                        neighbors, neighbors_mask, node_mask,
                        values0, round_keys, alpha, rounds):
    """Per-shard body: ``rounds`` push-pull gossip rounds (models/gossip.py).

    Each node samples one incoming neighbor — the k-th VALID slot of its
    (liveness-re-masked) table row, matching the engine's draw — and pulls
    that neighbor's value over the ring: at ring step ``t`` the resident
    value block belongs to shard ``(my - t) mod S``, and each node whose
    partner lives there grabs its value — every node matches exactly one
    step, so the accumulated sum IS the pulled value. ``exact_rng=True``
    reproduces the engine's full-population draw bit-for-bit (verification
    mode, O(N) per shard).
    """
    nbrs = neighbors[0]  # [B, W] global ids
    nmask = neighbors_mask[0]
    nm = node_mask[0]
    my = jax.lax.axis_index(axis_name)
    count = jnp.sum(nmask, axis=1)
    has_neighbor = (count > 0) & nm
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(nm.astype(jnp.int32)), axis_name), 1
    )
    csum = jnp.cumsum(nmask, axis=1)
    comm_obj = _make_ring_comm(comm, axis_name, S)
    draw_u = _make_draw(
        axis_name, S, block, rng, my,
        sample=lambda k, shape: jax.random.randint(
            k, shape, 0, jnp.int32(2**31 - 1)
        ),
    )

    def one_round(values, rkey):
        key = jax.random.wrap_key_data(rkey)
        k = draw_u(key) % jnp.maximum(count, 1)
        slot = jnp.argmax((csum == (k + 1)[:, None]) & nmask, axis=1)
        partner = jnp.take_along_axis(nbrs, slot[:, None], axis=1)[:, 0]
        p_shard = partner // block
        p_local = partner % block

        # pcast: a fresh constant is shard-invariant by type; the ring
        # fold adds shard-varying blocks into it, so the accumulator must
        # be marked varying up front (scan carries demand matching vma).
        acc0 = jax.lax.pcast(jnp.zeros((block,), values.dtype),
                             (axis_name,), to="varying")

        def ring_step(rc, t):
            rot, acc = rc
            # Halo hop issued first (comm seam): the pull below only READS
            # the resident block, so the transfer is in flight across it.
            rot_next = comm_obj.shift(rot)
            resident = (my - t) % S
            acc = acc + jnp.where(p_shard == resident, rot[p_local], 0.0)
            return (rot_next, acc), None

        if S > 1:
            (rot, pulled), _ = jax.lax.scan(
                ring_step, (values, acc0), jnp.arange(S - 1)
            )
        else:
            rot, pulled = values, acc0
        resident = (my - (S - 1)) % S
        pulled = pulled + jnp.where(p_shard == resident, rot[p_local], 0.0)

        mixed = (1.0 - alpha) * values + alpha * pulled
        values = jnp.where(has_neighbor, mixed, values)

        masked = values * nm
        mean = jax.lax.psum(jnp.sum(masked), axis_name) / n_live
        var = jax.lax.psum(
            jnp.sum(jnp.where(nm, (values - mean) ** 2, 0.0)), axis_name
        ) / n_live
        stats = {
            "messages": 2 * jax.lax.psum(
                jnp.sum(has_neighbor.astype(jnp.int32)), axis_name
            ),
            "variance": var,
            "mean": mean,
        }
        return values, stats

    values, stats = jax.lax.scan(one_round, values0[0], round_keys)
    return values[None], stats


@functools.lru_cache(maxsize=64)
def _gossip_fn(mesh: Mesh, axis_name: str, S: int, block: int, rounds: int,
               rng: str, comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_rounds_gossip, axis_name, S, block,
                             rng, comm)
    spec = P(axis_name)
    # check_vma=False under the pallas backend: see the ring-body factories.
    kw = {} if comm == "ppermute" else {"check_vma": False}
    fn = shard_map(
        lambda *args: body(*args, rounds=rounds),
        mesh=mesh,
        in_specs=(spec,) * 4 + (P(), P()),
        out_specs=(spec, P()),
        **kw,
    )
    return jax.jit(fn)


def gossip(sg: ShardedGraph, mesh: Mesh, protocol, key: jax.Array,
           rounds: int, axis_name: str = DEFAULT_AXIS,
           exact_rng: bool = False, rng: Optional[str] = None,
           values0=None, comm: str = DEFAULT_COMM):
    """Run ``rounds`` of push-pull gossip averaging (models/gossip.py) on
    the sharded graph — randomized consensus, the second protocol family
    reference users build on ``node_message`` [ref: README.md:20].

    Returns ``(values [S, block] f32, stats dict of [rounds] arrays)``. The
    init draw and per-round key schedule match ``engine.run``'s, so with
    ``exact_rng=True`` and ``S*block == n_pad`` the values are bit-identical
    to the single-device engine (tests/test_sharded.py).
    """
    if sg.neighbors is None:
        raise ValueError(
            "sharded gossip needs a partner table: shard a graph built "
            "with a neighbor table (from_edges build_neighbor_table=True)"
        )
    S, block = sg.n_shards, sg.block
    if values0 is None:
        values0 = init_state(sg, protocol, key)
    round_keys = jax.random.key_data(
        jax.random.split(jax.random.fold_in(key, 1), rounds)
    )
    fn = _gossip_fn(mesh, axis_name, S, block, rounds,
                    _resolve_rng(sg, exact_rng, rng), _resolve_comm(comm))
    values, stats = fn(
        sg.neighbors, sg.neighbors_mask, sg.node_mask, values0,
        round_keys, jnp.float32(protocol.alpha),
    )
    return values, stats


# ---------------------------------------------------------------------- SIR


#: Node tile size for the shard-count-invariant scalable RNG. One PRNG key
#: per 128-node tile, derived from the GLOBAL tile index — each shard only
#: generates its own tiles (O(block) work), and the draw stream does not
#: depend on how many shards the population is split across.
RNG_TILE = 128


def _make_draw(axis_name, S, block, rng, my, sample=None):
    """Per-shard random-draw function for the chosen RNG mode.

    - ``"exact"``: draw the full population on every shard, slice own block
      — O(N)/shard, bit-identical to the single-device engine (oracle mode).
    - ``"tile"`` (scalable default): one key per global 128-node tile —
      O(block)/shard AND invariant across shard counts, so results have a
      cross-shard-count regression oracle. Requires ``block % 128 == 0``
      (callers fall back to ``"fold"`` otherwise).
    - ``"fold"``: fold the shard index into the key — cheapest, but results
      change with the mesh size.

    ``sample(key, shape)`` defaults to a [0, 1) uniform draw.
    """
    if sample is None:
        sample = lambda k, shape: jax.random.uniform(k, shape)  # noqa: E731
    if rng == "tile" and block % RNG_TILE != 0:  # pragma: no cover
        raise ValueError("tile RNG requires block % 128 == 0")

    def draw(key):
        if rng == "exact":
            full = sample(key, (S * block,))
            return jax.lax.dynamic_slice(full, (my * block,), (block,))
        if rng == "tile":
            tiles = block // RNG_TILE
            base = my * tiles
            keys = jax.vmap(
                lambda i: jax.random.fold_in(key, base + i)
            )(jnp.arange(tiles))
            return jax.vmap(
                lambda k: sample(k, (RNG_TILE,))
            )(keys).reshape(block)
        return sample(jax.random.fold_in(key, my), (block,))

    return draw


def _resolve_rng(sg: ShardedGraph, exact_rng: bool, rng: Optional[str]) -> str:
    if exact_rng:
        return "exact"
    if rng is not None:
        if rng not in ("exact", "tile", "fold"):
            raise ValueError(
                f"rng must be 'exact', 'tile' or 'fold', got {rng!r}"
            )
        return rng
    return "tile" if sg.block % RNG_TILE == 0 else "fold"


def _make_sir_round(axis_name, S, block, rng, pieces, mxu_block, comm,
                    bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                    mxu_src, mxu_dst, mxu_mask, diag_masks,
                    node_mask, out_degree, one_minus_beta, gamma):
    """Build the per-shard SIR round closure (shared by the fixed-rounds
    scan and the run-to-coverage while_loop): ``one_round(status, key) ->
    (status, stats)`` with infection pressure via a ring sum pass.
    ``beta``/``gamma`` are replicated scalars (runtime operands, so a
    parameter sweep does not recompile per value); ``rng`` selects the
    uniform-draw scheme — see :func:`_make_draw`.
    """
    from p2pnetwork_tpu.models.sir import INFECTED, RECOVERED, SUSCEPTIBLE

    pass_ = _make_sum_pass(axis_name, S, block, pieces, mxu_block, comm,
                           bkt_src, bkt_dst, bkt_mask,
                           dyn_src, dyn_dst, dyn_mask,
                           mxu_src, mxu_dst, mxu_mask, diag_masks)
    node_mask_b, out_degree_b = node_mask[0], out_degree[0]
    # Live-count denominator (models/sir.py parity under failures).
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )
    my = jax.lax.axis_index(axis_name)
    draw = _make_draw(axis_name, S, block, rng, my)

    def one_round(status, key):
        k_inf, k_rec = jax.random.split(key)
        infected = (status == INFECTED) & node_mask_b
        susceptible = (status == SUSCEPTIBLE) & node_mask_b

        pressure = pass_(infected.astype(jnp.float32))
        # one_minus_beta arrives precomputed in f64 then cast, matching the
        # engine's `jnp.power(1.0 - beta, ...)` constant bit-for-bit.
        p_infect = 1.0 - jnp.power(one_minus_beta, pressure)
        newly_infected = susceptible & (draw(k_inf) < p_infect)
        recovers = infected & (draw(k_rec) < gamma)

        status = jnp.where(newly_infected, INFECTED, status)
        status = jnp.where(recovers, RECOVERED, status)

        def frac(mask):
            return jax.lax.psum(jnp.sum(mask.astype(jnp.int32)), axis_name) / n_live

        stats = {
            "messages": jax.lax.psum(
                jnp.sum(jnp.where(infected, out_degree_b, 0)), axis_name
            ),
            "s_frac": frac((status == SUSCEPTIBLE) & node_mask_b),
            "i_frac": frac((status == INFECTED) & node_mask_b),
            "r_frac": frac((status == RECOVERED) & node_mask_b),
            "coverage": frac((status != SUSCEPTIBLE) & node_mask_b),
        }
        return status, stats

    return one_round


def _ring_rounds_sir(axis_name, S, block, rng, pieces, mxu_block, comm,
                     bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                     mxu_src, mxu_dst, mxu_mask, diag_masks,
                     node_mask, out_degree,
                     status0, round_keys, one_minus_beta, gamma, rounds):
    """Per-shard body: ``rounds`` SIR rounds (scan over replicated raw key
    data, engine.run key-schedule parity)."""
    one_round = _make_sir_round(
        axis_name, S, block, rng, pieces, mxu_block, comm,
        bkt_src, bkt_dst, bkt_mask,
        dyn_src, dyn_dst, dyn_mask, mxu_src, mxu_dst, mxu_mask, diag_masks,
        node_mask, out_degree, one_minus_beta, gamma,
    )

    def body(status, rkey):
        return one_round(status, jax.random.wrap_key_data(rkey))

    status, stats = jax.lax.scan(body, status0[0], round_keys)
    return status[None], stats


def _ring_coverage_sir(axis_name, S, block, rng, pieces, mxu_block, comm,
                       coverage_target, max_rounds,
                       bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                       mxu_src, mxu_dst, mxu_mask, diag_masks,
                       node_mask, out_degree,
                       status0, key_data, one_minus_beta, gamma):
    """Per-shard body: SIR until ever-infected coverage reaches the target
    (engine.run_until_coverage's key schedule: split the carried key each
    round). Messages accumulate in the two-limb counter."""
    one_round = _make_sir_round(
        axis_name, S, block, rng, pieces, mxu_block, comm,
        bkt_src, bkt_dst, bkt_mask,
        dyn_src, dyn_dst, dyn_mask, mxu_src, mxu_dst, mxu_mask, diag_masks,
        node_mask, out_degree, one_minus_beta, gamma,
    )

    def cond(carry):
        _, _, rounds, coverage, _, _ = carry
        return (coverage < coverage_target) & (rounds < max_rounds)

    def body(carry):
        status, kd, rounds, _, hi, lo = carry
        k, sub = jax.random.split(jax.random.wrap_key_data(kd))
        status, stats = one_round(status, sub)
        hi, lo = accum.add((hi, lo), stats["messages"])
        return (status, jax.random.key_data(k), rounds + 1,
                stats["coverage"], hi, lo)

    from p2pnetwork_tpu.models.sir import SUSCEPTIBLE

    node_mask_b = node_mask[0]
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )
    cov0 = jax.lax.psum(
        jnp.sum(((status0[0] != SUSCEPTIBLE) & node_mask_b).astype(jnp.int32)),
        axis_name,
    ) / n_live
    init = (status0[0], key_data, jnp.int32(0), cov0, *accum.zero())
    status, _, rounds, coverage, hi, lo = jax.lax.while_loop(cond, body, init)
    # Single-transfer summary, like the flood coverage body.
    return status[None], accum.pack_summary(rounds, coverage, (hi, lo))


@functools.lru_cache(maxsize=64)
def _sir_cov_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                max_rounds: int, rng: str, pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_coverage_sir, axis_name, S, block, rng,
                             pieces, mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factory.
    fn = shard_map(
        lambda target, *args: body(target, max_rounds, *args),
        mesh=mesh, check_vma=False,
        in_specs=(P(),) + (spec,) * 13 + (P(), P(), P()),
        out_specs=(spec, P()),
    )
    return jax.jit(fn)


def sir_until_coverage(sg: ShardedGraph, mesh: Mesh, protocol,
                       key: jax.Array, *,
                       coverage_target: float = 0.99,
                       max_rounds: int = 1024,
                       axis_name: str = DEFAULT_AXIS,
                       exact_rng: bool = False, rng: Optional[str] = None,
                       status0=None, comm: str = DEFAULT_COMM):
    """Run SIR until the ever-infected coverage of the LIVE population
    reaches the target — engine.run_until_coverage's measurement for the
    epidemic protocol, on the multi-chip path. Same key schedule as the
    engine loop (split the carried key per round), so ``exact_rng=True``
    with ``S*block == n_pad`` is bit-identical to it.

    Returns ``(status [S, block] i32, dict(rounds, coverage, messages))``
    with ``messages`` an exact Python int.
    """
    S, block = sg.n_shards, sg.block
    if status0 is None:
        status0 = init_state(sg, protocol, key)
    fn = _sir_cov_fn(mesh, axis_name, S, block, max_rounds,
                     _resolve_rng(sg, exact_rng, rng), sg.diag_pieces,
                     sg.mxu_block, _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    status, packed = fn(
        jnp.float32(coverage_target),
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree, status0,
        jax.random.key_data(key),
        jnp.float32(1.0 - protocol.beta), jnp.float32(protocol.gamma),
    )
    return status, accum.unpack_summary(packed)


@functools.lru_cache(maxsize=64)
def _sir_fn(mesh: Mesh, axis_name: str, S: int, block: int, rounds: int,
            rng: str, pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_rounds_sir, axis_name, S, block, rng,
                             pieces, mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False: the body may invoke the Pallas bucket kernel, whose
    # vma-typed lowering trips a cache bug in current JAX (see
    # ops/pallas_edge.py); scoped to the ring-body programs only.
    fn = shard_map(
        lambda *args: body(*args, rounds=rounds),
        mesh=mesh, check_vma=False,
        in_specs=(spec,) * 13 + (P(), P(), P()),
        out_specs=(spec, P()),
    )
    return jax.jit(fn)


def sir(sg: ShardedGraph, mesh: Mesh, protocol, key: jax.Array, rounds: int,
        axis_name: str = DEFAULT_AXIS, exact_rng: bool = False,
        rng: Optional[str] = None, status0=None,
        comm: str = DEFAULT_COMM):
    """Run ``rounds`` of SIR (models/sir.py) on the sharded graph.

    Returns ``(status [S, block] i32, stats dict of [rounds] arrays)``. The
    key schedule matches ``engine.run``'s, so with ``exact_rng=True`` and a
    node count divisible by the shard count this is bit-identical to the
    single-device engine (tests/test_sharded.py). The scalable default is
    ``rng="tile"`` — O(block) draws that are INVARIANT across shard counts
    (the same run on 1, 2, or 8 shards gives the same epidemic), falling
    back to ``"fold"`` when the block size is not tile-aligned.
    """
    S, block = sg.n_shards, sg.block
    if status0 is None:
        status0 = init_state(sg, protocol, key)
    # engine.run's schedule: one subkey per round off fold_in(key, 1).
    round_keys = jax.random.key_data(
        jax.random.split(jax.random.fold_in(key, 1), rounds)
    )
    fn = _sir_fn(mesh, axis_name, S, block, rounds,
                 _resolve_rng(sg, exact_rng, rng), sg.diag_pieces,
                 sg.mxu_block, _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    status, stats = fn(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree,
        status0, round_keys,
        jnp.float32(1.0 - protocol.beta), jnp.float32(protocol.gamma),
    )
    return status, stats


# ------------------------------------------- generic value propagation


def _make_sum_pass(axis_name, S, block, pieces, mxu_block, comm,
                   bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                   mxu_src, mxu_dst, mxu_mask, diag_masks):
    """Build ``pass_(x) -> f32[block]``: one full ring rotation summing a
    per-node value over every incoming edge — the sharded mirror of
    ops/segment.propagate_sum. All bucket arrays arrive with their leading
    length-1 shard axis already peeled (``arr[0]``)."""
    groups = _groups_sum(
        block, mxu_block, (bkt_src[0], bkt_dst[0], bkt_mask[0]),
        (dyn_src[0], dyn_dst[0], dyn_mask[0]),
        (mxu_src[0], mxu_dst[0], mxu_mask[0]),
    )
    diag = (pieces, diag_masks[0], _diag_sum_piece)
    comm_obj = _make_ring_comm(comm, axis_name, S)

    def pass_(x):
        return _ring_pass(axis_name, S, x, groups,
                          jnp.zeros((block,), x.dtype), jnp.add, diag=diag,
                          comm=comm_obj)

    return pass_


def _make_or_pass(axis_name, S, block, pieces, mxu_block, comm,
                  bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                  mxu_src, mxu_dst, mxu_mask, diag_masks):
    """Build ``pass_(frontier) -> bool[block]``: one ring rotation OR-ing a
    boolean signal over every incoming edge — the OR twin of
    :func:`_make_sum_pass`, shared by the flood bodies, the coverage
    loops, :func:`propagate` and the BFS hop-distance bodies."""
    groups = _groups_or(
        block, mxu_block, (bkt_src[0], bkt_dst[0], bkt_mask[0]),
        (dyn_src[0], dyn_dst[0], dyn_mask[0]),
        (mxu_src[0], mxu_dst[0], mxu_mask[0]),
    )
    diag = (pieces, diag_masks[0], _diag_or_piece)
    comm_obj = _make_ring_comm(comm, axis_name, S)

    def pass_(frontier):
        return _ring_pass(axis_name, S, frontier, groups,
                          jnp.zeros((block,), bool), jnp.logical_or,
                          diag=diag, comm=comm_obj)

    pass_.comm = comm_obj  # round-context handle for fault-wired loops
    return pass_


def _make_max_pass(axis_name, S, block, pieces, mxu_block, comm,
                   bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                   mxu_src, mxu_dst, mxu_mask, diag_masks):
    """Build ``pass_(x) -> x.dtype[block]``: one full ring rotation taking
    the per-node MAX over every incoming edge — segment buckets and
    diagonal shifts only (max cannot ride the one-hot-matmul MXU layout,
    which computes sums; :func:`propagate` rejects such graphs up front)."""
    from p2pnetwork_tpu.ops.segment import neutral_min

    groups = [
        (_bucket_max(block, sorted_dst=True),
         bkt_src[0], bkt_dst[0], bkt_mask[0]),
        (_bucket_max(block, sorted_dst=False),
         dyn_src[0], dyn_dst[0], dyn_mask[0]),
    ]
    diag = (pieces, diag_masks[0], _diag_max_piece)
    comm_obj = _make_ring_comm(comm, axis_name, S)

    def pass_(x):
        return _ring_pass(axis_name, S, x, groups,
                          jnp.full((block,), neutral_min(x.dtype), x.dtype),
                          jnp.maximum, diag=diag, comm=comm_obj)

    return pass_


def _make_minplus_pass(axis_name, S, block, pieces, mxu_block, comm,
                       bkt_src, bkt_dst, bkt_mask,
                       dyn_src, dyn_dst, dyn_mask,
                       mxu_src, mxu_dst, mxu_mask, diag_masks):
    """Build ``pass_(dist) -> f32[block]``: one full ring rotation taking
    the per-node MIN of ``dist[u] + 1`` over every incoming edge — one
    unit-weight Bellman-Ford round, the tropical-semiring sibling of
    :func:`_make_max_pass` (segment buckets only: min cannot ride the
    one-hot-matmul MXU layout, and the ring layouts carry no weight
    channel — ops/segment.propagate_min_plus's unweighted case)."""
    groups = [
        (_bucket_minplus(block, sorted_dst=True),
         bkt_src[0], bkt_dst[0], bkt_mask[0]),
        (_bucket_minplus(block, sorted_dst=False),
         dyn_src[0], dyn_dst[0], dyn_mask[0]),
    ]
    diag = (pieces, diag_masks[0], _diag_minplus_piece)
    comm_obj = _make_ring_comm(comm, axis_name, S)

    def pass_(x):
        return _ring_pass(axis_name, S, x, groups,
                          jnp.full((block,), jnp.inf, x.dtype),
                          jnp.minimum, diag=diag, comm=comm_obj)

    return pass_


def _propagate_body(axis_name, S, block, pieces, mxu_block, comm, op,
                    bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                    mxu_src, mxu_dst, mxu_mask, diag_masks,
                    node_mask, signal):
    node_mask_b = node_mask[0]
    if op == "or":
        pass_ = _make_or_pass(axis_name, S, block, pieces, mxu_block, comm,
                              bkt_src, bkt_dst, bkt_mask,
                              dyn_src, dyn_dst, dyn_mask,
                              mxu_src, mxu_dst, mxu_mask, diag_masks)
        return (pass_(signal[0]) & node_mask_b)[None]
    if op == "max":
        from p2pnetwork_tpu.ops.segment import neutral_min

        pass_ = _make_max_pass(axis_name, S, block, pieces, mxu_block, comm,
                               bkt_src, bkt_dst, bkt_mask,
                               dyn_src, dyn_dst, dyn_mask,
                               mxu_src, mxu_dst, mxu_mask, diag_masks)
        out = pass_(signal[0])
        return jnp.where(node_mask_b, out, neutral_min(out.dtype))[None]
    if op == "minplus":
        pass_ = _make_minplus_pass(axis_name, S, block, pieces, mxu_block,
                                   comm, bkt_src, bkt_dst, bkt_mask,
                                   dyn_src, dyn_dst, dyn_mask,
                                   mxu_src, mxu_dst, mxu_mask, diag_masks)
        out = pass_(signal[0])
        return jnp.where(node_mask_b, out, jnp.inf)[None]
    pass_ = _make_sum_pass(axis_name, S, block, pieces, mxu_block, comm,
                           bkt_src, bkt_dst, bkt_mask,
                           dyn_src, dyn_dst, dyn_mask,
                           mxu_src, mxu_dst, mxu_mask, diag_masks)
    out = pass_(signal[0])
    return (out * node_mask_b.astype(out.dtype))[None]


@functools.lru_cache(maxsize=64)
def _propagate_fn(mesh: Mesh, axis_name: str, S: int, block: int, op: str,
                  pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    body = functools.partial(_propagate_body, axis_name, S, block, pieces,
                             mxu_block, comm, op)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(body, mesh=mesh, check_vma=False,
                       in_specs=(spec,) * 12, out_specs=spec)
    return jax.jit(fn)


def propagate(sg: ShardedGraph, mesh: Mesh, signal: jax.Array,
              op: str = "sum", axis_name: str = DEFAULT_AXIS,
              comm: str = DEFAULT_COMM) -> jax.Array:
    """One aggregation pass over every edge of the sharded graph: the
    multi-chip mirror of ``ops.segment.propagate_or`` / ``propagate_sum``,
    and the extension seam for protocols the library does not ship — the
    reference's users write their own protocol logic [ref: README.md:20];
    here they write a per-round function of elementwise updates around this
    call and it runs at ring-sharded scale.

    ``signal`` is ``[S, block]`` (bool for ``op="or"``, float for
    ``op="sum"``, float/int for ``op="max"``, f32 distances for
    ``op="minplus"``); returns the per-node aggregate in the same layout,
    masked to live nodes (``max`` masks to the dtype's -inf/int-min
    identity, ``minplus`` to ``+inf``). Static + dynamic
    (runtime-connected) edges and the ring-decomposed diagonals all
    contribute, exactly as in the shipped protocol bodies. ``op="max"``
    and ``op="minplus"`` need the segment layout: shard the graph
    without the MXU remainder (no ``hybrid=True``/``min_count``) —
    one-hot matmuls compute sums, not maxima/minima. ``minplus`` is one
    unit-weight Bellman-Ford round — the ring layouts carry no weight
    channel, so it matches ``ops.segment.propagate_min_plus`` on
    UNWEIGHTED graphs (weighted routing rides the GSPMD auto path).
    ``comm`` selects the halo-exchange backend (:data:`COMM_BACKENDS`).
    """
    if op not in ("or", "sum", "max", "minplus"):
        raise ValueError(
            f"op must be 'or', 'sum', 'max' or 'minplus', got {op!r}")
    if op in ("max", "minplus") and sg.mxu_src is not None:
        raise ValueError(
            f"op={op!r} cannot ride the MXU one-hot layout — shard_graph "
            "without hybrid/min_count for max/min-aggregating protocols"
        )
    fn = _propagate_fn(mesh, axis_name, sg.n_shards, sg.block, op,
                       sg.diag_pieces, sg.mxu_block, _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    return fn(sg.bkt_src, sg.bkt_dst, sg.bkt_mask,
              dyn_src, dyn_dst, dyn_mask, mxu_src, mxu_dst, mxu_mask,
              _diag_masks_or_empty(sg), sg.node_mask, signal)


# ---------------------------------------------------- pagerank / pushsum


def _make_pagerank_round(axis_name, S, block, pieces, mxu_block, comm,
                         bkt_src, bkt_dst, bkt_mask,
                         dyn_src, dyn_dst, dyn_mask,
                         mxu_src, mxu_dst, mxu_mask, diag_masks,
                         node_mask, out_degree, damping, one_minus_damping):
    """Build the per-shard power-iteration round closure
    (models/pagerank.py arithmetic, edge sums over the ring), shared by
    the fixed-rounds scan and the run-to-residual while_loop. ``damping``
    rides as a replicated runtime operand so a damping sweep does not
    recompile; ``one_minus_damping`` arrives precomputed in f64 then cast,
    matching the engine's constant folding."""
    pass_ = _make_sum_pass(axis_name, S, block, pieces, mxu_block, comm,
                           bkt_src, bkt_dst, bkt_mask,
                           dyn_src, dyn_dst, dyn_mask,
                           mxu_src, mxu_dst, mxu_mask, diag_masks)
    node_mask_b = node_mask[0]
    mask_f = node_mask_b.astype(jnp.float32)
    deg = out_degree[0]
    deg_f = deg.astype(jnp.float32)
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    ).astype(jnp.float32)
    msgs = jax.lax.psum(
        jnp.sum(jnp.where(node_mask_b, deg, 0)), axis_name
    )

    def one_round(ranks):
        contrib = jnp.where(node_mask_b & (deg > 0),
                            ranks / jnp.maximum(deg_f, 1.0), 0.0)
        pulled = pass_(contrib)
        dangling = jax.lax.psum(
            jnp.sum(jnp.where(node_mask_b & (deg == 0), ranks, 0.0)),
            axis_name,
        )
        new = (one_minus_damping / n_live
               + damping * (pulled + dangling / n_live)) * mask_f
        stats = {
            "messages": msgs,
            "residual": jax.lax.psum(jnp.sum(jnp.abs(new - ranks)), axis_name),
            "rank_total": jax.lax.psum(jnp.sum(new), axis_name),
            "rank_max": jax.lax.pmax(jnp.max(new), axis_name),
        }
        return new, stats

    return one_round


def _ring_rounds_pagerank(axis_name, S, block, pieces, mxu_block, comm,
                          bkt_src, bkt_dst, bkt_mask,
                          dyn_src, dyn_dst, dyn_mask,
                          mxu_src, mxu_dst, mxu_mask, diag_masks,
                          node_mask, out_degree,
                          ranks0, damping, one_minus_damping, rounds):
    """Per-shard body: ``rounds`` damped power-iteration rounds."""
    one_round = _make_pagerank_round(
        axis_name, S, block, pieces, mxu_block, comm,
        bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, diag_masks,
        node_mask, out_degree, damping, one_minus_damping,
    )
    ranks, stats = jax.lax.scan(lambda r, _: one_round(r), ranks0[0], None,
                                length=rounds)
    return ranks[None], stats


@functools.lru_cache(maxsize=64)
def _pagerank_fn(mesh: Mesh, axis_name: str, S: int, block: int, rounds: int,
                 pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_rounds_pagerank, axis_name, S, block,
                             pieces, mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        lambda *args: body(*args, rounds=rounds),
        mesh=mesh, check_vma=False,
        in_specs=(spec,) * 13 + (P(), P()),
        out_specs=(spec, P()),
    )
    return jax.jit(fn)


def pagerank(sg: ShardedGraph, mesh: Mesh, protocol, rounds: int,
             axis_name: str = DEFAULT_AXIS, ranks0=None,
             comm: str = DEFAULT_COMM):
    """Run ``rounds`` of PageRank power iteration (models/pagerank.py) on
    the sharded graph. Deterministic — no RNG. Returns ``(ranks [S, block]
    f32, stats dict of [rounds] arrays)``; agrees with the single-device
    engine to f32 summation-order tolerance (edge sums accumulate in
    bucket/ring order here, receiver order there)."""
    S, block = sg.n_shards, sg.block
    if ranks0 is None:
        ranks0 = init_state(sg, protocol, None)
    fn = _pagerank_fn(mesh, axis_name, S, block, rounds, sg.diag_pieces,
                      sg.mxu_block, _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    return fn(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree, ranks0,
        jnp.float32(protocol.damping), jnp.float32(1.0 - protocol.damping),
    )


def _freeze_while(state0, value0, one_step, keep_going,
                 steps_per_round: int):
    """The shared device-side early-exit loop for the ring's run-to-*
    measurements, with optional T-batched iterations.

    ``one_step(state) -> (state, value, messages)`` is one protocol
    round; the loop runs while ``keep_going(value, rounds)`` holds,
    accumulating messages in the two-limb counter. ``steps_per_round=T``
    batches T rounds per while iteration as a ``lax.scan``, each
    sub-step re-checking the predicate and freezing the WHOLE carry once
    it fails — bit-exact vs T=1 by construction (the engine's
    ``_stat_while`` contract; rounds-bound runs amortize the
    per-iteration dispatch/collective floor T-fold). The freeze masks
    every state leaf; a leaf whose post-exit value is semantically dead
    (e.g. the walker's chained key data) freezes harmlessly, because a
    frozen sub-step implies the next ``cond`` is False.

    Returns ``(state, rounds, value, (hi, lo))`` — callers pack their
    own summaries.
    """

    def cond(carry):
        _, rounds, value, _, _ = carry
        return keep_going(value, rounds)

    def body(carry):
        state, rounds, _, hi, lo = carry
        state, value, msgs = one_step(state)
        hi, lo = accum.add((hi, lo), msgs)
        return (state, rounds + 1, value, hi, lo)

    def batched_body(carry):
        def substep(c, _):
            state, rounds, value, hi, lo = c
            live = keep_going(value, rounds)
            nstate, nvalue, msgs = one_step(state)
            state = jax.tree_util.tree_map(
                lambda new, old: jnp.where(live, new, old), nstate, state)
            hi, lo = accum.add(
                (hi, lo), jnp.where(live, msgs, jnp.zeros_like(msgs)))
            rounds = jnp.where(live, rounds + 1, rounds)
            value = jnp.where(live, nvalue, value)
            return (state, rounds, value, hi, lo), None

        carry, _ = jax.lax.scan(substep, carry, None,
                                length=steps_per_round)
        return carry

    init = (state0, jnp.int32(0), value0, *accum.zero())
    state, rounds, value, hi, lo = jax.lax.while_loop(
        cond, body if steps_per_round == 1 else batched_body, init)
    return state, rounds, value, (hi, lo)


def _ring_residual_pagerank(axis_name, S, block, pieces, mxu_block, comm,
                            steps_per_round, tol, max_rounds,
                            bkt_src, bkt_dst, bkt_mask,
                            dyn_src, dyn_dst, dyn_mask,
                            mxu_src, mxu_dst, mxu_mask, diag_masks,
                            node_mask, out_degree,
                            ranks0, damping, one_minus_damping):
    """Per-shard body: power iteration until the L1 residual drops below
    ``tol`` — engine.run_until_converged's measurement on the multi-chip
    path, with the packed single-transfer summary. ``steps_per_round``
    batches iterations per while step (bit-exact vs 1; _freeze_while)."""
    one_round = _make_pagerank_round(
        axis_name, S, block, pieces, mxu_block, comm,
        bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, diag_masks,
        node_mask, out_degree, damping, one_minus_damping,
    )

    def one_step(ranks):
        ranks, stats = one_round(ranks)
        return ranks, stats["residual"], stats["messages"]

    ranks, rounds, residual, (hi, lo) = _freeze_while(
        ranks0[0], jnp.float32(jnp.inf), one_step,
        lambda v, r: (v >= tol) & (r < max_rounds), steps_per_round)
    return ranks[None], accum.pack_summary(rounds, residual, (hi, lo))


@functools.lru_cache(maxsize=64)
def _pagerank_residual_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                          max_rounds: int, pieces=(), mxu_block: int = 128,
                          steps_per_round: int = 1,
                          comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_residual_pagerank, axis_name, S, block,
                             pieces, mxu_block, comm, steps_per_round)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        lambda tol, *args: body(tol, max_rounds, *args),
        mesh=mesh, check_vma=False,
        in_specs=(P(),) + (spec,) * 13 + (P(), P()),
        out_specs=(spec, P()),
    )
    return jax.jit(fn)


def pagerank_until_residual(sg: ShardedGraph, mesh: Mesh, protocol, *,
                            tol: float = 1e-6, max_rounds: int = 1024,
                            steps_per_round: int = 1,
                            axis_name: str = DEFAULT_AXIS, ranks0=None,
                            comm: str = DEFAULT_COMM):
    """Run PageRank until the L1 residual drops below ``tol`` — the
    convergence measurement (engine.run_until_converged with
    stat="residual"), multi-chip, as one device-side while_loop. Returns
    ``(ranks [S, block] f32, dict(rounds, value, messages))`` with
    ``value`` the final residual and ``messages`` an exact Python int."""
    S, block = sg.n_shards, sg.block
    if steps_per_round < 1:
        raise ValueError(
            f"steps_per_round must be >= 1, got {steps_per_round}")
    if ranks0 is None:
        ranks0 = init_state(sg, protocol, None)
    fn = _pagerank_residual_fn(mesh, axis_name, S, block, max_rounds,
                               sg.diag_pieces, sg.mxu_block,
                               int(steps_per_round), _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    ranks, packed = fn(
        jnp.float32(tol),
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree, ranks0,
        jnp.float32(protocol.damping), jnp.float32(1.0 - protocol.damping),
    )
    out = accum.unpack_summary(packed)
    out["value"] = out.pop("coverage")
    return ranks, out


def _ring_leader_quiet(axis_name, S, block, pieces, mxu_block, comm,
                       max_rounds,
                       bkt_src, bkt_dst, bkt_mask,
                       dyn_src, dyn_dst, dyn_mask,
                       mxu_src, mxu_dst, mxu_mask, diag_masks,
                       node_mask, out_degree):
    """Per-shard body: highest-live-id leader election run to quiescence —
    the multi-chip mirror of models/leader.py under
    engine.run_until_converged(stat="changed", threshold=1), as one
    device-side while_loop. Nodes re-broadcast only the round after they
    learned a better candidate; the loop exits on the first all-quiet
    round (which is executed and message-counted, matching the engine)."""
    from p2pnetwork_tpu.ops.segment import neutral_min

    pass_ = _make_max_pass(axis_name, S, block, pieces, mxu_block, comm,
                           bkt_src, bkt_dst, bkt_mask,
                           dyn_src, dyn_dst, dyn_mask,
                           mxu_src, mxu_dst, mxu_mask, diag_masks)
    node_mask_b, deg = node_mask[0], out_degree[0]
    neutral = neutral_min(jnp.int32)
    my = jax.lax.axis_index(axis_name)
    ids = (my * block + jnp.arange(block)).astype(jnp.int32)
    known0 = jnp.where(node_mask_b, ids, -1)
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )

    def cond(carry):
        _, _, rounds, changed, _, _ = carry
        return (changed > 0) & (rounds < max_rounds)

    def body(carry):
        known, frontier, rounds, _, hi, lo = carry
        msgs = jax.lax.psum(jnp.sum(jnp.where(frontier, deg, 0)), axis_name)
        heard = pass_(jnp.where(frontier, known, neutral))
        new_known = jnp.where(node_mask_b, jnp.maximum(known, heard), -1)
        changed_mask = (new_known != known) & node_mask_b
        changed = jax.lax.psum(
            jnp.sum(changed_mask.astype(jnp.int32)), axis_name
        )
        hi, lo = accum.add((hi, lo), msgs)
        return new_known, changed_mask, rounds + 1, changed, hi, lo

    init = (known0, node_mask_b, jnp.int32(0),
            jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name),
            *accum.zero())
    known, _, rounds, _, hi, lo = jax.lax.while_loop(cond, body, init)
    winner = jax.lax.pmax(jnp.max(known), axis_name)
    agreed = jax.lax.psum(
        jnp.sum(((known == winner) & node_mask_b).astype(jnp.int32)),
        axis_name,
    )
    return known[None], accum.pack_summary(rounds, agreed / n_live, (hi, lo))


@functools.lru_cache(maxsize=64)
def _leader_quiet_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                     max_rounds: int, pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_leader_quiet, axis_name, S, block,
                             pieces, mxu_block, comm, max_rounds)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(body, mesh=mesh, check_vma=False,
                       in_specs=(spec,) * 12, out_specs=(spec, P()))
    return jax.jit(fn)


def leader_until_quiet(sg: ShardedGraph, mesh: Mesh, *,
                       max_rounds: int = 1024,
                       axis_name: str = DEFAULT_AXIS,
                       comm: str = DEFAULT_COMM):
    """Highest-live-id leader election run until no node learns anything —
    the multi-chip convergence loop of models/leader.py. Returns
    ``(known [S, block] i32, dict(rounds, coverage, messages))`` where
    ``coverage`` is the fraction of live nodes agreeing on the global
    winner (1.0 on a connected live graph) and ``messages`` an exact
    Python int. Requires the segment layout (``op="max"`` constraint —
    shard_graph without hybrid/min_count)."""
    if sg.mxu_src is not None:
        raise ValueError(
            "leader_until_quiet cannot ride the MXU one-hot layout — "
            "shard_graph without hybrid/min_count for max aggregation"
        )
    S, block = sg.n_shards, sg.block
    fn = _leader_quiet_fn(mesh, axis_name, S, block, max_rounds,
                          sg.diag_pieces, sg.mxu_block, _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    known, packed = fn(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree,
    )
    return known, accum.unpack_summary(packed)


def _make_pushsum_round(axis_name, S, block, pieces, mxu_block, comm,
                        bkt_src, bkt_dst, bkt_mask,
                        dyn_src, dyn_dst, dyn_mask,
                        mxu_src, mxu_dst, mxu_mask, diag_masks,
                        node_mask, out_degree):
    """Build the per-shard push-sum round closure (models/pushsum.py
    arithmetic — mass split over out-edges, two ring sums per round),
    shared by the fixed-rounds scan and the run-to-variance while_loop."""
    pass_ = _make_sum_pass(axis_name, S, block, pieces, mxu_block, comm,
                           bkt_src, bkt_dst, bkt_mask,
                           dyn_src, dyn_dst, dyn_mask,
                           mxu_src, mxu_dst, mxu_mask, diag_masks)
    node_mask_b = node_mask[0]
    mask_f = node_mask_b.astype(jnp.float32)
    deg = out_degree[0]
    shares = 1.0 / (deg.astype(jnp.float32) + 1.0)
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )
    msgs = jax.lax.psum(
        jnp.sum(jnp.where(node_mask_b, deg, 0)), axis_name
    )

    def one_round(s, w):
        s_share = s * shares
        w_share = w * shares
        s = (s_share + pass_(s_share)) * mask_f
        w = (w_share + pass_(w_share)) * mask_f
        est = jnp.where(w > 0, s / jnp.maximum(w, 1e-30), 0.0)
        mean = jax.lax.psum(jnp.sum(est * mask_f), axis_name) / n_live
        var = jax.lax.psum(
            jnp.sum(jnp.where(node_mask_b, (est - mean) ** 2, 0.0)), axis_name
        ) / n_live
        stats = {
            "messages": msgs,
            "s_total": jax.lax.psum(jnp.sum(s), axis_name),
            "w_total": jax.lax.psum(jnp.sum(w), axis_name),
            "variance": var,
            "mean": mean,
        }
        return s, w, stats

    return one_round


def _ring_rounds_pushsum(axis_name, S, block, pieces, mxu_block, comm,
                         bkt_src, bkt_dst, bkt_mask,
                         dyn_src, dyn_dst, dyn_mask,
                         mxu_src, mxu_dst, mxu_mask, diag_masks,
                         node_mask, out_degree, s0, w0, rounds):
    """Per-shard body: ``rounds`` push-sum rounds."""
    one_round = _make_pushsum_round(
        axis_name, S, block, pieces, mxu_block, comm,
        bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, diag_masks, node_mask, out_degree,
    )

    def body(carry, _):
        s, w, stats = one_round(*carry)
        return (s, w), stats

    (s, w), stats = jax.lax.scan(body, (s0[0], w0[0]), None, length=rounds)
    return s[None], w[None], stats


def _ring_variance_pushsum(axis_name, S, block, pieces, mxu_block, comm,
                           steps_per_round, tol, max_rounds,
                           bkt_src, bkt_dst, bkt_mask,
                           dyn_src, dyn_dst, dyn_mask,
                           mxu_src, mxu_dst, mxu_mask, diag_masks,
                           node_mask, out_degree, s0, w0):
    """Per-shard body: push-sum until the estimate variance drops below
    ``tol`` — engine.run_until_converged's measurement on the multi-chip
    path, with the packed single-transfer summary. ``steps_per_round``
    batches rounds per while step (bit-exact vs 1; _freeze_while —
    push-sum's ring rounds are deterministic, no key chain)."""
    one_round = _make_pushsum_round(
        axis_name, S, block, pieces, mxu_block, comm,
        bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, diag_masks, node_mask, out_degree,
    )

    def one_step(state):
        s, w = state
        s, w, stats = one_round(s, w)
        return (s, w), stats["variance"], stats["messages"]

    (s, w), rounds, var, (hi, lo) = _freeze_while(
        (s0[0], w0[0]), jnp.float32(jnp.inf), one_step,
        lambda v, r: (v >= tol) & (r < max_rounds), steps_per_round)
    return s[None], w[None], accum.pack_summary(rounds, var, (hi, lo))


@functools.lru_cache(maxsize=64)
def _pushsum_variance_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                         max_rounds: int, pieces=(), mxu_block: int = 128,
                         steps_per_round: int = 1,
                         comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_variance_pushsum, axis_name, S, block,
                             pieces, mxu_block, comm, steps_per_round)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        lambda tol, *args: body(tol, max_rounds, *args),
        mesh=mesh, check_vma=False,
        in_specs=(P(),) + (spec,) * 14,
        out_specs=(spec, spec, P()),
    )
    return jax.jit(fn)


def pushsum_until_variance(sg: ShardedGraph, mesh: Mesh, protocol,
                           key: jax.Array, *,
                           tol: float = 1e-9, max_rounds: int = 1024,
                           steps_per_round: int = 1,
                           axis_name: str = DEFAULT_AXIS, state0=None,
                           comm: str = DEFAULT_COMM):
    """Run push-sum until the estimate variance drops below ``tol`` — the
    consensus-reached measurement (engine.run_until_converged with
    stat="variance"), multi-chip. Returns ``((s, w), dict(rounds, value,
    messages))`` with ``value`` the final variance. ``steps_per_round``
    batches rounds per while iteration (bit-exact vs 1 — the same freeze
    contract as the engine loops)."""
    S, block = sg.n_shards, sg.block
    if steps_per_round < 1:
        raise ValueError(
            f"steps_per_round must be >= 1, got {steps_per_round}")
    if state0 is None:
        state0 = init_state(sg, protocol, key)
    s0, w0 = state0
    fn = _pushsum_variance_fn(mesh, axis_name, S, block, max_rounds,
                              sg.diag_pieces, sg.mxu_block,
                              int(steps_per_round), _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    s, w, packed = fn(
        jnp.float32(tol),
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree, s0, w0,
    )
    out = accum.unpack_summary(packed)
    out["value"] = out.pop("coverage")
    return (s, w), out


@functools.lru_cache(maxsize=64)
def _pushsum_fn(mesh: Mesh, axis_name: str, S: int, block: int, rounds: int,
                pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_rounds_pushsum, axis_name, S, block,
                             pieces, mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        lambda *args: body(*args, rounds=rounds),
        mesh=mesh, check_vma=False,
        in_specs=(spec,) * 14,
        out_specs=(spec, spec, P()),
    )
    return jax.jit(fn)


def pushsum(sg: ShardedGraph, mesh: Mesh, protocol, key: jax.Array,
            rounds: int, axis_name: str = DEFAULT_AXIS, state0=None,
            comm: str = DEFAULT_COMM):
    """Run ``rounds`` of push-sum consensus (models/pushsum.py) on the
    sharded graph. ``key`` seeds the initial values exactly as the engine
    path does (Gossip-init parity); pass ``state0 = (s, w)`` to continue a
    run instead. Returns ``((s, w) [S, block] f32 each, stats dict)``;
    the conservation invariants (sum(s) fixed, sum(w) == live count) hold
    here too, to f32 summation order."""
    S, block = sg.n_shards, sg.block
    if state0 is None:
        state0 = init_state(sg, protocol, key)
    s0, w0 = state0
    fn = _pushsum_fn(mesh, axis_name, S, block, rounds, sg.diag_pieces,
                     sg.mxu_block, _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    s, w, stats = fn(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree, s0, w0,
    )
    return (s, w), stats


# ------------------------------------------------------------ hop distance


def _make_hopdist_round(axis_name, S, block, pieces, mxu_block, comm,
                        bkt_src, bkt_dst, bkt_mask,
                        dyn_src, dyn_dst, dyn_mask,
                        mxu_src, mxu_dst, mxu_mask, diag_masks,
                        node_mask, out_degree):
    """Per-shard BFS round closure (models/hopdist.py arithmetic): the wave
    is the flood wave; nodes record the first round that reaches them."""
    pass_ = _make_or_pass(axis_name, S, block, pieces, mxu_block, comm,
                          bkt_src, bkt_dst, bkt_mask,
                          dyn_src, dyn_dst, dyn_mask,
                          mxu_src, mxu_dst, mxu_mask, diag_masks)
    node_mask_b, out_degree_b = node_mask[0], out_degree[0]
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )

    def one_round(dist, frontier, rnd):
        delivered = pass_(frontier)
        new = delivered & (dist < 0) & node_mask_b
        rnd = rnd + 1
        dist = jnp.where(new, rnd, dist)
        reached = (dist >= 0) & node_mask_b
        stats = {
            "messages": jax.lax.psum(
                jnp.sum(jnp.where(frontier, out_degree_b, 0)), axis_name
            ),
            "coverage": jax.lax.psum(
                jnp.sum(reached.astype(jnp.int32)), axis_name
            ) / n_live,
            "frontier": jax.lax.psum(jnp.sum(new.astype(jnp.int32)),
                                     axis_name),
            "max_dist": jax.lax.pmax(jnp.max(dist), axis_name),
        }
        return dist, new, rnd, stats

    return one_round


def _ring_rounds_hopdist(axis_name, S, block, pieces, mxu_block, comm,
                         bkt_src, bkt_dst, bkt_mask,
                         dyn_src, dyn_dst, dyn_mask,
                         mxu_src, mxu_dst, mxu_mask, diag_masks,
                         node_mask, out_degree,
                         dist0, frontier0, round0, rounds):
    one_round = _make_hopdist_round(
        axis_name, S, block, pieces, mxu_block, comm,
        bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, diag_masks, node_mask, out_degree,
    )

    def body(carry, _):
        dist, frontier, rnd = carry
        dist, frontier, rnd, stats = one_round(dist, frontier, rnd)
        return (dist, frontier, rnd), stats

    (dist, frontier, rnd), stats = jax.lax.scan(
        body, (dist0[0], frontier0[0], round0), None, length=rounds
    )
    return dist[None], frontier[None], rnd, stats


@functools.lru_cache(maxsize=64)
def _hopdist_fn(mesh: Mesh, axis_name: str, S: int, block: int, rounds: int,
                pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_rounds_hopdist, axis_name, S, block,
                             pieces, mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        lambda *args: body(*args, rounds=rounds),
        mesh=mesh, check_vma=False,
        in_specs=(spec,) * 14 + (P(),),
        out_specs=(spec, spec, P(), P()),
    )
    return jax.jit(fn)


def hopdist(sg: ShardedGraph, mesh: Mesh, protocol, rounds: int,
            axis_name: str = DEFAULT_AXIS, state0=None,
            comm: str = DEFAULT_COMM):
    """Run ``rounds`` of BFS hop-distance (models/hopdist.py) on the sharded
    graph. Deterministic; integer state, so parity with the single-device
    engine is bit-exact. Returns ``((dist, frontier, round), stats)`` with
    ``dist [S, block] i32`` (-1 = unreached)."""
    S, block = sg.n_shards, sg.block
    if state0 is None:
        state0 = init_state(sg, protocol, None)
    dist0, frontier0, round0 = state0
    fn = _hopdist_fn(mesh, axis_name, S, block, rounds, sg.diag_pieces,
                     sg.mxu_block, _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    dist, frontier, rnd, stats = fn(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
        mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree, dist0, frontier0, round0,
    )
    return (dist, frontier, rnd), stats


def _ring_coverage_hopdist(axis_name, S, block, pieces, mxu_block, comm,
                           coverage_target, max_rounds,
                           bkt_src, bkt_dst, bkt_mask,
                           dyn_src, dyn_dst, dyn_mask,
                           mxu_src, mxu_dst, mxu_mask, diag_masks,
                           node_mask, out_degree, dist0, frontier0, round0):
    """Per-shard body: BFS until coverage reaches the target OR the wave
    dies out (frontier empty) — whichever first — as one while_loop with
    the packed single-transfer summary. Lean: only the collectives the
    loop consumes (messages, live frontier count, covered count) run per
    round; eccentricity is a single reduction after the loop."""
    pass_ = _make_or_pass(axis_name, S, block, pieces, mxu_block, comm,
                          bkt_src, bkt_dst, bkt_mask,
                          dyn_src, dyn_dst, dyn_mask,
                          mxu_src, mxu_dst, mxu_mask, diag_masks)
    node_mask_b, out_degree_b = node_mask[0], out_degree[0]
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )

    def cond(carry):
        _, _, rnd, alive, covered, _, _ = carry
        return ((alive > 0) & (rnd - round0 < max_rounds)
                & (covered / n_live < coverage_target))

    def body(carry):
        dist, frontier, rnd, _, covered, hi, lo = carry
        msgs = jax.lax.psum(
            jnp.sum(jnp.where(frontier, out_degree_b, 0)), axis_name
        )
        hi, lo = accum.add((hi, lo), msgs)
        delivered = pass_(frontier)
        new = delivered & (dist < 0) & node_mask_b
        rnd = rnd + 1
        dist = jnp.where(new, rnd, dist)
        alive = jax.lax.psum(jnp.sum(new.astype(jnp.int32)), axis_name)
        return dist, new, rnd, alive, covered + alive, hi, lo

    covered0 = jax.lax.psum(
        jnp.sum(((dist0[0] >= 0) & node_mask_b).astype(jnp.int32)), axis_name
    )
    alive0 = jax.lax.psum(jnp.sum(frontier0[0].astype(jnp.int32)), axis_name)
    init = (dist0[0], frontier0[0], round0, alive0, covered0, *accum.zero())
    dist, frontier, rnd, _, covered, hi, lo = jax.lax.while_loop(
        cond, body, init
    )
    return dist[None], frontier[None], accum.pack_summary(
        rnd - round0, covered / n_live, (hi, lo)
    )


@functools.lru_cache(maxsize=64)
def _hopdist_cov_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                    max_rounds: int, pieces=(), mxu_block: int = 128,
              comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_coverage_hopdist, axis_name, S, block,
                             pieces, mxu_block, comm)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        lambda target, *args: body(target, max_rounds, *args),
        mesh=mesh, check_vma=False,
        in_specs=(P(),) + (spec,) * 14 + (P(),),
        out_specs=(spec, spec, P()),
    )
    return jax.jit(fn)


def hopdist_until_coverage(sg: ShardedGraph, mesh: Mesh, protocol, *,
                           coverage_target: float = 0.99,
                           max_rounds: int = 1024,
                           axis_name: str = DEFAULT_AXIS, state0=None,
                           adaptive_k: int = 0, comm: str = DEFAULT_COMM):
    """BFS until the reached fraction of the LIVE population hits the
    target — engine.run_until_coverage's measurement for HopDistance,
    multi-chip — with an extra early exit the engine loop lacks: if the
    wave dies out first (unreachable remainder), the loop stops instead of
    spinning to ``max_rounds``. Returns ``((dist, frontier, round),
    dict(rounds, coverage, messages))``.

    ``adaptive_k > 0`` (requires ``shard_graph(source_csr=True)``) runs
    small-frontier rounds through the work-item sparse path — the same
    machinery, budget and bit-identity contract as
    ``flood_until_coverage(adaptive_k=...)``; BFS layers, rounds and
    message totals are unchanged."""
    S, block = sg.n_shards, sg.block
    if state0 is None:
        state0 = init_state(sg, protocol, None)
    dist0, frontier0, round0 = state0
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    mxu_src, mxu_dst, mxu_mask = _mxu_or_empty(sg)
    if adaptive_k > 0:
        if sg.csr_pos is None:
            raise ValueError(
                "adaptive_k requires a sender-CSR sharded graph — build "
                "with shard_graph(source_csr=True)"
            )
        fn = _hopdist_adaptive_cov_fn(
            mesh, axis_name, S, block, max_rounds, adaptive_k,
            max(sg.csr_span, 1), sg.diag_pieces, sg.mxu_block,
            _resolve_comm(comm),
        )
        dist, frontier, packed = fn(
            jnp.float32(coverage_target),
            sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
            mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
            sg.node_mask, sg.out_degree, sg.csr_pos, sg.csr_offsets,
            dist0, frontier0, round0,
        )
    else:
        fn = _hopdist_cov_fn(mesh, axis_name, S, block, max_rounds,
                             sg.diag_pieces, sg.mxu_block, _resolve_comm(comm))
        dist, frontier, packed = fn(
            jnp.float32(coverage_target),
            sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
            mxu_src, mxu_dst, mxu_mask, _diag_masks_or_empty(sg),
            sg.node_mask, sg.out_degree, dist0, frontier0, round0,
        )
    out = accum.unpack_summary(packed)
    rnd = round0 + out["rounds"]
    return (dist, frontier, rnd), out


def hopdist_until_done(sg: ShardedGraph, mesh: Mesh, protocol, *,
                       max_rounds: int = 1024,
                       axis_name: str = DEFAULT_AXIS, state0=None,
                       adaptive_k: int = 0, comm: str = DEFAULT_COMM):
    """BFS until the wave dies out (or ``max_rounds``): the complete
    single-source reachability / eccentricity measurement — the
    coverage loop with an unreachable target, so only frontier death
    stops it. ``rounds`` includes the final round that observes the
    emptied frontier (one past the last delivery); the max over ``dist``
    is the source's eccentricity. ``adaptive_k`` as in
    :func:`hopdist_until_coverage` — the sparse tail is where adaptive
    rounds pay off most (the wave's last layers are a trickle)."""
    return hopdist_until_coverage(
        sg, mesh, protocol, coverage_target=2.0, max_rounds=max_rounds,
        axis_name=axis_name, state0=state0, adaptive_k=adaptive_k,
        comm=comm,
    )


# ----------------------------------------- frontier-adaptive coverage loop


def _pack_global_frontier(axis_name, S, k, local_ids, local_count, pad_id):
    """Combine per-shard winner lists into one REPLICATED global frontier
    list: all-gather the (tiny) per-shard [k] lists + counts, then every
    shard deterministically packs them at running offsets — identical
    output everywhere, so the list can drive replicated control flow.
    Truncation past ``k`` is benign: the total then exceeds ``k`` and the
    next round runs dense, never reading the list."""
    lists = jax.lax.all_gather(local_ids, axis_name)  # [S, k]
    counts = jax.lax.all_gather(local_count, axis_name)  # [S]
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
    )
    out = jnp.full(k, pad_id, dtype=jnp.int32)
    idx = jnp.arange(k, dtype=jnp.int32)
    for s in range(S):
        tpos = offs[s] + idx
        valid = (idx < counts[s]) & (tpos < k)
        out = out.at[jnp.where(valid, tpos, k)].set(
            jnp.where(valid, lists[s], pad_id), mode="drop"
        )
    return out, jnp.sum(counts).astype(jnp.int32)


def _make_adaptive_wave(axis_name, S, block, pieces, mxu_block, comm, k, span,
                        bkt_src, bkt_dst, bkt_mask,
                        dyn_src, dyn_dst, dyn_mask,
                        mxu_src, mxu_dst, mxu_mask, diag_masks,
                        node_mask, out_degree, csr_pos, csr_offsets):
    """Build the adaptive wave-round closures shared by the run-to-coverage
    flood and the adaptive BFS loops: rounds with a small global frontier
    skip the ring entirely — the frontier rides as a replicated index
    list, and each shard gathers only ITS edges from those senders
    through the sender-CSR view, chunked into W-wide WORK ITEMS (O(k·W)
    work and one tiny all-gather, instead of O(E/S) bucket work and S
    ppermute hops). Budgeting is by out-edge mass: the sparse branch runs
    while the largest per-shard item count fits ``k``, so a hub whose row
    rivals the budget tips the round dense instead of widening every
    gather to its degree (the multi-chip mirror of
    models/adaptive_flood.py's hub tolerance); results stay bit-identical
    to the dense loop.

    Returns ``(sparse_round, dense_round, my_new_ids, item_budget,
    n_live)`` — both rounds map ``(seen, frontier, F, fncount, ficount)
    -> (seen, frontier, F, fncount, ficount, msgs)``."""
    pass_ = _make_or_pass(axis_name, S, block, pieces, mxu_block, comm,
                          bkt_src, bkt_dst, bkt_mask,
                          dyn_src, dyn_dst, dyn_mask,
                          mxu_src, mxu_dst, mxu_mask, diag_masks)
    node_mask_b, out_degree_b = node_mask[0], out_degree[0]
    csr_pos_b, csr_offsets_b = csr_pos[0], csr_offsets[0]
    flat_mask = bkt_mask[0].reshape(-1)
    flat_dst = bkt_dst[0].reshape(-1)
    dyn_src_b, dyn_dst_b, dyn_mask_b = dyn_src[0], dyn_dst[0], dyn_mask[0]
    has_dyn = dyn_src_b.shape[-1] > 0
    n_g = S * block
    pad_id = n_g - 1
    w = max(1, min(span, 128))  # work-item slice width
    my = jax.lax.axis_index(axis_name)
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )
    idx_k = jnp.arange(k, dtype=jnp.int32)

    def my_new_ids(new_local_mask, local_count):
        """This shard's new nodes as global ids, [k]-padded."""
        lpos = jnp.nonzero(new_local_mask, size=k, fill_value=block - 1)[0]
        return jnp.where(idx_k < local_count,
                         my * block + lpos.astype(jnp.int32), pad_id)

    def item_budget(F, ncount):
        """Replicated sparse-mode budget for frontier list ``F``: the
        largest per-shard W-slice work-item count (pmax), saturated past
        ``k`` when the node list itself overflowed (truncated F is never
        read). Every shard computes the identical value, so it can drive
        the replicated sparse/dense branch."""
        fvalid = idx_k < ncount
        f = jnp.where(fvalid, F, pad_id)
        row_len = csr_offsets_b[f + 1] - csr_offsets_b[f]
        items = jnp.where(fvalid, (row_len + w - 1) // w, 0)
        icount = jax.lax.pmax(jnp.sum(items).astype(jnp.int32), axis_name)
        return jnp.where(ncount > k, jnp.int32(k + 1), icount)

    def sparse_round(seen, frontier, F, fncount, ficount):
        msgs = jax.lax.psum(
            jnp.sum(jnp.where(frontier, out_degree_b, 0)), axis_name
        )
        # Expand the replicated node list into THIS shard's work items
        # (cumsum + searchsorted over k entries): item p covers slots
        # [base + slice*w, ...) of its owning node's local CSR row.
        fvalid = idx_k < fncount
        f = jnp.where(fvalid, F, pad_id)
        base_row = csr_offsets_b[f]
        row_end = csr_offsets_b[f + 1]
        if span <= w:
            # STATIC fast path (span and w are trace-time ints, the
            # engine's _one_item_per_node twin): no per-shard row chunks,
            # so item p IS node list entry p — in sparse mode the node
            # count is <= k by the budget's saturation, so the direct
            # mapping covers every entry and empty local rows simply
            # contribute no slots. Skips the cumsum + searchsorted.
            slot = base_row[:, None] + jnp.arange(w)[None, :]  # [k, w]
            svalid = (slot < row_end[:, None]) & fvalid[:, None]
        else:
            items_per = jnp.where(fvalid,
                                  (row_end - base_row + w - 1) // w, 0)
            offs = jnp.cumsum(items_per)
            starts = offs - items_per
            icount_local = offs[-1]
            j = jnp.clip(jnp.searchsorted(offs, idx_k, side="right"),
                         0, k - 1)
            ivalid = idx_k < icount_local
            base = base_row[j] + (idx_k - starts[j]) * w
            slot = base[:, None] + jnp.arange(w)[None, :]  # [k, w]
            svalid = (slot < row_end[j][:, None]) & ivalid[:, None]
        pos = csr_pos_b[jnp.where(svalid, slot, 0)]
        evalid = (svalid & flat_mask[pos]).reshape(-1)
        cand = jnp.where(evalid, flat_dst[pos].reshape(-1), block - 1)
        fresh = evalid & ~seen[cand] & node_mask_b[cand]
        if has_dyn:
            # Dynamic out-edges: reconstruct the global sender from the
            # ring step, membership-test against the frontier list via
            # binary search in the sorted list — O(E_dyn·log k), where the
            # naive broadcast compare is O(E_dyn·k) and can rival the
            # dense pass with a generous dynamic capacity (ADVICE r3).
            # The -1 sentinel (never a node id) keeps padded F entries
            # from matching a live spare node.
            t_i = jnp.arange(S, dtype=jnp.int32)[:, None]
            g_send = ((my - t_i) % S) * block + dyn_src_b
            probe = jnp.sort(jnp.where(fvalid, F, -1))
            j = jnp.clip(jnp.searchsorted(probe, g_send), 0, k - 1)
            member = (probe[j] == g_send) & dyn_mask_b
            dcand = jnp.where(member, dyn_dst_b, block - 1).reshape(-1)
            dfresh = (member.reshape(-1) & ~seen[dcand]
                      & node_mask_b[dcand])
            cand = jnp.concatenate([cand, dcand])
            fresh = jnp.concatenate([fresh, dfresh])
        # First-claim dedup onto this shard's node block (each shard owns
        # its receivers, so dedup is purely local).
        order = jnp.arange(cand.shape[0], dtype=jnp.int32)
        big = jnp.int32(2**31 - 1)
        claim = jnp.where(fresh, order, big)
        scratch = jnp.full(block, big, dtype=jnp.int32).at[cand].min(claim)
        winner = fresh & (scratch[cand] == order)
        local_count = jnp.sum(winner).astype(jnp.int32)
        seen = seen.at[jnp.where(fresh, cand, block)].set(True, mode="drop")
        frontier = (
            jnp.zeros(block, dtype=bool)
            .at[jnp.where(winner, cand, block)].set(True, mode="drop")
        )
        wpos = jnp.nonzero(winner, size=k, fill_value=cand.shape[0] - 1)[0]
        local_ids = jnp.where(idx_k < local_count,
                              my * block + cand[wpos], pad_id)
        F, ncount = _pack_global_frontier(axis_name, S, k, local_ids,
                                          local_count, pad_id)
        return seen, frontier, F, ncount, item_budget(F, ncount), msgs

    def dense_round(seen, frontier, F, fncount, ficount):
        msgs = jax.lax.psum(
            jnp.sum(jnp.where(frontier, out_degree_b, 0)), axis_name
        )
        delivered = pass_(frontier)
        new = delivered & ~seen & node_mask_b
        seen = seen | new
        local_count = jnp.sum(new).astype(jnp.int32)
        ncount = jax.lax.psum(local_count, axis_name)

        def compact(_):
            return _pack_global_frontier(
                axis_name, S, k, my_new_ids(new, local_count), local_count,
                pad_id,
            )[0]

        F = jax.lax.cond(ncount <= k, compact, lambda _: F, None)
        # item_budget saturates to k+1 when ncount > k, so the stale F of
        # the non-compacted branch is never trusted.
        return seen, new, F, ncount, item_budget(F, ncount), msgs

    return sparse_round, dense_round, my_new_ids, item_budget, n_live


def _ring_adaptive_cov_or(axis_name, S, block, pieces, mxu_block, comm, k, span,
                          coverage_target, max_rounds,
                          bkt_src, bkt_dst, bkt_mask,
                          dyn_src, dyn_dst, dyn_mask,
                          mxu_src, mxu_dst, mxu_mask, diag_masks,
                          node_mask, out_degree, csr_pos, csr_offsets,
                          seen0, frontier0):
    """Per-shard body: run-to-coverage flood on the adaptive wave rounds
    (see :func:`_make_adaptive_wave` for the work-item machinery)."""
    sparse_round, dense_round, my_new_ids, item_budget, n_live = (
        _make_adaptive_wave(axis_name, S, block, pieces, mxu_block, comm, k, span,
                            bkt_src, bkt_dst, bkt_mask,
                            dyn_src, dyn_dst, dyn_mask,
                            mxu_src, mxu_dst, mxu_mask, diag_masks,
                            node_mask, out_degree, csr_pos, csr_offsets)
    )
    node_mask_b = node_mask[0]
    pad_id = S * block - 1

    def cond(carry):
        _, _, _, _, _, rounds, covered, _, _, _ = carry
        return (covered / n_live < coverage_target) & (rounds < max_rounds)

    def body(carry):
        (seen, frontier, F, fncount, ficount, rounds, prev_covered,
         hi, lo, occ) = carry
        seen, frontier, F, fncount, ficount, msgs = jax.lax.cond(
            ficount <= k, sparse_round, dense_round,
            seen, frontier, F, fncount, ficount,
        )
        hi, lo = accum.add((hi, lo), msgs)
        covered = jax.lax.psum(
            jnp.sum((seen & node_mask_b).astype(jnp.int32)), axis_name
        )
        # Same ints as the dense loop and the engine (ops/frontier.py
        # occupancy) — the adaptive and dense summaries must stay
        # bit-identical (tests pin `out_a == out_d`). The new frontier's
        # live count IS the coverage delta, so no extra psum per round.
        occ = occ + ((covered - prev_covered) / n_live).astype(jnp.float32)
        return (seen, frontier, F, fncount, ficount, rounds + 1, covered,
                hi, lo, occ)

    seen_b, frontier_b = seen0[0], frontier0[0]
    count0 = jnp.sum(frontier_b).astype(jnp.int32)
    F0, ncount0 = _pack_global_frontier(
        axis_name, S, k, my_new_ids(frontier_b, count0), count0, pad_id
    )
    covered0 = jax.lax.psum(
        jnp.sum((seen_b & node_mask_b).astype(jnp.int32)), axis_name
    )
    init = (seen_b, frontier_b, F0, ncount0, item_budget(F0, ncount0),
            jnp.int32(0), covered0, *accum.zero(), jnp.float32(0.0))
    seen, frontier, _, _, _, rounds, covered, hi, lo, occ = jax.lax.while_loop(
        cond, body, init
    )
    return seen[None], frontier[None], accum.pack_summary(
        rounds, covered / n_live, (hi, lo),
        extra=occ / jnp.maximum(rounds, 1)
    )


@functools.lru_cache(maxsize=64)
def _flood_adaptive_cov_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                           max_rounds: int, k: int, span: int, pieces=(),
                           mxu_block: int = 128,
                           comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_adaptive_cov_or, axis_name, S, block,
                             pieces, mxu_block, comm, k, span)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        lambda target, *args: body(target, max_rounds, *args),
        mesh=mesh, check_vma=False,
        in_specs=(P(),) + (spec,) * 16,
        out_specs=(spec, spec, P()),
    )
    return jax.jit(fn)


def _ring_adaptive_cov_hopdist(axis_name, S, block, pieces, mxu_block, comm, k,
                               span, coverage_target, max_rounds,
                               bkt_src, bkt_dst, bkt_mask,
                               dyn_src, dyn_dst, dyn_mask,
                               mxu_src, mxu_dst, mxu_mask, diag_masks,
                               node_mask, out_degree, csr_pos, csr_offsets,
                               dist0, frontier0, round0):
    """Per-shard body: BFS on the adaptive wave rounds — loop semantics of
    :func:`_ring_coverage_hopdist` (stop on coverage, wave death, or
    max_rounds), wave mechanics of :func:`_make_adaptive_wave`. ``seen``
    is carried explicitly alongside ``dist`` so the round closures stay
    shared with the flood loop; the two are linked by ``seen == (dist >=
    0)`` at every step."""
    sparse_round, dense_round, my_new_ids, item_budget, n_live = (
        _make_adaptive_wave(axis_name, S, block, pieces, mxu_block, comm, k, span,
                            bkt_src, bkt_dst, bkt_mask,
                            dyn_src, dyn_dst, dyn_mask,
                            mxu_src, mxu_dst, mxu_mask, diag_masks,
                            node_mask, out_degree, csr_pos, csr_offsets)
    )
    node_mask_b = node_mask[0]
    pad_id = S * block - 1

    def cond(carry):
        _, _, _, _, fncount, _, rnd, covered, _, _ = carry
        return ((fncount > 0) & (rnd - round0 < max_rounds)
                & (covered / n_live < coverage_target))

    def body(carry):
        seen, dist, frontier, F, fncount, ficount, rnd, _, hi, lo = carry
        seen, frontier, F, fncount, ficount, msgs = jax.lax.cond(
            ficount <= k, sparse_round, dense_round,
            seen, frontier, F, fncount, ficount,
        )
        rnd = rnd + 1
        dist = jnp.where(frontier, rnd, dist)
        hi, lo = accum.add((hi, lo), msgs)
        covered = jax.lax.psum(
            jnp.sum((seen & node_mask_b).astype(jnp.int32)), axis_name
        )
        return seen, dist, frontier, F, fncount, ficount, rnd, covered, hi, lo

    dist_b, frontier_b = dist0[0], frontier0[0]
    seen_b = (dist_b >= 0) & node_mask_b
    count0 = jnp.sum(frontier_b).astype(jnp.int32)
    F0, ncount0 = _pack_global_frontier(
        axis_name, S, k, my_new_ids(frontier_b, count0), count0, pad_id
    )
    covered0 = jax.lax.psum(
        jnp.sum(seen_b.astype(jnp.int32)), axis_name
    )
    init = (seen_b, dist_b, frontier_b, F0, ncount0,
            item_budget(F0, ncount0), round0, covered0, *accum.zero())
    _, dist, frontier, _, _, _, rnd, covered, hi, lo = jax.lax.while_loop(
        cond, body, init
    )
    return dist[None], frontier[None], accum.pack_summary(
        rnd - round0, covered / n_live, (hi, lo)
    )


@functools.lru_cache(maxsize=64)
def _hopdist_adaptive_cov_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                             max_rounds: int, k: int, span: int, pieces=(),
                             mxu_block: int = 128,
                             comm: str = DEFAULT_COMM):
    body = functools.partial(_ring_adaptive_cov_hopdist, axis_name, S,
                             block, pieces, mxu_block, comm, k, span)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        lambda target, *args: body(target, max_rounds, *args),
        mesh=mesh, check_vma=False,
        in_specs=(P(),) + (spec,) * 16 + (P(),),
        out_specs=(spec, spec, P()),
    )
    return jax.jit(fn)


# ------------------------------------------------------------ random walks


def _make_walk_round(axis_name, S, block, W, span, restart_p,
                     bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                     node_mask, csr_pos, csr_offsets):
    """Per-shard walker-cohort round (models/walk.py, multi-chip).

    The cohort's positions ride REPLICATED [W]; each shard owns the
    edges INTO its node block, so it scores exactly the candidates the
    engine would gather for those receivers — through the per-shard
    sender-CSR over the bucket arrays (liveness re-masks and disconnects
    apply with no rebuild). Because every candidate's uniform is keyed
    by the edge IDENTITY (utils/edgehash.py), not its slot, the global
    argmax = pmax of per-shard maxima reproduces the engine's choice
    bit-for-bit: equal-u ties break on the higher receiver id, composed
    here as a second pmax over the per-shard best receivers among
    global-max holders.

    Returns ``one_round(pos, start, alive_start, visited_b, key) ->
    (pos, visited_b, moved, can_move, covered)``.
    """
    from p2pnetwork_tpu.utils.edgehash import edge_uniform

    node_mask_b = node_mask[0]
    csr_pos_b, csr_offsets_b = csr_pos[0], csr_offsets[0]
    flat_mask = bkt_mask[0].reshape(-1)
    flat_dst = bkt_dst[0].reshape(-1)
    dyn_src_b, dyn_dst_b, dyn_mask_b = dyn_src[0], dyn_dst[0], dyn_mask[0]
    has_dyn = dyn_src_b.shape[-1] > 0
    my = jax.lax.axis_index(axis_name)
    w = max(span, 1)
    walkers = jnp.arange(W, dtype=jnp.int32)

    def one_round(pos, start, alive_start, visited, key):
        # Same split as RandomWalks.step — the engine and every shard
        # derive identical sub-keys from the identical round key.
        k_edge, k_restart = jax.random.split(key)

        base = csr_offsets_b[pos]
        end = csr_offsets_b[pos + 1]
        slot = base[:, None] + jnp.arange(w)[None, :]
        svalid = slot < end[:, None]  # out-of-row slots masked (csr_pos
        # padding stays in bounds but can alias live slots — same
        # contract as the adaptive wave)
        p = csr_pos_b[jnp.where(svalid, slot, 0)]
        dst_local = flat_dst[p]
        rcv = my * block + dst_local
        live = svalid & flat_mask[p] & node_mask_b[dst_local]
        u = jnp.where(live,
                      edge_uniform(k_edge, walkers[:, None], pos[:, None],
                                   rcv),
                      -1.0)
        m_loc = jnp.max(u, axis=1)
        r_loc = jnp.max(jnp.where(live & (u == m_loc[:, None]), rcv, -1),
                        axis=1)
        if has_dyn:
            # Dynamic out-edges: reconstruct global senders from the ring
            # step, membership-test against the cohort ([W, S, K]).
            t_i = jnp.arange(S, dtype=jnp.int32)[:, None]
            g_send = ((my - t_i) % S) * block + dyn_src_b  # [S, K]
            member = ((g_send[None] == pos[:, None, None])
                      & dyn_mask_b[None]
                      & node_mask_b[dyn_dst_b][None])  # [W, S, K]
            drcv = jnp.broadcast_to((my * block + dyn_dst_b)[None],
                                    member.shape)
            du = jnp.where(member,
                           edge_uniform(k_edge, walkers[:, None, None],
                                        pos[:, None, None], drcv),
                           -1.0).reshape(W, -1)
            dm = jnp.max(du, axis=1)
            dr = jnp.max(jnp.where(
                member.reshape(W, -1) & (du == dm[:, None]),
                drcv.reshape(W, -1), -1), axis=1)
            r_loc = jnp.where(dm > m_loc, dr,
                              jnp.where(dm == m_loc, jnp.maximum(r_loc, dr),
                                        r_loc))
            m_loc = jnp.maximum(m_loc, dm)

        m = jax.lax.pmax(m_loc, axis_name)  # [W], replicated
        r = jax.lax.pmax(
            jnp.where((m_loc == m) & (m >= 0), r_loc, -1), axis_name
        )
        can_move = m >= 0.0
        dest = jnp.where(can_move, r, pos)

        if restart_p > 0.0:
            restart = (
                (jax.random.uniform(k_restart, (W,)) < restart_p)
                & alive_start
            )
            dest = jnp.where(restart, start, dest)
            moved = (restart | can_move) & (dest != pos)
        else:
            moved = can_move & (dest != pos)

        owned = (dest // block) == my
        visited = (
            visited.at[jnp.where(owned, dest % block, block)]
            .set(True, mode="drop")
            & node_mask_b
        )
        covered = jax.lax.psum(
            jnp.sum((visited & node_mask_b).astype(jnp.int32)), axis_name
        )
        return dest, visited, moved, can_move, covered

    return one_round


def _ring_rounds_walk(axis_name, S, block, W, span, restart_p,
                      bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                      node_mask, csr_pos, csr_offsets,
                      pos0, start0, alive_start, visited0, round_keys):
    one_round = _make_walk_round(axis_name, S, block, W, span, restart_p,
                                 bkt_dst, bkt_mask, dyn_src, dyn_dst,
                                 dyn_mask, node_mask, csr_pos, csr_offsets)
    node_mask_b = node_mask[0]
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )

    def body(carry, rkey):
        pos, visited = carry
        pos, visited, moved, can_move, covered = one_round(
            pos, start0, alive_start, visited,
            jax.random.wrap_key_data(rkey),
        )
        stats = {
            "messages": jnp.sum(moved),
            "coverage": covered / n_live,
            "stuck": jnp.sum(~can_move),
        }
        return (pos, visited), stats

    (pos, visited), stats = jax.lax.scan(body, (pos0, visited0[0]),
                                         round_keys)
    return pos, visited[None], stats


@functools.lru_cache(maxsize=64)
def _walk_fn(mesh: Mesh, axis_name: str, S: int, block: int,
             W: int, span: int, restart_p: float):
    """The scan length rides on round_keys' shape, so the round count is
    deliberately NOT part of this cache key (jit retraces on shape)."""
    body = functools.partial(_ring_rounds_walk, axis_name, S, block, W,
                             span, restart_p)
    spec = P(axis_name)
    fn = shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(spec,) * 8 + (P(), P(), P(), spec, P()),
        out_specs=(P(), spec, P()),
    )
    return jax.jit(fn)


def _ring_cov_walk(axis_name, S, block, W, span, restart_p, steps_per_round,
                   coverage_target, max_rounds,
                   bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                   node_mask, csr_pos, csr_offsets,
                   pos0, start0, alive_start, visited0, key_data):
    one_round = _make_walk_round(axis_name, S, block, W, span, restart_p,
                                 bkt_dst, bkt_mask, dyn_src, dyn_dst,
                                 dyn_mask, node_mask, csr_pos, csr_offsets)
    node_mask_b = node_mask[0]
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(node_mask_b.astype(jnp.int32)), axis_name), 1
    )

    def one_step(state):
        pos, visited, kd = state
        # Chained split, mirroring engine._stat_while round for round.
        k, sub = jax.random.split(jax.random.wrap_key_data(kd))
        pos, visited, moved, _, covered = one_round(
            pos, start0, alive_start, visited, sub
        )
        return (pos, visited, jax.random.key_data(k)), covered, \
            jnp.sum(moved)

    covered0 = jax.lax.psum(
        jnp.sum((visited0[0] & node_mask_b).astype(jnp.int32)), axis_name
    )
    (pos, visited, _), rounds, covered, (hi, lo) = _freeze_while(
        (pos0, visited0[0], key_data), covered0, one_step,
        lambda cov, r: (cov / n_live < coverage_target) & (r < max_rounds),
        steps_per_round)
    return pos, visited[None], accum.pack_summary(
        rounds, covered / n_live, (hi, lo)
    )


@functools.lru_cache(maxsize=64)
def _walk_cov_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                 max_rounds: int, W: int, span: int, restart_p: float,
                 steps_per_round: int = 1):
    body = functools.partial(_ring_cov_walk, axis_name, S, block, W, span,
                             restart_p, steps_per_round)
    spec = P(axis_name)
    fn = shard_map(
        lambda target, *args: body(target, max_rounds, *args),
        mesh=mesh, check_vma=False,
        in_specs=(P(),) + (spec,) * 8 + (P(), P(), P(), spec, P()),
        out_specs=(P(), spec, P()),
    )
    return jax.jit(fn)


def _walk_require_csr(sg: ShardedGraph):
    if sg.csr_pos is None:
        raise ValueError(
            "the sharded walk requires a sender-CSR sharded graph — build "
            "with shard_graph(source_csr=True)"
        )


def _walk_state0(sg: ShardedGraph, protocol):
    """RandomWalks.init parity on the sharded representation — a one-off
    host-side O(N) setup (eager jnp on mesh-sharded operands would trip
    sharding propagation outside a mesh context)."""
    mask = np.asarray(sg.node_mask).reshape(-1)
    n_pad = sg.n_shards * sg.block
    live_ids = np.flatnonzero(mask)
    if live_ids.size:
        n_live = live_ids.size
        stride = max(n_live // protocol.n_walkers, 1)
        pos = live_ids[
            (np.arange(protocol.n_walkers) * stride) % n_live
        ].astype(np.int32)
    else:
        pos = np.zeros(protocol.n_walkers, np.int32)
    visited = np.zeros(n_pad, dtype=bool)
    visited[pos] = True
    visited &= mask
    return (jnp.asarray(pos), jnp.asarray(pos),
            jnp.asarray(visited.reshape(sg.n_shards, sg.block)))


def _walk_call(sg: ShardedGraph, protocol, state0):
    """Shared argument marshalling for walk()/walk_until_coverage()."""
    if state0 is None:
        pos0, start0, visited0 = _walk_state0(sg, protocol)
    else:
        pos0, start0, visited0 = state0
    # Host-side gather for the same reason as _walk_state0.
    alive_start = jnp.asarray(
        np.asarray(sg.node_mask).reshape(-1)[np.asarray(start0)]
    )
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    common = (sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst, dyn_mask,
              sg.node_mask, sg.csr_pos, sg.csr_offsets)
    return common, pos0, start0, alive_start, visited0


def walk(sg: ShardedGraph, mesh: Mesh, protocol, key: jax.Array,
         rounds: int, axis_name: str = DEFAULT_AXIS, state0=None,
         return_state: bool = False):
    """Run ``rounds`` of the walker cohort (models/walk.py RandomWalks) on
    the sharded graph — bit-identical to ``engine.run(graph, protocol,
    key, rounds)`` for any shard count, because candidate draws are keyed
    by edge identity (utils/edgehash.py), not layout.

    Returns ``(visited [S, block] bool, stats dict of [rounds] arrays)``;
    with ``return_state=True``, ``((pos, start, visited), stats)`` — the
    resume triple ``walk_until_coverage`` also accepts.
    """
    _walk_require_csr(sg)
    S, block = sg.n_shards, sg.block
    common, pos0, start0, alive_start, visited0 = _walk_call(
        sg, protocol, state0)
    keys = jax.random.split(jax.random.fold_in(key, 1), rounds)
    fn = _walk_fn(mesh, axis_name, S, block, protocol.n_walkers,
                  max(sg.csr_span, 1), float(protocol.restart_p))
    pos, visited, stats = fn(*common, pos0, start0, alive_start, visited0,
                             jax.random.key_data(keys))
    if return_state:
        return (pos, start0, visited), stats
    return visited, stats


def walk_until_coverage(sg: ShardedGraph, mesh: Mesh, protocol,
                        key: jax.Array, *,
                        coverage_target: float = 0.99,
                        max_rounds: int = 1024,
                        steps_per_round: int = 1,
                        axis_name: str = DEFAULT_AXIS, state0=None,
                        return_state: bool = False):
    """Walk until the cohort has visited ``coverage_target`` of the live
    population — ``engine.run_until_coverage`` with RandomWalks,
    multi-chip, one XLA program (the discovery question: rounds to map
    the overlay). Same identity-keyed draws as :func:`walk`, so the
    trajectory is bit-identical to the engine loop's for any shard count.

    ``steps_per_round=T`` batches T walk rounds per while-loop iteration
    (bit-exact vs T=1, same contract as ``engine.run_until_coverage``) —
    the crawl is rounds-bound at a per-iteration floor set by dispatch
    and the ring's collectives, which T amortizes.

    Returns ``(visited, dict(rounds, coverage, messages))``; with
    ``return_state=True``, ``((pos, start, visited), dict)``.
    """
    _walk_require_csr(sg)
    if steps_per_round < 1:
        raise ValueError(
            f"steps_per_round must be >= 1, got {steps_per_round}")
    S, block = sg.n_shards, sg.block
    common, pos0, start0, alive_start, visited0 = _walk_call(
        sg, protocol, state0)
    fn = _walk_cov_fn(mesh, axis_name, S, block, max_rounds,
                      protocol.n_walkers, max(sg.csr_span, 1),
                      float(protocol.restart_p), int(steps_per_round))
    pos, visited, packed = fn(
        jnp.float32(coverage_target), *common, pos0, start0, alive_start,
        visited0, jax.random.key_data(key),
    )
    out = accum.unpack_summary(packed)
    if return_state:
        return (pos, start0, visited), out
    return visited, out


# --------------------------------------------- lane-word batched plane
#
# The PR-10 batched message plane packs 32 concurrent broadcast states per
# uint32 word (ops/bitset.py lane algebra; models/messagebatch.py). Here
# those lane words are the HALO PAYLOAD: the ring's resident block becomes
# ``u32[W, block]``, so ONE halo hop per ring step moves the boundary
# state of every in-flight message at once — 32·W messages per DMA — and
# the batched plane goes multi-chip without any new per-message traffic.


def _bucket_or_lanes(block, sorted_dst=True):
    """Word-level OR bucket for lane-packed payloads: the resident block
    is ``u32[W, block]``; one gather per word serves its 32 message
    lanes, and the per-edge OR is the bit-plane uint8 segment-max of
    ``ops/segment.propagate_or_lanes``'s segment method (word-level
    ``.at[].max`` cannot OR two different patterns landing on one
    receiver)."""
    from p2pnetwork_tpu.ops import bitset

    def apply(rot, src, dst, m):
        def word(wl):
            contrib = jnp.where(m, wl[src], jnp.uint32(0))
            planes = jax.ops.segment_max(
                bitset.expand_lanes(contrib).astype(jnp.uint8), dst,
                num_segments=block, indices_are_sorted=sorted_dst,
            )
            return bitset.collapse_lanes(planes > 0)

        return jax.vmap(word)(rot)

    return apply


def _make_or_lanes_pass(axis_name, S, block, comm,
                        bkt_src, bkt_dst, bkt_mask,
                        dyn_src, dyn_dst, dyn_mask):
    """Build ``pass_(lanes u32[W, block]) -> u32[W, block]``: one full
    ring rotation OR-ing every lane of every word over every incoming
    edge — :func:`_make_or_pass` lifted to the lane-packed carrier. The
    halo payload is the whole ``[W, block]`` word stack, so each ring
    step's single hop carries 32·W messages' boundary state. Segment
    buckets only (the MXU one-hot and diagonal layouts have no
    word-level form — callers gate)."""
    groups = [
        (_bucket_or_lanes(block, sorted_dst=True),
         bkt_src[0], bkt_dst[0], bkt_mask[0]),
        (_bucket_or_lanes(block, sorted_dst=False),
         dyn_src[0], dyn_dst[0], dyn_mask[0]),
    ]
    comm_obj = _make_ring_comm(comm, axis_name, S)

    def pass_(lanes):
        return _ring_pass(axis_name, S, lanes, groups,
                          jnp.zeros_like(lanes), jnp.bitwise_or,
                          comm=comm_obj)

    pass_.comm = comm_obj  # round-context handle for fault-wired loops
    return pass_


def _require_lanes_layout(sg: ShardedGraph, what: str) -> None:
    if sg.mxu_src is not None:
        raise ValueError(
            f"{what} cannot ride the MXU one-hot layout — shard_graph "
            "without hybrid/min_count for the lane-packed batched path "
            "(word-level OR has no one-hot-matmul form)"
        )


def _or_lanes_body(axis_name, S, block, comm,
                   bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                   node_mask, lanes):
    pass_ = _make_or_lanes_pass(axis_name, S, block, comm,
                                bkt_src, bkt_dst, bkt_mask,
                                dyn_src, dyn_dst, dyn_mask)
    nm = node_mask[0]
    node_lanes = jnp.where(nm, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    return (pass_(lanes[0]) & node_lanes[None, :])[None]


@functools.lru_cache(maxsize=64)
def _or_lanes_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                 comm: str = DEFAULT_COMM):
    body = functools.partial(_or_lanes_body, axis_name, S, block, comm)
    spec = P(axis_name)
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(body, mesh=mesh, check_vma=False,
                   in_specs=(spec,) * 8, out_specs=spec)
    return jax.jit(fn)


def shard_lanes(sg: ShardedGraph, lanes) -> jax.Array:
    """Place a lane-word stack ``u32[W, N_pad]`` (the single-device
    layout of ops/segment.propagate_or_lanes / MessageBatch predicates)
    on the mesh as ``[S, W, block]`` — node-blocked like every other
    sharded per-node array, zero-padding the node axis when the shard
    grid rounds it up."""
    lanes = jnp.asarray(lanes)
    w = lanes.shape[0]
    pad = sg.n_nodes_padded - lanes.shape[1]
    if pad:
        lanes = jnp.pad(lanes, ((0, 0), (0, pad)))
    blocked = lanes.reshape(w, sg.n_shards, sg.block).transpose(1, 0, 2)
    shard = NamedSharding(_mesh_of(sg), P(_mesh_of(sg).axis_names[0]))
    return jax.device_put(blocked, shard)


def unshard_lanes(sg: ShardedGraph, lanes: jax.Array,
                  n_pad: Optional[int] = None) -> jax.Array:
    """Inverse of :func:`shard_lanes`: ``[S, W, block] -> u32[W, n_pad]``
    (``n_pad`` defaults to the full shard grid ``S·block``)."""
    w = lanes.shape[1]
    flat = lanes.transpose(1, 0, 2).reshape(w, -1)
    return flat if n_pad is None else flat[:, :n_pad]


def propagate_or_lanes(sg: ShardedGraph, mesh: Mesh, lanes: jax.Array,
                       axis_name: str = DEFAULT_AXIS,
                       comm: str = DEFAULT_COMM) -> jax.Array:
    """Lane-packed neighbor-OR over the sharded graph: the multi-chip
    mirror of ``ops.segment.propagate_or_lanes`` — 32·W concurrent
    boolean signals advanced by one ring pass, the lane words as the
    halo payload. ``lanes`` is ``[S, W, block]`` (see
    :func:`shard_lanes`); returns the same layout, masked to live
    nodes. Dynamic (runtime-connected) edges fold in; requires the
    segment layout (no ``hybrid``/``min_count``)."""
    _require_lanes_layout(sg, "propagate_or_lanes")
    fn = _or_lanes_fn(mesh, axis_name, sg.n_shards, sg.block,
                      _resolve_comm(comm))
    dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
    return fn(sg.bkt_src, sg.bkt_dst, sg.bkt_mask,
              dyn_src, dyn_dst, dyn_mask, sg.node_mask, lanes)


def _ring_batch_cov(axis_name, S, block, comm, max_rounds,
                    bkt_src, bkt_dst, bkt_mask, dyn_src, dyn_dst, dyn_mask,
                    node_mask, out_degree,
                    seen0, frontier0, sent0, source, admitted, done0,
                    rounds0, seen_count0, target,
                    ring0=None, ici_round=None, fault_round0=None):
    """Per-shard body: advance EVERY running lane of a lane-packed batch
    until all admitted lanes complete (or ``max_rounds``) — the
    multi-chip mirror of ``engine._batch_loop`` + ``BatchFlood.step``,
    arithmetic-identical per lane: same ``new = delivered & ~seen &
    live`` dedup against node-masked kernels, same incremental
    transpose-popcount coverage numerator (psum'd across shards), same
    freeze/latch semantics, same per-word u32 send subtotals folded into
    the two-limb counter, same union-frontier occupancy ints. The ring's
    halo payload is the whole ``[W, block]`` word stack — one hop per
    ring step moves every in-flight message's boundary state."""
    from p2pnetwork_tpu.ops import bitset

    pass_ = _make_or_lanes_pass(axis_name, S, block, comm,
                                bkt_src, bkt_dst, bkt_mask,
                                dyn_src, dyn_dst, dyn_mask)
    # graftquake round context: a fault-spec comm keys its sites on the
    # GLOBAL round (fault_round0 + r), so chunked serving drivers hit
    # the same sites an unchunked run would.
    wire_faults = (fault_round0 is not None
                   and getattr(pass_.comm, "wants_step", False))
    nm = node_mask[0]
    node_lanes = jnp.where(nm, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    deg_u = out_degree[0].astype(jnp.uint32)
    n_live = jnp.maximum(
        jax.lax.psum(jnp.sum(nm.astype(jnp.int32)), axis_name), 1
    )

    def lane_counts_psum(words):  # u32[W, block] -> global i32[capacity]
        per = jax.vmap(bitset.lane_counts)(words).reshape(-1)
        return jax.lax.psum(per, axis_name)

    rec = ring0 is not None

    def cond(carry):
        done, r = carry[3], carry[6]
        return jnp.any(admitted & ~done) & (r < max_rounds)

    def body(carry):
        seen, frontier, sent, done, rounds_l, seen_count, r, hi, lo, occ = \
            carry[:10]
        if wire_faults:
            pass_.comm.set_context(round=fault_round0 + r)
        live = admitted & ~done
        live_mask = bitset.pack_bits(live)  # u32[W] replicated
        front = frontier & live_mask[:, None]
        delivered = pass_(front) & node_lanes[None, :]
        new = delivered & ~seen & live_mask[:, None]
        seen = seen | new
        sent = sent | front  # every frontier node broadcasts once
        # Per-word aggregate sends (u32-safe to E <= 2^27 globally, the
        # messagebatch contract) — psum'd per word, folded per word into
        # the exact two-limb total like engine._add_words.
        msgs_words = jax.lax.psum(
            jax.vmap(lambda f: jnp.sum(deg_u * jax.lax.population_count(f))
                     )(front),
            axis_name,
        )

        def fold(i, a):
            return accum.add(a, msgs_words[i])

        hi2, lo2 = jax.lax.fori_loop(0, msgs_words.shape[0], fold, (hi, lo))
        new_counts = lane_counts_psum(new)
        seen_count = seen_count + new_counts
        coverage = seen_count / n_live
        done = done | (admitted & (coverage >= target))
        rounds_l = rounds_l + live.astype(jnp.int32)
        next_mask = bitset.pack_bits(admitted & ~done)
        frontier = new & next_mask[:, None]
        # Union-frontier occupancy: the engine's exact ints
        # (ops/frontier.occupancy of the across-words OR), psum'd.
        union = jnp.any(frontier != 0, axis=0)
        occ_cnt = jax.lax.psum(
            jnp.sum((union & nm).astype(jnp.int32)), axis_name
        )
        occ = occ + (occ_cnt / n_live).astype(jnp.float32)
        out = (seen, frontier, sent, done, rounds_l, seen_count, r + 1,
               hi2, lo2, occ)
        if not rec:
            return out
        # Flight-recorder row: every value psum'd/replicated, so the
        # ring stays replicated (engine._batch_loop_rec's columns).
        return out + (flightrec.write_row(
            carry[10], r,
            occupancy=(occ_cnt / n_live).astype(jnp.float32),
            new=jnp.sum(msgs_words.astype(jnp.float32)),
            total=flightrec.total_f32(hi2, lo2),
            coverage=jnp.sum(seen_count.astype(jnp.float32)),
            active_lanes=jnp.sum((admitted & ~done).astype(jnp.int32)),
            ici_bytes=ici_round),)

    init = (seen0[0], frontier0[0], sent0[0], done0, rounds0, seen_count0,
            jnp.int32(0), *accum.zero(), jnp.float32(0.0))
    if rec:
        init = init + (ring0,)
    final = jax.lax.while_loop(cond, body, init)
    (seen, frontier, sent, done, rounds_l, seen_count, r, hi, lo, occ) = \
        final[:10]
    packed = accum.pack_batch_summary(
        r,
        jnp.sum((admitted & ~done).astype(jnp.int32)),
        jnp.sum(done.astype(jnp.int32)),
        (hi, lo),
        occ / jnp.maximum(r, 1),
        bitset.pack_bits(done),
        rounds_l,
    )
    out = (seen[None], frontier[None], sent[None], source, admitted, done,
           rounds_l, seen_count, target, packed)
    if rec:
        return out + (final[10],)
    return out


@functools.lru_cache(maxsize=64)
def _batch_cov_fn(mesh: Mesh, axis_name: str, S: int, block: int,
                  max_rounds: int, comm: str = DEFAULT_COMM,
                  donate: bool = False, rec: bool = False):
    """The compiled sharded batched-flood loop. ``donate=True`` builds
    the carry-donating variant (the 9 MessageBatch leaves alias the
    loop's buffers — the same contract engine's ``batch_from`` audits;
    graftaudit's donation audit covers this seam too). ``rec=True``
    appends the replicated flight ring + static per-round ICI estimate
    to the arguments and the ring to the outputs; the ring joins the
    donated carry."""
    body = functools.partial(_ring_batch_cov, axis_name, S, block, comm,
                             max_rounds)
    spec = P(axis_name)
    # A fault-spec comm (graftquake) appends the global first-round
    # scalar LAST — after the recorder pair when present — so the
    # donated carry indices below never move and string-comm programs
    # keep their exact pre-fault signature.
    faulty = not isinstance(comm, str)
    wrapped = body if not faulty else (
        lambda *a: body(*a[:-1], fault_round0=a[-1]))
    # check_vma=False: see the note on the sibling ring-body factories.
    fn = shard_map(
        wrapped, mesh=mesh, check_vma=False,
        in_specs=(spec,) * 11 + (P(),) * 6 + ((P(), P()) if rec else ())
        + ((P(),) if faulty else ()),
        out_specs=(spec,) * 3 + (P(),) * 6 + (P(),)
        + ((P(),) if rec else ()),
    )
    donate_argnums = ()
    if donate:
        # The 9 MessageBatch carry leaves — plus the flight ring when
        # recording (arg 17; the trailing ICI scalar is not a carry).
        donate_argnums = tuple(range(8, 17)) + ((17,) if rec else ())
    return jax.jit(fn, donate_argnums=donate_argnums)


def _shard_batch_args(sg: ShardedGraph, batch):
    """Marshal a MessageBatch onto the mesh: packed predicates blocked
    ``[S, W, block]`` (node axis zero-padded to the shard grid), per-lane
    metadata replicated."""
    mesh = _mesh_of(sg)
    rep = NamedSharding(mesh, P())
    put = lambda x: jax.device_put(jnp.asarray(x), rep)  # noqa: E731
    return (
        shard_lanes(sg, batch.seen), shard_lanes(sg, batch.frontier),
        shard_lanes(sg, batch.sent),
        put(batch.source), put(batch.admitted), put(batch.done),
        put(batch.rounds), put(batch.seen_count), put(batch.target),
    )


def run_batch_until_coverage(sg: ShardedGraph, mesh: Mesh, protocol,
                             batch, key=None, *,
                             max_rounds: int = 1024,
                             axis_name: str = DEFAULT_AXIS,
                             comm: str = DEFAULT_COMM,
                             donate: bool = True, recorder=None,
                             fault_round0: int = 0):
    """Advance ALL in-flight messages of a lane-packed batch on the
    SHARDED graph until every admitted lane reaches its coverage target —
    ``engine.run_batch_until_coverage`` on the multi-chip ring, one XLA
    program, the lane words as the halo payload (one hop per ring step
    moves 32·W messages' boundary state; ``comm`` picks ppermute or the
    Pallas ring-DMA kernels).

    ``batch`` is a plain single-device
    :class:`~p2pnetwork_tpu.models.messagebatch.MessageBatch` (built by
    ``protocol.init`` / ``admit`` against the UNSHARDED graph — the
    admission control plane stays host-side); it is marshalled onto the
    mesh per call and the returned batch is back in the single-device
    layout, so ``admit``/``retire``/``lane_seen`` and the engine loop
    interoperate freely. Per-lane results, round counts and the summary
    dict are BIT-IDENTICAL to the engine loop on the same batch
    (tests/test_ring.py pins the sweep). ``protocol`` supplies the
    entry-refresh semantics; its ``method`` is not consulted — the
    sharded path has exactly one lane lowering (segment buckets over the
    ring), like :func:`flood` vs ``Flood.method``. ``key`` is accepted
    for engine-signature symmetry and unused (the batched flood is
    deterministic). Requires the segment layout (no
    ``hybrid``/``min_count``).

    ``donate=True`` donates the loop's mesh-resident carry buffers —
    and, exactly like the engine loop's contract, treats the passed-in
    ``batch`` as CONSUMED (marshalling may alias rather than copy a
    leaf, e.g. replicated metadata on a host-backed mesh, so a donated
    run can invalidate it; resuming it raises the engine's friendly
    deleted-buffer error). Pass ``donate=False`` to keep reading the
    pre-run batch or to run the same batch through several loops — the
    parity tests do.

    ``recorder`` rides the per-round flight ring in the donated
    replicated carry (``ici_bytes`` column = this config's static
    per-round comm-census estimate) and attaches
    ``out["flight_record"]``; results stay bit-identical on both comm
    backends. The trace plane's ``batch_run`` span and per-lane
    lifecycle events mirror the engine loop's (``loop="sharded"``).

    ``comm`` also accepts a graftquake
    :class:`~p2pnetwork_tpu.chaos.device.FaultSpec` — seeded halo-hop
    faults keyed on the global round ``fault_round0 + r`` (chunked
    drivers pass ``fault_round0`` = the batch's cumulative round so
    chunk boundaries never move a fault site), counted into
    ``chaos_device_faults_total{kind}`` after the run.
    """
    from p2pnetwork_tpu.chaos import device as chaos_device
    from p2pnetwork_tpu.sim import engine as _engine

    chaos_device.dispatch_gate("sharded-batch")
    _require_lanes_layout(sg, "sharded run_batch_until_coverage")
    del key  # engine-signature symmetry; the batched flood draws nothing
    t0 = time.perf_counter()
    _engine._check_not_donated(batch)
    done0 = np.asarray(batch.done)
    tracer = spans.current_tracer()
    admitted0 = np.asarray(batch.admitted) if tracer is not None else None
    rounds0 = np.asarray(batch.rounds) if tracer is not None else None
    with spans.span("batch_run", loop="sharded", max_rounds=max_rounds):
        if tracer is not None:
            _engine._emit_batch_entry_events(admitted0, done0, rounds0)
        # Entry-time refresh — the batched cov0 seeding
        # (BatchFlood.refresh), against the sharded graph's CURRENT node
        # mask, host-fetched once: eager jnp on mesh-sharded operands
        # outside a mesh context trips sharding propagation (the
        # _walk_state0 rule), and refresh replaces only the two small
        # metadata leaves.
        from p2pnetwork_tpu.ops import bitset

        nm_host = _host_fetch(sg.node_mask).reshape(-1)[: batch.seen.shape[1]]
        node_lanes = jnp.where(jnp.asarray(nm_host), jnp.uint32(0xFFFFFFFF),
                               jnp.uint32(0))
        seen_count = jax.vmap(bitset.lane_counts)(
            batch.seen & node_lanes[None, :]).reshape(-1)
        n_live = jnp.maximum(jnp.int32(int(nm_host.sum())), 1)
        done = batch.done | (batch.admitted
                             & (seen_count / n_live >= batch.target))
        batch = dataclasses.replace(batch, seen_count=seen_count, done=done)

        resolved = _resolve_comm(comm)
        fn = _batch_cov_fn(mesh, axis_name, sg.n_shards, sg.block,
                           max_rounds, resolved, bool(donate),
                           rec=recorder is not None)
        dyn_src, dyn_dst, dyn_mask = _dyn_or_empty(sg)
        args = (sg.bkt_src, sg.bkt_dst, sg.bkt_mask, dyn_src, dyn_dst,
                dyn_mask, sg.node_mask, sg.out_degree,
                *_shard_batch_args(sg, batch))
        ftail = () if isinstance(resolved, str) \
            else (jnp.int32(fault_round0),)
        ring = None
        if recorder is None:
            (seen, frontier, sent, source, admitted, done, rounds_l,
             seen_count, target, packed) = fn(*args, *ftail)
        else:
            n_words = int(batch.seen.shape[0])
            base_fn = _batch_cov_fn(mesh, axis_name, sg.n_shards, sg.block,
                                    max_rounds, resolved, False)
            ici = _rec_ici_round_bytes(
                ("batch", mesh, axis_name, sg.n_shards, sg.block, resolved,
                 n_words),
                lambda: (base_fn, (*args, *ftail), sg.n_shards))
            (seen, frontier, sent, source, admitted, done, rounds_l,
             seen_count, target, packed, ring) = fn(
                *args, recorder.init(), jnp.float32(ici), *ftail)
        t1 = time.perf_counter()
        n_pad = batch.seen.shape[1]
        nbytes = sum(int(getattr(leaf, "nbytes", 0))
                     for leaf in jax.tree_util.tree_leaves((packed, ring)))
        if ring is not None:
            packed, ring = jax.device_get((packed, ring))
        out = accum.unpack_batch_summary(packed, int(batch.seen.shape[0]))
        _record_comm_faults(resolved, out["rounds"], sg.n_shards,
                            round0=fault_round0)
        if ring is not None:
            out["flight_record"] = flightrec.trim(ring, out["rounds"])
        batch = dataclasses.replace(
            batch,
            seen=unshard_lanes(sg, seen, n_pad),
            frontier=unshard_lanes(sg, frontier, n_pad),
            sent=unshard_lanes(sg, sent, n_pad),
            source=source, admitted=admitted, done=done, rounds=rounds_l,
            seen_count=seen_count, target=target,
        )
        t2 = time.perf_counter()
        newly = out["lane_done"] & ~done0
        # Engine-contract parity: the lanes completed in THIS call (the
        # serving front-end's harvest set) ride the summary here too.
        out["newly_completed_lanes"] = np.flatnonzero(newly).astype(np.int32)
        newly_rounds = out["lane_rounds"][newly]
        if newly_rounds.size:
            out["completion_rounds_p50"] = float(
                np.percentile(newly_rounds, 50))
            out["completion_rounds_p99"] = float(
                np.percentile(newly_rounds, 99))
        if tracer is not None:
            _engine._emit_batch_exit_events(admitted0, done0, out)
        # One summary-bridging site (engine's): shared sim_* counters under
        # loop="batch", batch gauges/histograms, occupancy recency pruning.
        _engine._record_batch_summary(t2 - t0, t2 - t1, nbytes, out,
                                      newly_rounds, type(protocol).__name__)
    return batch, out

"""The ring's host layout as ``parallel/sharded.shard_graph`` built it
before the layout moved to ``parallel/ringlayout.py``: a lexsort of every
edge into its bucket and a per-offset pass over each diagonal. Kept as
the oracle the tests hold ``ringlayout.build`` to, array for array."""

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2pnetwork_tpu.parallel.mesh import DEFAULT_AXIS
from p2pnetwork_tpu.parallel.sharded import ShardedGraph
from p2pnetwork_tpu.sim.graph import Graph, _round_up


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


def shard_graph_oracle(graph: Graph, mesh: Mesh, axis_name: str = DEFAULT_AXIS,
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

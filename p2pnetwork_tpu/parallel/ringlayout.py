"""The ring layout: a graph's edges partitioned for an ``S``-shard ring,
built on the host.

``parallel/sharded.py`` runs a propagation round as ``S`` ring steps. Shard
``d`` owns nodes ``[d * block, (d + 1) * block)``, and every edge it
receives sits in bucket ``(d, t)``, where ``t = (d - sender_shard) mod S``
is the ring step at which the sender's frontier block is resident on
``d``. This module derives from the edges alone everything the ring needs
of them:

- the bucketing: each edge's local sender and receiver ids in bucket order
  (within a bucket, receivers non-decreasing, ties in edge order), and the
  buckets' offsets;
- for the ``hybrid`` attach layout, the circular diagonals
  (``ops/diag.py``'s selection) decomposed into static ring pieces with
  their masks, and the remainder's per-bucket MXU blocks.

Like the single-chip ``hybrid`` and ``blocked`` layouts it is a function of
the edges and ``S`` alone, so ``sim.graph.from_edges(ring_shards=S)``
builds it with the graph and the layout cache keeps it
(``sim/checkpoint.py``). ``sharded.shard_graph`` pads and places a layout
the graph carries, or builds one here through the same functions.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from p2pnetwork_tpu.ops.blocked import build_blocked_arrays_np
from p2pnetwork_tpu.ops.diag import kept_offsets

#: Destination-block width of the ring's MXU remainder (ops/diag.py's
#: remainder block: less padding waste than 128).
MXU_BLOCK = 512

#: A ring-step code no edge of a kept diagonal carries.
_NO_DIAG = 255


def ring_block(n_nodes_padded: int, n_shards: int) -> int:
    """Nodes per shard: the padded population split into ``n_shards``
    equal contiguous blocks."""
    return -(-n_nodes_padded // n_shards)


class RingLayout:
    """What an ``S``-shard ring needs of a graph's edges, as host arrays.

    ``src``/``dst`` are every edge's local sender and receiver ids in
    bucket order (bucket ``b = d * S + t`` holds ``offsets[b]:
    offsets[b + 1]``). With ``hybrid``, ``diag_pieces`` /
    ``diag_masks [S, P, block]`` are the diagonals' static
    ``(ring_step, local_shift)`` pieces (``ShardedGraph.diag_pieces``) and
    ``mxu_* [S, S, NB, W]`` the remainder's MXU blocks; ``max_diags`` /
    ``min_count`` are the selection's arguments. ``n_edges`` is the number
    of edges it was built from: a graph whose edges are no longer exactly
    those is sharded afresh.

    Not a pytree: a :class:`~p2pnetwork_tpu.sim.graph.Graph` carries it as
    a static field, so its arrays never ride a jitted call; equality is
    identity.
    """

    __slots__ = ("n_shards", "block", "n_edges", "src", "dst", "offsets",
                 "hybrid", "max_diags", "min_count", "diag_pieces",
                 "diag_masks", "mxu_src", "mxu_dst", "mxu_mask")

    def __init__(self, *, n_shards: int, block: int, n_edges: int,
                 src: np.ndarray, dst: np.ndarray, offsets: np.ndarray,
                 hybrid: bool = False, max_diags: int = 64,
                 min_count: Optional[int] = None,
                 diag_pieces: Tuple[Tuple[int, int], ...] = (),
                 diag_masks: Optional[np.ndarray] = None,
                 mxu_src: Optional[np.ndarray] = None,
                 mxu_dst: Optional[np.ndarray] = None,
                 mxu_mask: Optional[np.ndarray] = None):
        self.n_shards, self.block, self.n_edges = n_shards, block, n_edges
        self.src, self.dst, self.offsets = src, dst, offsets
        self.hybrid, self.max_diags, self.min_count = (hybrid, max_diags,
                                                       min_count)
        self.diag_pieces, self.diag_masks = tuple(diag_pieces), diag_masks
        self.mxu_src, self.mxu_dst, self.mxu_mask = mxu_src, mxu_dst, mxu_mask

    @property
    def counts(self) -> np.ndarray:
        """Edges per bucket, ``[S * S]``."""
        return np.diff(self.offsets)

    def bucket(self, d: int, t: int) -> slice:
        b = d * self.n_shards + t
        return slice(int(self.offsets[b]), int(self.offsets[b + 1]))

    def mxu_blocks(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The MXU blocks of every edge (the ``mxu`` attach layout; a
        ``hybrid`` layout stores its remainder's instead)."""
        return _mxu_blocks(self, None)


def _partition(senders, receivers, S: int, block: int, flags=None):
    """Bucket order of receiver-sorted edges: a stable partition of each
    destination shard's (contiguous) edges by ring step. Returns ``(src,
    dst, offsets, flags)``: local ids in bucket order, the buckets'
    offsets, and the per-edge ``flags`` (bool) carried into bucket order
    (``None`` without)."""
    n_e = senders.shape[0]
    src = np.empty(n_e, np.int32)
    dst = np.empty(n_e, np.int32)
    out_flags = None if flags is None else np.empty(n_e, bool)
    offsets = np.zeros(S * S + 1, np.int64)
    # int32 probes: searchsorted would cast the whole haystack to another
    # dtype first.
    bounds = np.searchsorted(receivers, np.minimum(
        np.arange(S + 1, dtype=np.int64) * block, 2**31 - 1).astype(np.int32))
    bounds[-1] = n_e
    pos = 0
    for d in range(S):
        lo, hi = int(bounds[d]), int(bounds[d + 1])
        s_d, r_d = senders[lo:hi], receivers[lo:hi]
        # The ring step of each edge into shard d: (d - sender shard) mod S.
        step_of = np.array([(d - x) % S for x in range(S)], np.uint8)
        step = step_of[s_d // np.int32(block)]
        # Step 0 (the shard's own senders) first, by one pass: on a
        # small-world overlay it holds most edges; the rest split by step.
        local = step == 0
        rest = np.flatnonzero(~local)
        rest_step = step[rest]
        del step
        for t in range(S):
            sel = local if t == 0 else rest[rest_step == t]
            end = pos + (int(np.count_nonzero(sel)) if t == 0 else sel.size)
            np.subtract(s_d[sel], np.int32(((d - t) % S) * block),
                        out=src[pos:end])
            np.subtract(r_d[sel], np.int32(d * block), out=dst[pos:end])
            if flags is not None:
                out_flags[pos:end] = flags[lo:hi][sel]
            pos = end
            offsets[d * S + t + 1] = pos
    return src, dst, offsets, out_flags


def _diagonals(senders, receivers, n: int, S: int, block: int,
               max_diags: int, min_count: Optional[int]):
    """The dominant circular diagonals of receiver-sorted edges,
    decomposed into static ring pieces: ``(pieces, masks [S, P, block] or
    None, diag_sel)``, ``diag_sel`` flagging the edges the pieces cover.

    The selection is ``ops/diag.select_diagonals``'s (its kept offsets, one
    edge per (offset, receiver)); an edge whose signed offset wraps the
    real-node boundary (``v + off_s`` outside ``[0, n)``) stays in the
    remainder: only a diagonal's no-wrap body has the shard-invariant
    piece structure. Piece ``(t, r)`` of offset ``off_s`` (``q, r =
    divmod(off_s, block)``) covers local receivers ``j`` with ``j + r <
    block`` at step ``-q mod S``, the rest at step ``-q - 1 mod S``."""
    n_e = senders.shape[0]
    # (s - r) mod n without a division: ids are < n, so the difference
    # lies in (-n, n) and its sign bit picks whether to add n.
    off = np.subtract(senders, receivers)
    off += (off >> 31) & np.int32(n)
    if n_e and (int(senders.max()) >= n or int(receivers[-1]) >= n):
        # Padded ids are no candidates: offset 0 is never kept.
        off[(senders >= n) | (receivers >= n)] = 0
    kept = kept_offsets(off, n, max_diags, min_count)
    if not kept:
        return (), None, np.zeros(n_e, dtype=bool)
    code_of = np.full(max(int(off.max()) + 1, 1), _NO_DIAG, np.uint8)
    code_of[kept] = np.arange(len(kept), dtype=np.uint8)
    code = code_of[off]
    del off, code_of
    diag_sel = code != _NO_DIAG
    n_pad = S * block
    cand = np.flatnonzero(diag_sel)
    wide = np.int64 if len(kept) * n_pad >= 2**31 else np.int32
    flat = code[cand].astype(wide)
    flat *= n_pad
    flat += receivers[cand]
    covered = np.zeros((len(kept), n_pad), dtype=bool)
    covered.reshape(-1)[flat] = True
    if int(np.count_nonzero(covered)) != cand.size:
        # Some (offset, receiver) pair has several edges: a mask slot holds
        # one, the first; the others stay in the remainder.
        _, first = np.unique(flat, return_index=True)
        diag_sel[:] = False
        diag_sel[cand[first]] = True
    del flat, cand
    signed = [o if o <= n // 2 else o - n for o in kept]
    for k, off_s in enumerate(signed):
        # The receivers whose edge on this diagonal wraps the boundary
        # form one range; receivers are sorted, so their edges are a slice.
        lo_r, hi_r = (n - off_s, n) if off_s > 0 else (0, -off_s)
        a, b = np.searchsorted(receivers, np.array((lo_r, hi_r), np.int32))
        diag_sel[a:b] &= code[a:b] != k
        covered[k, lo_r:hi_r] = False
    del code
    pieces, rows = [], []
    for k, off_s in enumerate(signed):
        dmask = covered[k].reshape(S, block)
        if not dmask.any():
            continue
        q, r = divmod(off_s, block)
        t_a, t_b = (-q) % S, (-q - 1) % S
        if S == 1 or t_a == t_b:
            pieces.append((t_a, r))
            rows.append((dmask, slice(None)))
            continue
        for t, cols in ((t_a, slice(0, block - r)),
                        (t_b, slice(block - r, block))):
            if dmask[:, cols].any():
                pieces.append((t, r))
                rows.append((dmask, cols))
    if not pieces:
        return (), None, diag_sel
    masks = np.zeros((S, len(pieces), block), dtype=bool)
    for p, (dmask, cols) in enumerate(rows):
        masks[:, p, cols] = dmask[:, cols]
    return tuple(pieces), masks, diag_sel


def _mxu_blocks(layout: RingLayout, keep: Optional[np.ndarray]):
    """Per-bucket MXU blocks of the layout's edges (those ``keep`` flags,
    in bucket order, or all), stacked ``[S, S, NB, W]``."""
    S, block = layout.n_shards, layout.block
    per_bucket = []
    for d in range(S):
        for t in range(S):
            sl = layout.bucket(d, t)
            s_b, r_b = layout.src[sl], layout.dst[sl]
            if keep is not None:
                k_b = keep[sl]
                s_b, r_b = s_b[k_b], r_b[k_b]
            per_bucket.append(build_blocked_arrays_np(s_b, r_b, block,
                                                      MXU_BLOCK))
    nb = max(bs.shape[0] for bs, _, _ in per_bucket)
    w = max(bs.shape[1] for bs, _, _ in per_bucket)
    mxu_src = np.zeros((S, S, nb, w), np.int32)
    mxu_dst = np.zeros((S, S, nb, w), np.int32)
    mxu_mask = np.zeros((S, S, nb, w), bool)
    for i, (bs, bd, bm) in enumerate(per_bucket):
        d, t = divmod(i, S)
        r, c = bs.shape
        mxu_src[d, t, :r, :c] = bs
        mxu_dst[d, t, :r, :c] = bd
        mxu_mask[d, t, :r, :c] = bm
    return mxu_src, mxu_dst, mxu_mask


def build(senders: np.ndarray, receivers: np.ndarray, n_nodes: int,
          n_nodes_padded: int, n_shards: int, *, hybrid: bool = True,
          max_diags: int = 64,
          min_count: Optional[int] = None) -> RingLayout:
    """The ring layout of host edge arrays (``receivers`` non-decreasing,
    live edges only) for ``n_shards`` shards; ``hybrid`` adds the
    diagonal pieces and the remainder's MXU blocks
    (``ops/diag.select_diagonals``'s ``max_diags`` / ``min_count``)."""
    senders = np.ascontiguousarray(senders, dtype=np.int32)
    receivers = np.ascontiguousarray(receivers, dtype=np.int32)
    S = int(n_shards)
    block = ring_block(n_nodes_padded, S)
    diag_pieces, diag_masks, keep = (), None, None
    if hybrid:
        diag_pieces, diag_masks, diag_sel = _diagonals(
            senders, receivers, n_nodes, S, block, max_diags, min_count)
        keep = ~diag_sel
        del diag_sel
    src, dst, offsets, keep = _partition(senders, receivers, S, block, keep)
    layout = RingLayout(n_shards=S, block=block, n_edges=senders.shape[0],
                        src=src, dst=dst, offsets=offsets, hybrid=hybrid,
                        max_diags=max_diags, min_count=min_count,
                        diag_pieces=diag_pieces, diag_masks=diag_masks)
    if hybrid:
        layout.mxu_src, layout.mxu_dst, layout.mxu_mask = _mxu_blocks(
            layout, keep)
    return layout

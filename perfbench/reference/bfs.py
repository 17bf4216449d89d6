"""Plain reference for a flood: a NumPy breadth-first search.

``reference_run`` and ``seen_sha256`` are copied from the program's
``chip_smoke.py``, which checked them against the chip; its
``bfs_levels`` scanned every edge at every level and is kept here as
``_scan_levels``, the oracle the tests hold the sender-CSR search to.
``messages`` is new here: a synchronous flood's message count, which is
the out-degree summed over every node that broadcast. This module imports
nothing of the program and reads only the benchmark's own edge lists
(``perfbench/overlays.py``).
"""

from __future__ import annotations

import hashlib

import numpy as np


def sender_csr(src: np.ndarray, dst: np.ndarray, n: int):
    """Out-edges grouped by sender: ``(offsets, neighbors)``, where node
    ``v``'s out-neighbours are ``neighbors[offsets[v]:offsets[v + 1]]``
    (int64 offsets, int32 neighbours)."""
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=offsets[1:])
    neighbors = dst[np.argsort(src, kind="stable")].astype(np.int32,
                                                            copy=False)
    return offsets, neighbors


def bfs_levels(edges, source: int) -> np.ndarray:
    """Hop distance from ``source`` over ``edges = (offsets, neighbors,
    node_mask)`` (a :func:`sender_csr` and the live nodes; -1: never
    reached), one level per synchronous flood round. Each level reads only
    the out-edges of the nodes it reached last, so a search reads every
    edge once."""
    offsets, neighbors, node_mask = edges
    n = node_mask.shape[0]
    dist = np.full(n, -1, np.int32)
    if not node_mask[source]:
        return dist
    dist[source] = 0
    frontier = np.array([source], np.int64)
    hit = np.zeros(n, bool)
    level = 0
    while frontier.size:
        level += 1
        starts = offsets[frontier]
        counts = offsets[frontier + 1] - starts
        ends = np.cumsum(counts)
        # The frontier's rows, concatenated: row i's slots start at
        # starts[i] and sit at [ends[i] - counts[i], ends[i]) of the list.
        slots = np.repeat(starts - (ends - counts), counts)
        slots += np.arange(slots.size)
        hit[:] = False
        hit[neighbors[slots]] = True
        hit &= dist < 0
        hit &= node_mask
        frontier = np.flatnonzero(hit)
        dist[frontier] = level
    return dist


def _scan_levels(src: np.ndarray, dst: np.ndarray, node_mask: np.ndarray,
                 source: int) -> np.ndarray:
    """:func:`bfs_levels` by a scan of every directed edge at every level
    (``chip_smoke.py``'s search): the tests' oracle."""
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


def reference_run(dist: np.ndarray, n_live: int, target: float,
                  max_rounds: int):
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


def messages(dist: np.ndarray, out_degree: np.ndarray, rounds: int) -> int:
    """Messages a synchronous flood sends in ``rounds`` rounds: every node
    first reached before the last round broadcasts once to each neighbour."""
    sent = (dist >= 0) & (dist < rounds)
    return int(out_degree[sent].sum(dtype=np.int64))


def seen_sha256(seen: np.ndarray) -> str:
    """sha256 of the packed seen bits (the serving plane's digest)."""
    return hashlib.sha256(np.packbits(seen.astype(np.uint8)).tobytes()
                          ).hexdigest()


def answer(edges, out_degree: np.ndarray, n_live: int, source: int,
           target: float, max_rounds: int, n_bits: int = 0) -> dict:
    """What a correct flood from ``source`` returns; the seen digest is
    over ``n_bits`` nodes (the program's padded node range: the pad is
    never reached)."""
    dist = bfs_levels(edges, source)
    rounds, seen = reference_run(dist, n_live, target, max_rounds)
    padded = np.zeros(max(n_bits, seen.size), bool)
    padded[:seen.size] = seen
    return {"rounds": rounds, "seen_count": int(seen.sum()),
            "seen_sha256": seen_sha256(padded),
            "messages": messages(dist, out_degree, rounds)}

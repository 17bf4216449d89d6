"""The sender-CSR search gives the edge scan's distances, and the answers
built on it are the scan's."""

import numpy as np
import pytest

import graphs
import overlays
from reference import bfs

N = 3000


def _overlay(generator: str):
    config = {"generator": generator, "n": N, "k": 10, "p": 0.1, "m": 5,
              "graph_seed": 0}
    lo, hi, n = overlays.undirected_edges(config)
    return graphs.Overlay(lo, hi, n), overlays.directed(lo, hi)


def _isolated_and_masked():
    """A path 0-1-2-3-4 with a branch 2-5, node 6 isolated and node 3
    masked out: 4 is reachable only through 3."""
    lo = np.array([0, 1, 2, 3, 2], np.int32)
    hi = np.array([1, 2, 3, 4, 5], np.int32)
    overlay = graphs.Overlay(lo, hi, 7)
    overlay.node_mask[3] = False
    return overlay, overlays.directed(lo, hi)


def _cases():
    sources = np.random.default_rng(7).choice(N, size=8, replace=False)
    cases = [(gen, int(s)) for gen in ("watts_strogatz", "barabasi_albert")
             for s in sources]
    return cases + [("isolated_and_masked", s) for s in (0, 3, 4, 6)]


@pytest.mark.parametrize("graph,source", _cases())
def test_csr_search_gives_the_scans_distances(graph, source):
    if graph == "isolated_and_masked":
        overlay, (src, dst) = _isolated_and_masked()
    else:
        overlay, (src, dst) = _overlay(graph)
    want = bfs._scan_levels(src, dst, overlay.node_mask, source)
    got = bfs.bfs_levels(overlay.edges, source)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    if graph == "isolated_and_masked":
        # 3 is masked: the source itself reaches nothing, and 4 lies
        # behind it; 6 is isolated.
        assert (got[3] == -1) and (got[4] == -1 or source == 4)
        assert got[6] == (0 if source == 6 else -1)


@pytest.mark.parametrize("generator", ["watts_strogatz", "barabasi_albert"])
def test_answers_are_the_scans(generator):
    overlay, (src, dst) = _overlay(generator)
    out_degree = np.bincount(src, minlength=N).astype(np.int64)
    np.testing.assert_array_equal(overlay.out_degree, out_degree)
    for source in (0, 17, 2999):
        dist = bfs._scan_levels(src, dst, overlay.node_mask, source)
        rounds, seen = bfs.reference_run(dist, N, 0.99, 64)
        padded = np.zeros(N + 72, bool)
        padded[:N] = seen
        want = {"rounds": rounds, "seen_count": int(seen.sum()),
                "seen_sha256": bfs.seen_sha256(padded),
                "messages": bfs.messages(dist, out_degree, rounds)}
        assert bfs.answer(overlay.edges, overlay.out_degree, N, source,
                          0.99, 64, N + 72) == want


def test_overlay_keeps_no_edge_list_and_builds_its_csr_on_first_use():
    overlay, (src, _) = _overlay("watts_strogatz")
    assert not hasattr(overlay, "src") and not hasattr(overlay, "dst")
    assert overlay._csr is None and overlay.directed_edges == src.size
    offsets, neighbors, _ = overlay.edges
    assert overlay._pairs is None
    assert offsets.dtype == np.int64 and neighbors.dtype == np.int32
    assert offsets[-1] == neighbors.size == src.size
    assert overlay.edges[1] is neighbors

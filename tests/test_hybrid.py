"""Hybrid (diagonal + blocked remainder) aggregation vs the segment
reference — exact OR equality and close sum agreement on graphs with full,
partial, and zero diagonal structure."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from p2pnetwork_tpu.models import Flood  # noqa: E402
from p2pnetwork_tpu.ops import diag as D  # noqa: E402
from p2pnetwork_tpu.ops import segment  # noqa: E402
from p2pnetwork_tpu.sim import engine  # noqa: E402
from p2pnetwork_tpu.sim import graph as G  # noqa: E402


@pytest.fixture(params=["ws", "er", "ba", "ring"])
def graph(request):
    make = {
        # min_count floor is 128, so structured families here are built big
        # enough that their lattice diagonals actually get extracted.
        "ws": lambda: G.watts_strogatz(400, 6, 0.2, seed=0),
        "er": lambda: G.erdos_renyi(500, 0.02, seed=1),
        "ba": lambda: G.barabasi_albert(300, 4, seed=2),
        "ring": lambda: G.ring(257),
    }[request.param]
    return make().with_hybrid()


class TestHybridRepresentation:
    def test_partition_is_lossless(self, graph):
        """Diagonal edges + remainder edges == all edges, none counted twice."""
        h = graph.hybrid
        n_rem = (
            0 if h.remainder is None else int(np.asarray(h.remainder.mask).sum())
        )
        assert h.n_diag_edges + n_rem == graph.n_edges

    def test_diagonal_masks_match_edges(self, graph):
        """Every masked (offset, v) slot is a real edge (v+off)%n -> v."""
        h = graph.hybrid
        emask = np.asarray(graph.edge_mask)
        s = np.asarray(graph.senders)[emask]
        r = np.asarray(graph.receivers)[emask]
        edges = set(zip(s.tolist(), r.tolist()))
        masks = np.asarray(h.masks)
        for d, off in enumerate(h.offsets):
            for v in np.nonzero(masks[d])[0]:
                assert ((v + off) % h.n, v) in edges

    def test_ring_has_no_remainder(self):
        g = G.ring(257).with_hybrid()
        assert g.hybrid.remainder is None
        assert set(g.hybrid.offsets) == {1, 257 - 1}

    def test_er_has_no_diagonals(self):
        g = G.erdos_renyi(500, 0.02, seed=1).with_hybrid()
        assert g.hybrid.offsets == ()


class TestHybridEquality:
    def test_or_matches_segment(self, graph):
        key = jax.random.key(0)
        signal = jax.random.uniform(key, (graph.n_nodes_padded,)) < 0.15
        signal = signal & graph.node_mask
        ref = segment.propagate_or(graph, signal, "segment")
        out = segment.propagate_or(graph, signal, "hybrid")
        assert (np.asarray(out) == np.asarray(ref)).all()

    def test_sum_matches_segment(self, graph):
        key = jax.random.key(1)
        x = jax.random.normal(key, (graph.n_nodes_padded,), dtype=jnp.float32)
        x = x * graph.node_mask
        ref = np.asarray(segment.propagate_sum(graph, x, "segment"))
        out = np.asarray(segment.propagate_sum(graph, x, "hybrid"))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_flood_end_to_end(self, graph):
        ref_state, _ = engine.run(graph, Flood(source=0, method="segment"),
                                  jax.random.key(0), 5)
        state, _ = engine.run(graph, Flood(source=0, method="hybrid"),
                              jax.random.key(0), 5)
        assert (np.asarray(state.seen) == np.asarray(ref_state.seen)).all()


def test_hybrid_requires_representation():
    g = G.ring(200)
    sig = jnp.zeros(g.n_nodes_padded, dtype=bool)
    with pytest.raises(ValueError, match="with_hybrid"):
        segment.propagate_or(g, sig, "hybrid")


def test_wraparound_offsets_padded_nodes():
    # n not a multiple of the 128 padding: the circular shift must wrap at n,
    # not at n_padded, or boundary nodes read padding slots.
    n = 300
    g = G.ring(n).with_hybrid()
    assert g.n_nodes_padded > n
    sig = jnp.zeros(g.n_nodes_padded, dtype=bool).at[0].set(True)
    out = np.asarray(segment.propagate_or(g, sig, "hybrid"))
    expect = np.zeros(g.n_nodes_padded, dtype=bool)
    expect[[1, n - 1]] = True
    assert (out == expect).all()


def test_duplicate_edges_counted_exactly():
    # Regression: a mask slot can hold only one edge per (offset, receiver);
    # duplicate user-supplied edges must spill to the remainder, not vanish.
    n = 300
    base = np.arange(n, dtype=np.int32)
    s = np.concatenate([base, (base + 1) % n, [5, 5]])
    r = np.concatenate([(base + 1) % n, base, [6, 6]])
    g = G.from_edges(s, r, n).with_hybrid()
    ones = jnp.ones(g.n_nodes_padded, dtype=jnp.float32) * g.node_mask
    ref = np.asarray(segment.propagate_sum(g, ones, "segment"))
    out = np.asarray(segment.propagate_sum(g, ones, "hybrid"))
    np.testing.assert_allclose(out, ref, rtol=1e-5)
    assert out[6] == 4.0  # ring both sides + two duplicates


def test_max_diags_cap_spills_to_remainder():
    g = G.watts_strogatz(400, 6, 0.0, seed=0)  # 6 pure lattice diagonals
    capped = g.with_hybrid(max_diags=2)
    assert len(capped.hybrid.offsets) == 2
    assert capped.hybrid.remainder is not None
    key = jax.random.key(0)
    sig = (jax.random.uniform(key, (g.n_nodes_padded,)) < 0.2) & g.node_mask
    full = g.with_hybrid()
    out_capped = segment.propagate_or(capped, sig, "hybrid")
    out_full = segment.propagate_or(full, sig, "hybrid")
    assert (np.asarray(out_capped) == np.asarray(out_full)).all()


def test_self_loops_do_not_displace_diagonals():
    # Regression (ADVICE r1, low): offset-0 (self-loop) filtering happened
    # AFTER the max_diags truncation, so frequent self-loops could evict a
    # qualifying real diagonal into the per-edge remainder.
    from p2pnetwork_tpu.ops.diag import build_hybrid_from_arrays

    n = 256
    base = np.arange(n, dtype=np.int32)
    # offset 0 on every node (count n), offset 1 (count n), offset 2 (n-1).
    s = np.concatenate([base, (base + 1) % n, ((base + 2) % n)[:-1]])
    r = np.concatenate([base, base, base[:-1]])
    order = np.argsort(r, kind="stable")
    h = build_hybrid_from_arrays(s[order], r[order], n, n,
                                 max_diags=2, min_count=16)
    assert sorted(h.offsets) == [1, 2]


# -- auto's OR route ---------------------------------------------------------

def _ws(hybrid):
    return G.watts_strogatz(2048, 10, 0.1, seed=0, hybrid=hybrid)


def _ba_hybrid():
    return G.barabasi_albert(2048, 3, seed=0, hybrid=True)


def _ws_on_mesh():
    from p2pnetwork_tpu.parallel import auto
    from p2pnetwork_tpu.parallel import mesh as M

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    g = auto.shard_graph_auto(_ws(True), M.ring_mesh(8))
    assert g.hybrid.remainder_kernel == "blocked"
    return g


# (make graph, aggregation, backend, lowering auto must resolve to).
_ROUTES = {
    "ws_hybrid_tpu": (lambda: _ws(True), "or", "tpu", "hybrid"),
    "ws_plain_tpu": (lambda: _ws(False), "or", "tpu", "gather"),
    "ba_hybrid_tpu": (_ba_hybrid, "or", "tpu", "segment"),
    "ws_hybrid_cpu": (lambda: _ws(True), "or", "cpu", "gather"),
    "ba_hybrid_cpu": (_ba_hybrid, "or", "cpu", "segment"),
    "ring_hybrid_cpu": (lambda: G.ring(1024, hybrid=True), "or", "cpu",
                        "gather"),
    "ws_mesh_tpu": (_ws_on_mesh, "or", "tpu", "gather"),
    "ws_hybrid_tpu_sum": (lambda: _ws(True), "sum", "tpu", "gather"),
    "ws_hybrid_tpu_max": (lambda: _ws(True), "max", "tpu", "gather"),
    "ws_hybrid_tpu_min_plus": (lambda: _ws(True), "min_plus", "tpu",
                               "gather"),
}


@pytest.mark.parametrize("case", sorted(_ROUTES))
def test_auto_route(case, monkeypatch):
    """OR takes the hybrid lowering only on a TPU, only when the layout's
    diagonals hold at least half the edges, and never for a layout placed
    on a mesh; every other graph, backend and aggregation routes as
    ``_auto_method`` always has."""
    make, op, backend, expect = _ROUTES[case]
    g = make()
    share = (0.0 if g.hybrid is None
             else g.hybrid.n_diag_edges / g.n_edges)
    if case.startswith("ws_hybrid"):
        assert share > 0.8
    if case.startswith("ba_hybrid"):
        assert share < segment._HYBRID_DIAG_SHARE
    if op == "or":
        assert segment._auto_or_method(g, backend=backend) == expect
        if expect != "hybrid":
            assert segment._auto_method(g) == expect
        return
    # Sum, max and min-plus never consult the backend: on a (faked) TPU
    # their auto program is still the one of the lowering _auto_method names.
    assert segment._auto_method(g) == expect
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    fn = {"sum": segment.propagate_sum, "max": segment.propagate_max,
          "min_plus": segment.propagate_min_plus}[op]
    x = jnp.zeros(g.n_nodes_padded, jnp.float32)

    def program(method):
        return str(jax.make_jaxpr(lambda v: fn(g, v, method))(x))

    assert program("auto") == program(expect)


def _route_count(method):
    from p2pnetwork_tpu import telemetry

    return telemetry.default_registry().value(
        "aggregation_route_total", op="or", method=method)


def test_auto_route_counter_counts_traced_programs():
    g = _ws(True)
    fn = jax.jit(lambda s: segment.propagate_or(g, s, "auto"))
    sig = jnp.zeros(g.n_nodes_padded, dtype=bool).at[3].set(True)
    before = _route_count("gather")
    fn(sig)
    fn(sig)  # the same program again: no new trace, no new count
    assert _route_count("gather") == before + 1


def test_forced_hybrid_flood_equals_gather(monkeypatch):
    """The TPU route on the CPU (Pallas interpreter): a flood to 99%
    through auto's hybrid choice gives the gather's rounds, messages and
    seen set, from several sources."""
    import functools

    g = G.watts_strogatz(3000, 10, 0.1, seed=5, hybrid=True)
    monkeypatch.setattr(segment, "_auto_or_method", functools.partial(
        segment._auto_or_method, backend="tpu"))
    key = jax.random.key(0)
    for source in (0, 1499, 2999):
        before = _route_count("hybrid")
        st_h, sum_h = engine.run_until_coverage(g, Flood(source=source), key)
        assert _route_count("hybrid") > before  # the route was traced
        st_g, sum_g = engine.run_until_coverage(
            g, Flood(source=source, method="gather"), key)
        assert sum_h["rounds"] == sum_g["rounds"] > 1
        assert sum_h["messages"] == sum_g["messages"]
        assert sum_h["coverage"] == sum_g["coverage"] >= 0.99
        assert (np.asarray(st_h.seen) == np.asarray(st_g.seen)).all()

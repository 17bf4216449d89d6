"""The ring layout (parallel/ringlayout.py): built with the graph
(``from_edges(ring_shards=S)``), kept by the layout cache, placed by
``sharded.shard_graph`` array for array as the graph's own edges would be
sharded, and kept across attaches by ``JaxSimNode``; the ring's spans and
counters."""

import dataclasses

import jax
import numpy as np
import pytest

from p2pnetwork_tpu import telemetry
from p2pnetwork_tpu.models.flood import Flood
from p2pnetwork_tpu.parallel import commviz, mesh as M, sharded
from p2pnetwork_tpu.sim import checkpoint, engine, failures, layoutcache
from p2pnetwork_tpu.sim import graph as G
from p2pnetwork_tpu.sim import topology
from p2pnetwork_tpu.sim.simnode import JaxSimNode
from p2pnetwork_tpu.telemetry import spans
from tests.ring_oracle import shard_graph_oracle

N = 3000
LAYOUTS = {"hybrid": dict(hybrid=True), "mxu": dict(mxu=True),
           "segment": {}, "hybrid+csr": dict(hybrid=True, source_csr=True)}


def _edges(kind: str):
    g = (G.watts_strogatz(N, 10, 0.1, seed=0) if kind == "ws"
         else G.barabasi_albert(N, 5, seed=0))
    live = np.asarray(g.edge_mask)
    return np.asarray(g.senders)[live], np.asarray(g.receivers)[live]


def _graphs(kind: str, S: int):
    """The same overlay without and with its ring layout for ``S``."""
    s, r = _edges(kind)
    return G.from_edges(s, r, N), G.from_edges(s, r, N, ring_shards=S)


def _variant(graph, name: str):
    if name == "masked node":  # node_mask only; the edges stay live
        return failures.mark_unresponsive(graph, [7, 1500])
    if name == "failed node":  # the node's edges masked too
        return failures.fail_nodes(graph, [7, 1500])
    if name == "dynamic edges":
        graph = topology.with_capacity(graph, extra_edges=64)
        return topology.connect(graph, [3, 11, 2999], [2100, 640, 4])
    return graph


def assert_same(a, b):
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, jax.Array) or isinstance(y, jax.Array):
            assert x is not None and y is not None, f.name
            assert x.dtype == y.dtype and x.shape == y.shape, f.name
            assert x.sharding == y.sharding, f.name
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f.name)
        else:
            assert x == y, f.name


def shard_counts() -> dict:
    fam = telemetry.default_registry().snapshot().get("sim_shard_total")
    if fam is None:
        return {}
    return {s["labels"]["result"]: s["value"] for s in fam["samples"]}


def moved_since(before: dict) -> dict:
    now = shard_counts()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("variant", ["plain", "masked node", "failed node",
                                     "dynamic edges"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("S", [2, 4])
@pytest.mark.parametrize("kind", ["ws", "ba"])
def test_a_carried_layout_shards_as_the_plain_graph_and_the_oracle(
        kind, S, layout, variant):
    plain, ringed = (_variant(g, variant) for g in _graphs(kind, S))
    mesh = M.ring_mesh(S)
    kw = LAYOUTS[layout]
    want = shard_graph_oracle(plain, mesh, **kw)
    assert_same(sharded.shard_graph(plain, mesh, **kw), want)
    before = shard_counts()
    assert_same(sharded.shard_graph(ringed, mesh, **kw), want)
    # The layout serves while exactly its edges are live.
    fits = variant in ("plain", "masked node")
    assert moved_since(before) == {"from_layout" if fits else "built": 1}


def test_a_layout_for_other_shards_is_not_used():
    plain, ringed = _graphs("ws", 2)
    mesh = M.ring_mesh(4)
    before = shard_counts()
    assert_same(sharded.shard_graph(ringed, mesh, hybrid=True),
                shard_graph_oracle(plain, mesh, hybrid=True))
    assert moved_since(before) == {"built": 1}


def test_edge_changes_drop_the_layout():
    _, ringed = _graphs("ws", 4)
    assert ringed.ring is not None
    assert G.from_edges(*_edges("ws"), N).ring is None
    delta = G.GraphDelta.undirected(add_senders=[1], add_receivers=[2000])
    assert G.apply_delta(ringed, delta).ring is None
    assert G.grow(ringed, 10).ring is None


def _ring_arrays(ring) -> dict:
    return {name: getattr(ring, name)
            for name in ("src", "dst", "offsets", "diag_masks", "mxu_src",
                         "mxu_dst", "mxu_mask")}


def assert_same_layout(a, b):
    for name in ("n_shards", "block", "n_edges", "hybrid", "max_diags",
                 "min_count", "diag_pieces"):
        assert getattr(a, name) == getattr(b, name), name
    for name, x in _ring_arrays(a).items():
        y = getattr(b, name)
        assert type(y) is np.ndarray and x.dtype == y.dtype, name
        assert x.tobytes() == y.tobytes(), name


def test_the_layout_survives_save_and_load(tmp_path):
    _, ringed = _graphs("ws", 4)
    path = str(tmp_path / "g.npz")
    checkpoint.save_graph(path, ringed)
    loaded = checkpoint.load_graph(path)
    assert_same_layout(ringed.ring, loaded.ring)
    mesh = M.ring_mesh(4)
    before = shard_counts()
    assert_same(sharded.shard_graph(loaded, mesh, hybrid=True),
                sharded.shard_graph(ringed, mesh, hybrid=True))
    assert moved_since(before) == {"from_layout": 2}


def test_the_layout_cache_keeps_the_layout(tmp_path):
    s, r = _edges("ws")
    built = []

    def build():
        built.append(1)
        return G.from_edges(s, r, N, ring_shards=4)
    first, _, hit1 = layoutcache.cached_graph(
        "ring", build, cache_dir=str(tmp_path), params={"ring_shards": 4})
    again, _, hit2 = layoutcache.cached_graph(
        "ring", build, cache_dir=str(tmp_path), params={"ring_shards": 4})
    assert (hit1, hit2, len(built)) == (False, True, 1)
    assert_same_layout(first.ring, again.ring)
    assert "parallel/ringlayout.py" in layoutcache.DEFAULT_SOURCES


# ------------------------------------------------------ attach on the mesh


def _node():
    return JaxSimNode("127.0.0.1", 0)


def test_attach_reuses_the_shard_of_the_same_graph_and_flags():
    plain, ringed = _graphs("ws", 4)
    mesh = M.ring_mesh(4)
    node = _node()
    before = shard_counts()
    node.attach_simulation(ringed, Flood(source=0), mesh=mesh)
    kept = node.sim_sharded
    node.attach_simulation(ringed, Flood(source=17), mesh=mesh)
    node.attach_simulation(ringed, Flood(source=2500), mesh=mesh)
    assert node.sim_sharded is kept
    assert moved_since(before) == {"from_layout": 1, "reused": 2}
    before = shard_counts()
    node.attach_simulation(plain, Flood(source=0), mesh=mesh)  # new graph
    node.attach_simulation(plain, Flood(source=0), mesh=mesh,
                           layout="mxu")  # new flag
    mxu = node.sim_sharded
    node.attach_simulation(plain, Flood(source=0), mesh=mesh,
                           layout="mxu", dynamic_edges=16)
    node.attach_simulation(plain, Flood(source=3), mesh=mesh,
                           layout="mxu", dynamic_edges=16)
    # The kept static shard, with a fresh dynamic region on top.
    assert moved_since(before) == {"built": 2, "reused": 2}
    assert node.sim_sharded.dyn_capacity == 16
    assert node.sim_sharded.bkt_src is mxu.bkt_src
    assert mxu.dyn_capacity == 0


def test_a_reattach_after_failures_floods_as_a_fresh_node():
    _, ringed = _graphs("ws", 4)
    mesh = M.ring_mesh(4)
    node = _node()
    node.attach_simulation(ringed, Flood(source=5), mesh=mesh)
    node.run_until_coverage(0.99, max_rounds=64)
    node.fail_sim_nodes(list(range(100, 900)))
    node.inject_sim_churn(0.2, seed=3)
    damaged = node.run_until_coverage(0.99, max_rounds=64)
    before = shard_counts()
    node.attach_simulation(ringed, Flood(source=1234), mesh=mesh)
    assert moved_since(before) == {"reused": 1}
    got = node.run_until_coverage(0.99, max_rounds=64)
    fresh = _node()
    fresh.attach_simulation(ringed, Flood(source=1234), mesh=mesh)
    want = fresh.run_until_coverage(0.99, max_rounds=64)
    assert got == want and got != damaged
    np.testing.assert_array_equal(np.asarray(node.sim_state[0]),
                                  np.asarray(fresh.sim_state[0]))


@pytest.mark.parametrize("source", [0, 1499, 2999])
def test_the_ring_flood_matches_the_one_chip_engine(source):
    plain, ringed = _graphs("ws", 4)
    node = _node()
    node.attach_simulation(ringed, Flood(source=source), mesh=M.ring_mesh(4))
    got = node.run_until_coverage(0.99, max_rounds=64)
    state, want = engine.run_until_coverage(plain, Flood(source=source),
                                            jax.random.key(0),
                                            coverage_target=0.99,
                                            max_rounds=64)
    for k in ("rounds", "messages", "coverage"):
        assert got[k] == np.asarray(want[k]).item(), k
    np.testing.assert_array_equal(
        np.asarray(node.sim_state[0]).reshape(-1)[:plain.n_nodes_padded],
        np.asarray(state.seen))


# ------------------------------------------------------ spans and counters


def _counter(name: str) -> float:
    fam = telemetry.default_registry().snapshot().get(name)
    return fam["samples"][0]["value"] if fam else 0.0


@pytest.mark.parametrize("layout", ["hybrid", "segment"])
def test_halo_bytes_are_the_comm_census_per_round(layout):
    _, ringed = _graphs("ws", 4)
    mesh = M.ring_mesh(4)
    sg = sharded.shard_graph(ringed, mesh, **LAYOUTS[layout])
    seed = sharded._flood_seed(sg, 0)
    fn = sharded._flood_cov_fn(mesh, mesh.axis_names[0], sg.n_shards,
                               sg.block, 64, sg.diag_pieces, sg.mxu_block,
                               "ppermute")
    args = (jax.numpy.float32(0.99), sg.bkt_src, sg.bkt_dst, sg.bkt_mask,
            *sharded._dyn_or_empty(sg), *sharded._mxu_or_empty(sg),
            sharded._diag_masks_or_empty(sg), sg.node_mask, sg.out_degree,
            seed, seed)
    census = commviz.jaxpr_comm_census(fn, args, sg.n_shards)
    per_round = sharded.halo_bytes_per_round(sg, seed)
    assert per_round == 3 * sg.block
    # The while body counts once: its ppermutes are one round's halo.
    assert census["ppermute"]["bytes"] == per_round

    rounds0 = _counter("sim_ring_rounds_total")
    bytes0 = _counter("sim_ring_halo_bytes_total")
    _, out = sharded.flood_until_coverage(sg, mesh, 0, max_rounds=64)
    rounds = _counter("sim_ring_rounds_total") - rounds0
    assert rounds == out["rounds"] > 0
    assert _counter("sim_ring_halo_bytes_total") - bytes0 == \
        rounds * per_round


def test_the_ring_flood_runs_under_its_dispatch_and_wait_spans():
    _, ringed = _graphs("ws", 4)
    mesh = M.ring_mesh(4)
    sg = sharded.shard_graph(ringed, mesh, hybrid=True)
    tracer = spans.Tracer()
    prev = spans.install_tracer(tracer)
    try:
        sharded.flood_until_coverage(sg, mesh, 0, max_rounds=64)
    finally:
        spans.install_tracer(prev)
    names = [sp.name for sp in tracer.spans()]
    assert names.index("run_dispatch") < names.index("ring_wait")
    got = {sp.name: sp for sp in tracer.spans()}
    assert got["run_dispatch"].args == {"loop": "ring"}
    assert got["run_dispatch"].t1 <= got["ring_wait"].t0

"""Placement: a configuration that places its overlay on a four-chip ring
runs through ``run.run_cell`` on four CPU devices, with the program's graph
built on the host until the program shards it; a configuration without a
placement builds what it built before."""

import copy

import jax
import numpy as np
import pytest

import graphs
import harness
import overlays
import run
import strata
from p2pnetwork_tpu.parallel import sharded
from p2pnetwork_tpu.sim import graph as G
from test_correctness import make_ctx

N = 3000
RING = {"mesh": "ring"}


def ring_ctx(placement=RING, chips: int = 4):
    """A copy of ``ws-1m.flood`` at 3,000 nodes placed on a ring."""
    ctx = make_ctx("ws-1m.flood")
    ctx.config["placement"] = dict(placement)
    ctx.cell = dict(ctx.cell, chips=chips)
    ctx.device = dict(ctx.device, count=chips)
    return ctx


def host_leaves(graph) -> bool:
    """Every array of ``graph`` is a NumPy array, held by no device."""
    leaves = jax.tree_util.tree_leaves(graph)
    return bool(leaves) and all(type(x) is np.ndarray for x in leaves)


@pytest.fixture
def built_on(monkeypatch):
    """The devices that hold the arrays of each graph the layout cache
    builds or loads, before the harness turns them into NumPy arrays."""
    from p2pnetwork_tpu.sim import layoutcache

    seen = []
    real = layoutcache.cached_graph

    def cached_graph(*a, **kw):
        graph, *rest = real(*a, **kw)
        seen.append(set().union(*(x.devices() for x in
                                  jax.tree_util.tree_leaves(graph)
                                  if isinstance(x, jax.Array))))
        return (graph, *rest)
    monkeypatch.setattr(layoutcache, "cached_graph", cached_graph)
    return seen


def on_the_host_alone(devices, mesh) -> bool:
    """``devices`` is the host device, which is no device of ``mesh``."""
    host = graphs.host_device()
    return devices == {host} and host not in set(mesh.devices.flat)


@pytest.fixture(autouse=True)
def small_strata(monkeypatch):
    real = strata.compute
    monkeypatch.setattr(strata, "load",
                        lambda config, mix: real(config, mix, 64)["by_rounds"])


@pytest.fixture
def sharded_inputs(monkeypatch):
    """The graphs the program's ring entry is handed to shard."""
    seen = []
    real = sharded.shard_graph

    def shard_graph(graph, mesh, *a, **kw):
        seen.append((graph, mesh))
        return real(graph, mesh, *a, **kw)
    monkeypatch.setattr(sharded, "shard_graph", shard_graph)
    return seen


def test_a_ring_placed_copy_is_correct_and_never_on_a_chip_unsharded(
        sharded_inputs, built_on):
    ctx = ring_ctx()
    line = run.run_cell(ctx)
    assert line["correct"], line["checks"]
    assert line["checks"]["checked"]["value"] >= 1
    assert ctx.mesh.devices.size == 4
    assert ctx.graph_info["on_host"]
    assert len(built_on) == 1 and on_the_host_alone(built_on[0], ctx.mesh)
    # Every flood's attach handed the program the host graph and the ring.
    assert len(sharded_inputs) == \
        line["attempted"] + ctx.traffic["warmup_floods"]
    for graph, mesh in sharded_inputs:
        assert host_leaves(graph)
        assert mesh is ctx.mesh


def _exchange_left_out(monkeypatch):
    monkeypatch.setattr(sharded._RingComm, "shift", lambda self, x: x)


def _altered_ring_answer(monkeypatch):
    real = sharded.flood_until_coverage

    def flood_until_coverage(*a, **kw):
        (seen, frontier), out = real(*a, **kw)
        flat = np.asarray(seen).reshape(-1).copy()
        flat[int(np.flatnonzero(flat)[0])] = False
        return (jax.numpy.asarray(flat.reshape(seen.shape)), frontier), out
    monkeypatch.setattr(sharded, "flood_until_coverage", flood_until_coverage)


@pytest.mark.parametrize("fault", [_exchange_left_out, _altered_ring_answer],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_fault_makes_the_ring_copy_not_correct(monkeypatch, fault):
    jax.clear_caches()
    sharded._flood_cov_fn.cache_clear()
    fault(monkeypatch)
    try:
        line = run.run_cell(ring_ctx())
    finally:
        jax.clear_caches()
        sharded._flood_cov_fn.cache_clear()
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("placement,chips", [
    (RING, 1), ({"mesh": "ring", "shards": 4}, 4), ({"mesh": "grid"}, 4)])
def test_a_placement_off_the_cells_chips_is_refused_before_any_graph(
        monkeypatch, placement, chips):
    def no_graph(*a, **kw):
        raise AssertionError("a graph was built")
    monkeypatch.setattr(overlays, "undirected_edges", no_graph)
    monkeypatch.setattr(graphs, "build", no_graph)
    with pytest.raises(ValueError, match="placement"):
        run.run_cell(ring_ctx(placement, chips))


def test_the_layout_cache_restores_a_ring_graph_onto_the_host(
        monkeypatch, tmp_path, built_on):
    monkeypatch.setattr(harness, "LAYOUT_CACHE_DIR", str(tmp_path))
    config = ring_ctx().config
    built, _, info = graphs.build(config, on_host=True)
    loaded, _, info2 = graphs.build(config, on_host=True)
    assert not info["layout_cache_hit"] and info2["layout_cache_hit"]
    # Built on a miss and loaded on a hit, the graph sits on the host and
    # not on the default device, which a ring would hold.
    ring = graphs.mesh(config, 4)
    assert jax.devices()[0] in set(ring.devices.flat)
    assert len(built_on) == 2
    assert all(on_the_host_alone(d, ring) for d in built_on)
    assert host_leaves(built) and host_leaves(loaded)
    for a, b in zip(jax.tree_util.tree_leaves(built),
                    jax.tree_util.tree_leaves(loaded)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("config_name", ["ws-1m", "ba-1m"])
def test_a_config_without_placement_builds_what_it_built_before(config_name):
    spec = harness.benchmark_spec()
    config = harness.load_json(harness.config_file(spec, config_name))
    mix = harness.load_json(f"{harness.HERE}/traffic/flood.json")
    # The committed strata were made for the overlays.spec of before.
    assert harness.load_json(strata.path(config))["made_for"] == {
        "overlay": overlays.spec(config),
        "coverage_target": mix["coverage_target"],
        "max_rounds": mix["max_rounds"]}
    assert graphs.mesh(config, 1) is None
    small = copy.deepcopy(config)
    small["n"] = N
    graph, _, info = graphs.build(small, cache=False)
    assert not info["on_host"]
    lo, hi, n = overlays.undirected_edges(small)
    before = G.from_edges(*overlays.directed(lo, hi), n, **small["layout"])
    assert jax.tree_util.tree_structure(graph) == \
        jax.tree_util.tree_structure(before)
    for a, b in zip(jax.tree_util.tree_leaves(graph),
                    jax.tree_util.tree_leaves(before)):
        assert isinstance(a, jax.Array)
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

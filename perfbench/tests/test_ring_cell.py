"""``ws-40m-ring.flood`` at a small size: a 3,000-node copy of its
configuration (the ring layout built with the graph, placed on a ring of
four CPU devices) runs correct through ``run.run_cell``, attach keeps the
shard, the ring's faults make it not correct, and the accepted flood
readers read the ring's trace."""

import copy

import jax
import pytest

import harness
import program
import run
import strata
import tracing
from p2pnetwork_tpu.parallel import sharded
from test_placement import _altered_ring_answer, _exchange_left_out

CELL = "ws-40m-ring.flood"
SEED = 2**31 + 2701
N = 3000


def ring_ctx(seconds: float = 0.3):
    spec = harness.benchmark_spec()
    cell, config, traffic = harness.load_cell(spec, CELL)
    config = copy.deepcopy(config)
    config["n"] = N
    ctx = run.Context(spec, cell, config, dict(traffic), SEED, seconds,
                      False)
    ctx.device = {"platform": "cpu", "kind": "cpu", "count": cell["chips"]}
    ctx.layout_cache = False
    return ctx


@pytest.fixture(autouse=True)
def small_strata(monkeypatch):
    # The committed strata are of the 40M overlay; draw the small copy's.
    real = strata.compute
    monkeypatch.setattr(strata, "load",
                        lambda config, mix: real(config, mix, 64)["by_rounds"])


@pytest.fixture(autouse=True)
def fresh_programs():
    # Faults patch traced code; compiled programs must not carry over.
    jax.clear_caches()
    sharded._flood_cov_fn.cache_clear()
    yield
    jax.clear_caches()
    sharded._flood_cov_fn.cache_clear()


def test_the_cell_is_a_ring_of_four_with_the_layout_built_with_the_graph():
    ctx = ring_ctx()
    assert ctx.cell["chips"] == 4
    assert ctx.config["placement"] == {"mesh": "ring"}
    assert ctx.config["layout"]["ring_shards"] == ctx.cell["chips"]
    run.build_graph(ctx)
    assert ctx.graph.ring is not None
    assert ctx.graph.ring.n_shards == ctx.mesh.devices.size == 4


def test_a_small_copy_is_correct_and_attach_keeps_the_shard():
    ctx = ring_ctx()
    line = run.run_cell(ctx)
    assert line["correct"], line["checks"]
    assert line["checks"]["checked"]["value"] >= 1
    moved = ctx.window_counters
    # The warm-up placed the graph's own ring layout; every window flood
    # reattached the same graph and reused its shard.
    assert moved["sim_shard_total{result=reused}"] == line["attempted"]
    assert "sim_shard_total{result=built}" not in moved
    assert "sim_shard_total{result=from_layout}" not in moved
    rounds = moved["sim_ring_rounds_total"]
    assert rounds >= line["attempted"]
    block = -(-N // 128) * 128 // 4  # nodes padded to 128, over 4 shards
    assert moved["sim_ring_halo_bytes_total"] == rounds * 3 * block


@pytest.mark.parametrize("fault", [_exchange_left_out, _altered_ring_answer],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_ring_fault_makes_the_copy_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = run.run_cell(ring_ctx())
    assert not line["correct"], line["checks"]


def _events(spans=True):
    """A ring flood window: two floods, each its attach, its
    ``run_dispatch`` (``loop=ring``) and its ``ring_wait``."""
    device = {"/device:TPU:0": [(100, 200, "f"), (500, 600, "f")]}
    host = [(0, 1000, "perfbench_window"),
            (0, 500, "perfbench_flood"), (500, 1000, "perfbench_flood")]
    if spans:
        host += [(0, 10, "graftscope.sim_attach"),
                 (10, 90, "graftscope.run_dispatch"),
                 (90, 480, "graftscope.ring_wait"),
                 (500, 504, "graftscope.sim_attach"),
                 (510, 550, "graftscope.run_dispatch"),
                 (550, 990, "graftscope.ring_wait"),
                 (1100, 1200, "graftscope.ring_wait")]
    return device, host


@pytest.fixture
def traced(monkeypatch):
    """The readers' trace is the synthetic one of :func:`_events`, read
    through a stand-in file."""
    monkeypatch.setattr(program, "_read", {})
    monkeypatch.setattr(program, "newest_xplane", lambda: "run.xplane.pb")
    monkeypatch.setattr(tracing, "read_events", lambda path: _events())


def _ring_readers() -> set:
    """The per-layer metrics the ring cell declares."""
    spec = harness.benchmark_spec()
    return {m["name"] for m in harness.cell_metrics(spec, CELL, "per_layer")}


@pytest.mark.parametrize("name,want", [
    # (80 + 40) ns of run_dispatch over 2 floods
    ("flood_dispatch_ms", 120e-9 / 2 * 1e3),
    # (10 + 4) ns of sim_attach over 2 floods
    ("flood_attach_ms", 14e-9 / 2 * 1e3),
    # each flood 500 ns of wall, 100 ns of it busy
    ("flood_host_ms", 400e-9 * 1e3),
    # 200 ns busy over 25 rounds
    ("round_device_ms.flood", 200e-9 / 25 * 1e3),
    ("device_idle.flood", 80.0),
])
def test_the_flood_readers_read_the_ring_trace(traced, name, want):
    assert name in _ring_readers()
    stats = {"n_floods": 2, "rounds": 25}
    trace = tracing.reduce(*_events())
    reader = harness.load_module("metrics", name)
    assert reader.read(stats, trace) == pytest.approx(want)


def test_the_ring_cell_declares_every_flood_reader():
    flood = harness.cell_metrics(harness.benchmark_spec(), "ws-1m.flood",
                                 "per_layer")
    assert _ring_readers() == {m["name"] for m in flood}


@pytest.mark.parametrize("name", ["flood_dispatch_ms", "flood_attach_ms"])
@pytest.mark.parametrize("case", ["untraced", "no span", "no flood"])
def test_the_span_readers_return_none_with_nothing_to_read(
        traced, monkeypatch, name, case):
    stats = {"n_floods": 2, "rounds": 25}
    trace = tracing.reduce(*_events())
    if case == "untraced":
        trace = None
    elif case == "no span":  # a program without the ring's spans
        monkeypatch.setattr(tracing, "read_events",
                            lambda path: _events(spans=False))
    else:
        stats = {"n_floods": 0, "rounds": 0}
    assert harness.load_module("metrics", name).read(stats, trace) is None

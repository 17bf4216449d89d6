"""Tracing/profiling: structured per-round records, JSONL sink, profiler
capture smoke test."""

import json

import pytest

jax = pytest.importorskip("jax")

from p2pnetwork_tpu.models import Flood  # noqa: E402
from p2pnetwork_tpu.sim import graph as G  # noqa: E402
from p2pnetwork_tpu.utils import trace  # noqa: E402


@pytest.fixture(scope="module")
def graph():
    return G.watts_strogatz(300, 4, 0.1, seed=0)


def test_records_match_engine_stats(graph):
    state, records = trace.run_traced(
        graph, Flood(source=0), jax.random.key(0), 4, label="flood"
    )
    assert len(records) == 4
    for i, rec in enumerate(records):
        assert rec["round"] == i
        assert rec["label"] == "flood"
        assert set(rec) >= {"coverage", "messages", "frontier"}
    # coverage is monotone for flood; final record reflects the final state
    covs = [r["coverage"] for r in records]
    assert covs == sorted(covs)
    import numpy as np

    n_seen = int(np.asarray(state.seen).sum())
    assert covs[-1] == pytest.approx(n_seen / graph.n_nodes)


def test_jsonl_sink(tmp_path, graph):
    path = tmp_path / "trace.jsonl"
    trace.run_traced(graph, Flood(source=0), jax.random.key(0), 3,
                     sink=str(path), label="t")
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(lines) == 4  # 3 rounds + summary
    assert lines[-1]["summary"] is True
    assert lines[-1]["rounds"] == 3
    assert lines[-1]["n_nodes"] == graph.n_nodes
    assert lines[-1]["wall_s"] > 0


def test_summary_schema_pinned(graph):
    """The summary line is a consumed artifact (BENCH.md tooling, ad-hoc
    jq) — its key set is pinned, telemetry-sourced fields included."""
    import io

    buf = io.StringIO()
    trace.run_traced(graph, Flood(source=0), jax.random.key(0), 2, sink=buf,
                     label="pin")
    summary = json.loads(buf.getvalue().splitlines()[-1])
    assert set(summary) == {"label", "summary", "rounds", "wall_s",
                            "compile_seconds", "device_transfer_bytes",
                            "n_nodes", "n_edges"}
    # stats history: 2 rounds x (coverage, messages, frontier,
    # frontier_occupancy) 4-byte scalars
    assert summary["device_transfer_bytes"] == 2 * 4 * 4
    assert summary["compile_seconds"] >= 0.0


def test_compile_seconds_sourced_from_registry():
    """A run that triggers fresh XLA compilation attributes its compile
    wall time in the summary (jax.monitoring -> registry delta); a warm
    rerun attributes ~none. A fresh graph SHAPE forces the cold compile
    without clearing the module's jit caches."""
    from p2pnetwork_tpu.telemetry import jaxhooks

    if not jaxhooks.install():
        pytest.skip("jax.monitoring unavailable")
    import io

    g = G.watts_strogatz(123, 4, 0.1, seed=3)  # unseen shape -> compiles
    buf = io.StringIO()
    trace.run_traced(g, Flood(source=0), jax.random.key(0), 2, sink=buf)
    first = json.loads(buf.getvalue().splitlines()[-1])
    assert first["compile_seconds"] > 0

    buf = io.StringIO()  # warm cache: no fresh compile attributed
    trace.run_traced(g, Flood(source=0), jax.random.key(0), 2, sink=buf)
    warm = json.loads(buf.getvalue().splitlines()[-1])
    assert warm["compile_seconds"] < first["compile_seconds"] / 10


def test_sink_accepts_file_object(graph):
    import io

    buf = io.StringIO()
    trace.run_traced(graph, Flood(source=0), jax.random.key(0), 2, sink=buf)
    assert len(buf.getvalue().splitlines()) == 3


def test_profile_capture(tmp_path, graph):
    prof_dir = tmp_path / "prof"
    trace.run_traced(graph, Flood(source=0), jax.random.key(0), 2,
                     profile_dir=str(prof_dir))
    # jax.profiler.trace writes a plugins/profile/<ts>/ tree
    captured = list(prof_dir.rglob("*.xplane.pb"))
    assert captured, "no profile artifacts captured"


def test_span_mirror_is_transparent(graph):
    from p2pnetwork_tpu.telemetry import spans

    with spans.span("custom-step"):
        out = jax.numpy.sum(jax.numpy.arange(8))
    assert int(out) == 28

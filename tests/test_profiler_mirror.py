"""The trace plane's profiler mirror and the entry/serving counters.

Every ``spans.span`` block, and every serve tick and tick phase, is also a
host event on the JAX profiler's timeline named ``graftscope.<span>``;
point events are not. The entry layer counts attach and dispatch seconds,
the compile hooks count persistent-cache loads, and the service counts
each ticket's wait for a lane."""

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from p2pnetwork_tpu import telemetry  # noqa: E402
from p2pnetwork_tpu.models.flood import Flood  # noqa: E402
from p2pnetwork_tpu.serve import SimService  # noqa: E402
from p2pnetwork_tpu.serve.service import TICK_PHASES, _PhaseClock  # noqa: E402
from p2pnetwork_tpu.sim import graph as G  # noqa: E402
from p2pnetwork_tpu.sim.simnode import JaxSimNode  # noqa: E402
from p2pnetwork_tpu.telemetry import jaxhooks, spans  # noqa: E402


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each mark's
    name as it is entered and exited."""

    def __init__(self):
        self.log = []
        rec = self

        class Mark:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                rec.log.append(("enter", self.name))
                return self

            def __exit__(self, *exc):
                rec.log.append(("exit", self.name))
                return False

        self.cls = Mark

    def names(self, what="enter"):
        return [n for kind, n in self.log if kind == what]


@pytest.fixture
def marks(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.cls)
    return rec


@pytest.fixture
def no_tracer():
    prev = spans.uninstall_tracer()
    yield
    spans.install_tracer(prev)


@pytest.fixture
def registry():
    reg = telemetry.Registry()
    prev = telemetry.set_default_registry(reg)
    yield reg
    telemetry.set_default_registry(prev)


def test_span_is_mirrored_without_a_tracer_and_emit_is_not(marks, no_tracer):
    with spans.span("work", step=3) as sid:
        assert sid is None  # no graftscope tracer installed
        spans.emit("point", k=1)
    assert marks.log == [("enter", "graftscope.work"),
                         ("exit", "graftscope.work")]


def test_span_is_mirrored_once_with_a_tracer(marks, no_tracer):
    tracer = spans.Tracer("t")
    spans.install_tracer(tracer)
    with spans.span("work"):
        spans.emit("point")
    assert marks.names() == ["graftscope.work"]
    assert [sp.name for sp in tracer.spans()] == ["t", "work", "point"]


def test_phase_clock_marks_the_tick_and_each_phase(marks):
    pc = _PhaseClock(None)
    for ph in TICK_PHASES:
        pc.enter(ph)
    phases = pc.done(0)
    assert set(phases) == set(TICK_PHASES)
    want = ["graftscope.serve_tick"] + [f"graftscope.tick_{ph}"
                                        for ph in TICK_PHASES]
    assert marks.names() == want
    assert sorted(marks.names("exit")) == sorted(want)
    # each phase closes before the next opens; the tick closes last
    assert marks.log[-1] == ("exit", "graftscope.serve_tick")


def test_flood_counts_attach_and_dispatch_once(marks, registry):
    g = G.watts_strogatz(256, 6, 0.1, seed=2)
    node = JaxSimNode("127.0.0.1", 0)
    node.attach_simulation(g, Flood(source=5))
    out = node.run_until_coverage(0.99, max_rounds=64)
    assert out["coverage"] >= 0.99
    assert sorted(marks.names()) == sorted(
        ["graftscope.sim_attach", "graftscope.sim_run",
         "graftscope.run_dispatch", "graftscope.run_summary"])
    attach = registry.value("sim_attach_seconds_total")
    dispatch = registry.value("sim_dispatch_seconds_total",
                              loop="coverage_from")
    transfer = registry.value("sim_transfer_seconds_total")
    run = registry.get("sim_run_seconds").labels("coverage_from")
    assert attach > 0 and dispatch > 0
    assert run.count == 1
    assert dispatch + transfer <= run.sum


def test_cache_retrieval_event_counts_load_seconds():
    reg = telemetry.Registry()
    if not jaxhooks.install(reg):
        pytest.skip("jax.monitoring unavailable")
    try:
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    finally:
        jaxhooks.uninstall(reg)
    assert reg.value("jax_cache_load_seconds_total") == pytest.approx(0.75)
    assert reg.get("jax_compile_seconds_total") is None


def test_ticket_queue_wait_is_observed_once_and_kept_off_the_record():
    g = G.watts_strogatz(300, 6, 0.2, seed=3, source_csr=True)

    def serve_one():
        reg = telemetry.Registry()
        svc = SimService(g, capacity=32, queue_depth=8, chunk_rounds=16,
                         seed=0, registry=reg)
        try:
            tid = svc.submit(7)
            for _ in range(8):
                svc.tick()
                if svc.poll(tid)["status"] == "done":
                    break
            return (svc.poll(tid), reg.get("serve_queue_wait_seconds"),
                    svc.tick_phases()["queue_wait"])
        finally:
            svc.close()

    rec, wait, totals = serve_one()
    assert rec["status"] == "done"
    assert wait.count == 1 and wait.sum >= 0.0
    # tick_phases() carries the same observation, for this service alone
    assert totals == {"count": 1, "total_s": pytest.approx(wait.sum)}
    # Wall times stay out of ticket records: a second service serving
    # the same request writes the same record.
    rec2, _, _ = serve_one()
    assert rec == rec2


def test_span_lands_on_the_profiler_host_plane(tmp_path, no_tracer):
    from jax.profiler import ProfileData

    x = jnp.arange(8.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("mirror_probe"):
            jnp.sum(x * 2).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    host = [ev.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    assert spans.PROFILER_PREFIX + "mirror_probe" in host

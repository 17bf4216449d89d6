"""The readers of the program's own spans and counters (``program.py``)."""

import os

import pytest

import harness
import program
import tracing

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tpu_fixture.xplane.pb")


def _events():
    device = {"/device:TPU:0": [(100, 200, "f"), (500, 600, "f"),
                                (800, 900, "f")]}
    host = [(0, 1000, "perfbench_window"), (0, 500, "perfbench_flood"),
            (500, 1000, "perfbench_flood"),
            (-50, 20, "graftscope.sim_attach"),
            (150, 790, "graftscope.sim_run"),
            (210, 480, "graftscope.run_dispatch"),
            (340, 360, "PjitFunction(add)"),
            (795, 1000, "graftscope.sim_attach"),
            (1100, 1200, "graftscope.run_dispatch")]
    return device, host


@pytest.fixture
def traced(monkeypatch):
    """The readers' trace is the synthetic one of :func:`_events`, read
    through a stand-in file; returns the count of reads."""
    reads = []

    def read_events(path):
        reads.append(path)
        return _events()
    monkeypatch.setattr(program, "_read", {})
    monkeypatch.setattr(program, "newest_xplane", lambda: "run.xplane.pb")
    monkeypatch.setattr(tracing, "read_events", read_events)
    return reads


def test_window_spans_sum_and_blame_program_spans_only():
    out = program.window_spans(*_events())
    # clipped to the window; the run_dispatch after it is not counted
    assert out["spans"] == {"sim_attach": pytest.approx(225e-9),
                            "sim_run": pytest.approx(640e-9),
                            "run_dispatch": pytest.approx(270e-9)}
    # gaps (middle): [0,100] (50) under no span; [200,500] (350) under
    # sim_run and run_dispatch; [600,800] (700) under sim_run alone;
    # [900,1000] (950) under sim_attach. PjitFunction is the runtime's.
    assert dict(out["idle_by_span"]) == {
        program.RESIDUE: pytest.approx(100e-9),
        "run_dispatch": pytest.approx(300e-9),
        "sim_run": pytest.approx(200e-9),
        "sim_attach": pytest.approx(100e-9)}
    assert out["idle_by_span"][0][0] == "run_dispatch"
    reduced = tracing.reduce(*_events())
    assert sum(t for _, t in out["idle_by_span"]) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    assert out["window_s"] == reduced["window_s"]


@pytest.mark.parametrize("name,stats,want", [
    ("flood_dispatch_ms", {"n_floods": 2}, 270e-9 / 2 * 1e3),
    ("flood_attach_ms", {"n_floods": 3}, 225e-9 / 3 * 1e3),
])
def test_flood_readers_divide_window_spans_by_floods(traced, name, stats,
                                                     want):
    trace = tracing.reduce(*_events())
    reader = harness.load_module("metrics", name)
    assert reader.read(stats, trace) == pytest.approx(want)
    assert reader.read(stats, trace) == pytest.approx(want)
    assert len(traced) == 1  # one read of the trace a process


@pytest.mark.parametrize("name,stats,trace", [
    # untraced: no trace at all
    ("flood_dispatch_ms", {"n_floods": 2}, None),
    # another run's trace: its window is not this one's
    ("flood_attach_ms", {"n_floods": 2}, {"window_s": 2e-6}),
    # no flood in the window
    ("flood_dispatch_ms", {"n_floods": 0}, "reduced"),
    # a span the trace does not hold (an older program)
    ("flood_dispatch_ms", {"n_floods": 2}, "no spans"),
])
def test_flood_readers_return_none_with_nothing_to_read(
        traced, monkeypatch, name, stats, trace):
    if trace == "no spans":
        device, host = _events()
        monkeypatch.setattr(tracing, "read_events", lambda path: (device, [
            ev for ev in host if not ev[2].startswith(program.PREFIX)]))
    if trace in ("reduced", "no spans"):
        trace = tracing.reduce(*_events())
    assert harness.load_module("metrics", name).read(stats, trace) is None


def _phases(count, total_s):
    return {"ticks": 0, "per_phase": {}, "recent": [],
            "queue_wait": {"count": count, "total_s": total_s}}


def test_ticket_queue_ms_differences_the_window(traced):
    stats = {"phases0": _phases(20, 10.0), "phases1": _phases(30, 13.5)}
    reader = harness.load_module("metrics", "ticket_queue_ms")
    assert reader.read(stats, None) == pytest.approx(350.0)
    assert reader.read(stats, tracing.reduce(*_events())) == \
        pytest.approx(350.0)


@pytest.mark.parametrize("stats", [
    {},
    # an older service: tick_phases() without queue_wait
    {"phases0": {"ticks": 1}, "phases1": {"ticks": 5}},
    # no ticket admitted in the window
    {"phases0": _phases(5, 1.0), "phases1": _phases(5, 1.0)},
])
def test_ticket_queue_ms_returns_none_with_nothing_to_read(stats):
    assert harness.load_module("metrics", "ticket_queue_ms").read(
        stats, None) is None


def test_no_trace_file_reads_as_nothing(monkeypatch):
    monkeypatch.setattr(program, "newest_xplane", lambda: None)
    assert program.traced_window({"window_s": 1.0}) is None


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_tpu_trace_has_no_program_span(monkeypatch):
    """The v5e fixture predates the mirror: every idle second goes to the
    residue, and the span readers leave their metrics out."""
    monkeypatch.setattr(program, "_read", {})
    monkeypatch.setattr(program, "newest_xplane", lambda: FIXTURE)
    trace = tracing.reduce(*tracing.read_events(FIXTURE))
    got = program.traced_window(trace)
    assert got["spans"] == {}
    assert got["idle_by_span"] == [[program.RESIDUE, pytest.approx(
        trace["window_s"] - trace["busy_s"], rel=1e-12)]]
    for name in ("flood_dispatch_ms", "flood_attach_ms"):
        assert harness.load_module("metrics", name).read(
            {"n_floods": 2}, trace) is None

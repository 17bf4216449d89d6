"""Metric arithmetic: the window, the tail, the readers, the reduction."""

import json
import math
import os

import pytest

import harness
import run
import tracing
from drivers import flood, serve

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tpu_fixture.xplane.pb")


def test_flood_window_extends_to_the_flood_in_flight():
    # Window of 10 s from t=100: floods end at 104, 108 and 112.5 (in
    # flight at 110): 12.5 s over 3 floods.
    assert flood.flood_seconds(100.0, [104.0, 108.0, 112.5]) == \
        pytest.approx(12.5 / 3)
    with pytest.raises(ValueError):
        flood.flood_seconds(100.0, [])


def test_p95_is_nearest_rank_over_every_ticket():
    assert serve.p95(list(range(1, 101))) == 95
    assert serve.p95([5.0]) == 5.0
    assert serve.p95([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16,
                      17, 18, 19, 20]) == 19


def test_ticket_window_extends_to_the_tick_in_flight():
    # Window 10 s from t=0; ticks end at 4, 8 and 12 (in flight at 10):
    # all 9 done tickets over 12 s; a failed ticket (None) is not done.
    obs = [4.0, 4.0, 4.0, 8.0, 8.0, 8.0, 12.0, 12.0, 12.0, None]
    assert serve.tickets_per_second(0.0, 10.0, obs) == pytest.approx(9 / 12)
    assert serve.tickets_per_second(0.0, 10.0, [4.0, None]) == \
        pytest.approx(1 / 10)


def test_failed_tickets_count_at_the_drain_end():
    lat = serve.latencies([0.0, 1.0, 2.0], [0.5, None, 2.25], 70.0)
    assert lat == [0.5, 69.0, 0.25]


def test_merge_overlap_and_gaps():
    m = tracing.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert m == [(0, 3), (5, 9)]
    assert tracing.overlap(m, 2, 6) == 2
    assert tracing.gaps(m, 0, 10) == [(3, 5), (9, 10)]


def _events():
    device = {"/device:TPU:0": [(100, 300, "fusion.1"), (250, 400, "fusion.2"),
                                (600, 700, "fusion.1"), (950, 1200, "copy")]}
    host = [(0, 1000, "perfbench_window"), (50, 500, "perfbench_flood"),
            (500, 1000, "perfbench_flood"), (420, 580, "PjitFunction(f)"),
            (400, 990, "outer"), (800, 900, "attach")]
    return device, host


def test_reduce_clips_to_the_window_and_splits_spans():
    out = tracing.reduce(*_events())
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 400] + [600, 700] + [950, 1000] = 450 ns
    assert out["busy_s"] == pytest.approx(450e-9)
    assert out["spans"]["flood"] == [pytest.approx((450e-9, 300e-9)),
                                     pytest.approx((500e-9, 150e-9))]
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(300e-9)
    assert ops["copy"] == pytest.approx(50e-9)
    idle = dict(out["breakdown"]["idle_gaps"])
    # gaps: [0,100] none, [400,600] PjitFunction, [700,950] attach
    assert idle["host: no event"] == pytest.approx(100e-9)
    assert idle["PjitFunction(f)"] == pytest.approx(200e-9)
    assert idle["attach"] == pytest.approx(250e-9)


def test_events_of_keeps_every_thread_line_of_one_name():
    # Every Python thread's line is named "python": the window is on the
    # main thread's, the service driver's comes after it.
    from types import SimpleNamespace as NS

    def line(name, *events):
        return NS(name=name, events=[NS(start_ns=s, duration_ns=d, name=n)
                                     for s, d, n in events])
    profile = NS(planes=[
        NS(name="/host:CPU", lines=[
            line("python", (0, 1000, "perfbench_window")),
            line("python", (10, 5, "PjitFunction(f)"))]),
        NS(name="/device:TPU:0", lines=[
            line("XLA Modules", (0, 900, "jit_f")),
            line("XLA Ops", (100, 200, "%fusion.1 = f32[8] fusion(...)"))])])
    device, host = tracing.events_of(profile)
    assert sorted(n for _, _, n in host) == ["PjitFunction(f)",
                                             "perfbench_window"]
    assert device == {"/device:TPU:0": [(100, 300,
                                         "%fusion.1 = f32[8] fusion(...)")]}
    assert tracing.reduce(device, host)["busy_s"] == pytest.approx(200e-9)


def test_reduce_refuses_a_trace_without_window_or_ops():
    device, host = _events()
    with pytest.raises(ValueError):
        tracing.reduce(device, [e for e in host if e[2] != "perfbench_window"])
    with pytest.raises(ValueError):
        tracing.reduce({"/device:TPU:0": []}, host)


READER_CASES = [
    ("flood_host_ms", {}, {"spans": {"flood": [(2.0, 1.5), (1.0, 0.5)]}}, 500.0),
    ("round_device_ms.flood", {"rounds": 20}, {"busy_s": 3.0}, 150.0),
    ("round_device_ms.serve", {"rounds": 40}, {"busy_s": 2.0}, 50.0),
    ("device_idle.flood", {}, {"busy_s": 7.5, "window_s": 10.0}, 25.0),
    ("device_idle.serve", {}, {"busy_s": 9.0, "window_s": 10.0}, 10.0),
]


@pytest.mark.parametrize("name,stats,trace,want", READER_CASES)
def test_readers(name, stats, trace, want):
    assert harness.load_module("metrics", name).read(stats, trace) == \
        pytest.approx(want)


@pytest.mark.parametrize("name,stats,trace,want", READER_CASES)
def test_readers_read_the_same_beside_the_window_counters(name, stats, trace,
                                                          want):
    stats = dict(stats, counters={
        "sim_dispatch_seconds_total{loop=coverage}": 9.0,
        "sim_attach_seconds_total": 0.5})
    assert harness.load_module("metrics", name).read(stats, trace) == \
        pytest.approx(want)


def test_moved_keeps_the_counters_that_moved_by_how_much():
    before = {"a_total": 1.0, "b_total{loop=batch}": 2.0}
    after = {"a_total": 1.0, "b_total{loop=batch}": 5.0, "c_seconds_count": 3}
    assert run.moved(before, after) == {"b_total{loop=batch}": 3.0,
                                        "c_seconds_count": 3}


def test_program_counters_flatten_the_registry():
    from p2pnetwork_tpu import telemetry

    reg = telemetry.Registry()
    prev = telemetry.set_default_registry(reg)
    try:
        reg.counter("x_total", "", ("loop", "op")).labels(
            loop="batch", op="or").inc(2)
        reg.histogram("w_seconds", "").observe(0.5)
        reg.gauge("depth", "").set(3)
        assert run.program_counters() == {
            "x_total{loop=batch,op=or}": 2.0, "w_seconds_sum": 0.5,
            "w_seconds_count": 1}
    finally:
        telemetry.set_default_registry(prev)


def test_run_cell_prints_the_window_counters(monkeypatch, capsys):
    import strata
    from test_correctness import make_ctx

    real = strata.compute
    monkeypatch.setattr(strata, "load",
                        lambda config, mix: real(config, mix, 64)["by_rounds"])
    line = run.run_cell(make_ctx("ba-1m.flood"))
    stats = [json.loads(ln.split(" ", 2)[2]) for ln in
             capsys.readouterr().err.splitlines()
             if ln.startswith("# stats ")]
    counters = stats[-1]["counters"]
    # Each flood of the window attached and dispatched once.
    assert counters["sim_dispatch_seconds_total{loop=coverage_from}"] > 0
    assert counters["sim_attach_seconds_total"] > 0
    assert line["correct"]


def test_tick_host_ms_differences_the_totals():
    def phases(ticks, each):
        return {"ticks": ticks, "per_phase": {
            ph: {"total_s": each * ticks} for ph in
            ("mutate", "retire", "admit", "dispatch", "harvest",
             "checkpoint")}}
    stats = {"phases0": phases(10, 0.001), "phases1": phases(30, 0.001)}
    got = harness.load_module("metrics", "tick_host_ms").read(stats, None)
    assert got == pytest.approx(5 * 0.001 * 1e3)


@pytest.mark.parametrize("name", ["flood_host_ms", "round_device_ms.flood",
                                  "device_idle.serve", "tick_host_ms"])
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert harness.load_module("metrics", name).read({}, None) is None


def test_every_declared_metric_has_a_reader():
    spec = harness.benchmark_spec()
    for m in spec["per_layer"]:
        assert hasattr(harness.load_module("metrics", m["name"]), "read")


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_reduction_of_a_recorded_tpu_trace():
    """A trace recorded on one v5e chip: a window holding two spans, each
    running two jitted programs around a 10 ms host sleep."""
    out = tracing.reduce(*tracing.read_events(FIXTURE))
    assert 0 < out["busy_s"] < out["window_s"]
    assert len(out["spans"]["flood"]) == 2
    for wall, busy in out["spans"]["flood"]:
        assert 0 < busy < wall and wall > 0.01
    assert out["breakdown"]["device_ops"]
    assert math.isclose(sum(t for _, t in out["breakdown"]["idle_gaps"]),
                        out["window_s"] - out["busy_s"], rel_tol=1e-6)

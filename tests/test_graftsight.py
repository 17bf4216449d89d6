"""graftsight tests: ticket-scoped tracing, tick phases, the SLO engine.

The contract under test (PR 16): one serve ticket's whole lifecycle —
submit → queue → admit → engine chunks → device fault → integrity
verdict → heal retry → completion — exports as ONE Perfetto tree under
a single ``tkt-<id>`` trace id, chaos included; the driver's tick wall
decomposes into named phases (retire/admit/dispatch/harvest/checkpoint)
published through ``/dashboard``; declarative SLOs evaluate over
rolling observation windows with multi-window burn-rate alerts that
AIMD admission consumes as an explicit, deterministic signal; and all
of it rides the determinism contract — tracing+SLO on is bit-identical
to off, with the slow-marked 1.10x serve-tick overhead ratchet keeping
the instrumentation honest.
"""

import dataclasses
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from p2pnetwork_tpu import telemetry  # noqa: E402
from p2pnetwork_tpu.chaos.device import (  # noqa: E402
    DispatchChaos, install_dispatch_chaos)
from p2pnetwork_tpu.serve import (  # noqa: E402
    SimService, TrafficPattern, drive, generate)
from p2pnetwork_tpu.serve.service import (  # noqa: E402
    TICK_PHASES, ticket_trace)
from p2pnetwork_tpu.sim import engine  # noqa: E402
from p2pnetwork_tpu.sim import graph as G  # noqa: E402
from p2pnetwork_tpu.supervise.heal import RetryPolicy  # noqa: E402
from p2pnetwork_tpu.telemetry import history, spans  # noqa: E402
from p2pnetwork_tpu.telemetry.httpd import dashboard_doc  # noqa: E402
from p2pnetwork_tpu.telemetry.slo import (  # noqa: E402
    Objective, SLOEngine, serve_objectives)
from p2pnetwork_tpu.utils.logging import EventLog  # noqa: E402

pytestmark = pytest.mark.sight

KEY = jax.random.key(0)


@pytest.fixture(scope="module")
def ws256():
    return G.watts_strogatz(256, 4, 0.2, seed=0)


@pytest.fixture()
def tracer():
    t = spans.Tracer("sight-test")
    prev = spans.install_tracer(t)
    yield t
    spans.install_tracer(prev)


@pytest.fixture()
def no_dispatch_chaos():
    prev = install_dispatch_chaos(None)
    yield
    install_dispatch_chaos(prev)


def _svc(g, **kw):
    kw.setdefault("capacity", 16)
    kw.setdefault("chunk_rounds", 4)
    kw.setdefault("seed", 0)
    kw.setdefault("registry", telemetry.Registry())
    return SimService(g, **kw)


# ------------------------------------------------- trace-id correlation


class TestTraceOverride:
    def test_trace_kwarg_overrides_span_trace_id(self):
        t = spans.Tracer("base")
        t.point("plain")
        t.point("scoped", trace="tkt-t01")
        with t.span("also-scoped", trace="tkt-t01"):
            pass
        by_name = {sp.name: sp for sp in t.spans()}
        assert by_name["plain"].trace_id == t.trace_id
        assert by_name["scoped"].trace_id == "tkt-t01"
        assert by_name["also-scoped"].trace_id == "tkt-t01"

    def test_module_emit_carries_trace(self):
        t = spans.Tracer("base")
        prev = spans.install_tracer(t)
        try:
            spans.emit("ev", trace="tkt-t02", lane=3)
        finally:
            spans.install_tracer(prev)
        (sp,) = t.find("ev")
        assert sp.trace_id == "tkt-t02" and sp.args["lane"] == 3

    def test_to_chrome_filters_one_trace(self):
        t = spans.Tracer("base")
        t.point("a", trace="tkt-x")
        t.point("b", trace="tkt-y")
        t.point("c", trace="tkt-x")
        doc = t.to_chrome(trace_id="tkt-x")
        assert [e["name"] for e in doc["traceEvents"]] == ["a", "c"]
        assert all(e["args"]["trace_id"] == "tkt-x"
                   for e in doc["traceEvents"])
        assert doc["metadata"]["trace_id"] == "tkt-x"
        assert doc["metadata"]["traces"] == 1

    def test_traces_table_insertion_ordered(self):
        t = spans.Tracer("base")
        t.point("a", trace="tkt-1")
        t.point("b", trace="tkt-2")
        t.point("c", trace="tkt-1")
        by = t.traces()
        assert list(by) == [t.trace_id, "tkt-1", "tkt-2"]
        assert by["tkt-1"] == 2 and by["tkt-2"] == 1

    def test_ticket_trace_shape(self):
        assert ticket_trace("t00000007") == "tkt-t00000007"


class TestOverflowMetadata:
    def test_to_chrome_reports_dropped_spans(self):
        # Satellite 1: an overflowed store must SAY so in the export's
        # metadata, not silently read as complete.
        t = spans.Tracer("tiny", max_spans=4)
        for i in range(10):
            t.point(f"p{i}")
        doc = t.to_chrome()
        meta = doc["metadata"]
        assert meta["dropped_spans"] == 6 == t.dropped_spans
        assert meta["spans"] == len(doc["traceEvents"]) == 5  # root + 4
        assert meta["traces"] == 1
        assert meta["trace_id"] == t.trace_id

    def test_unfiltered_metadata_counts_all_traces(self):
        t = spans.Tracer("base")
        t.point("a", trace="tkt-1")
        t.point("b", trace="tkt-2")
        meta = t.to_chrome()["metadata"]
        assert meta["dropped_spans"] == 0
        assert meta["traces"] == 3  # base + two ticket traces


# ------------------------------------------------------ httpd endpoints


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, r.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


class TestHttpdQueryParams:
    def _server(self, reg, **kw):
        return telemetry.MetricsServer(reg, port=0, **kw)

    def test_history_last_n(self):
        reg = telemetry.Registry()
        reg.gauge("sight_g", "g").set(0.0)
        hist = history.History(reg, capacity=16)
        for i in range(6):
            reg.gauge("sight_g", "g").set(float(i))
            hist.sample(ts=float(i))
        with self._server(reg, history=hist) as srv:
            code, body = _get(srv.port, "/history?n=2")
            assert code == 200
            doc = json.loads(body)
            pts = doc["series"]["sight_g"][0]["points"]
            assert pts == [[4.0, 4.0], [5.0, 5.0]]
            code, body = _get(srv.port, "/history")
            assert len(json.loads(body)["series"]["sight_g"][0]["points"]) \
                == 6

    def test_history_bad_n_is_400_not_500(self):
        reg = telemetry.Registry()
        hist = history.History(reg, capacity=4)
        with self._server(reg, history=hist) as srv:
            for q in ("n=zero", "n=0", "n=-3"):
                code, body = _get(srv.port, f"/history?{q}")
                assert code == 400, q
                assert "n must be" in json.loads(body)["error"]

    def test_trace_filtered_by_trace_id(self):
        reg = telemetry.Registry()
        t = spans.Tracer("srv")
        t.point("mine", trace="tkt-t0")
        t.point("other", trace="tkt-t1")
        with self._server(reg, tracer=t) as srv:
            code, body = _get(srv.port, "/trace?trace_id=tkt-t0")
            assert code == 200
            doc = json.loads(body)
            assert [e["name"] for e in doc["traceEvents"]] == ["mine"]
            assert doc["metadata"]["trace_id"] == "tkt-t0"

    def test_trace_empty_trace_id_is_400(self):
        reg = telemetry.Registry()
        with self._server(reg, tracer=spans.Tracer("srv")) as srv:
            code, body = _get(srv.port, "/trace?trace_id=")
            assert code == 400
            assert "trace_id" in json.loads(body)["error"]

    def test_history_snapshot_last_validation(self):
        hist = history.History(telemetry.Registry(), capacity=4)
        with pytest.raises(ValueError, match="last"):
            hist.snapshot(last=0)

    def test_dashboard_html_and_json(self):
        reg = telemetry.Registry()
        reg.counter("sight_total", "c").inc()
        hist = history.History(reg, capacity=4)
        hist.sample(ts=1.0)
        t = spans.Tracer("srv")
        t.point("ev", trace="tkt-t0")
        slo = SLOEngine(serve_objectives(slo_rounds=8), registry=reg)
        slo.record("completion_rounds", 4.0)
        slo.evaluate(0)
        with self._server(reg, history=hist, tracer=t, slo=slo) as srv:
            code, body = _get(srv.port, "/dashboard.json")
            assert code == 200
            doc = json.loads(body)
            assert doc["slo"]["objectives"]["completion_p99_rounds"][
                "samples"] == 1
            assert doc["traces"]["recent"]["tkt-t0"] == 1
            assert doc["metrics"]  # registry snapshot embedded
            code, page = _get(srv.port, "/dashboard")
            assert code == 200
            assert page.startswith("<!DOCTYPE html>")
            # The JSON island round-trips (the "</" embedding escape
            # must not corrupt it).
            island = page.split('<script id="data" '
                                'type="application/json">')[1]
            island = island.split("</script>")[0].replace("<\\/", "</")
            assert json.loads(island)["slo"] is not None

    def test_dashboard_without_slo_or_service(self):
        reg = telemetry.Registry()
        hist = history.History(reg, capacity=4)
        doc = dashboard_doc(reg, hist, None, None, None)
        assert doc["slo"] is None and doc["service"] is None \
            and doc["traces"] is None
        json.dumps(doc)


# ---------------------------------------------------------- SLO engine


class TestObjective:
    def test_validation(self):
        with pytest.raises(ValueError, match="mode"):
            Objective("o", metric="m", target=1.0, mode="eq")
        with pytest.raises(ValueError, match="goal"):
            Objective("o", metric="m", target=1.0, goal=1.0)
        with pytest.raises(ValueError, match="fast_window"):
            Objective("o", metric="m", target=1.0, fast_window=8,
                      slow_window=4)
        with pytest.raises(ValueError, match="burn_threshold"):
            Objective("o", metric="m", target=1.0, burn_threshold=0.0)
        with pytest.raises(ValueError, match="duplicate"):
            SLOEngine([Objective("o", metric="m", target=1.0)] * 2,
                      registry=telemetry.Registry())

    def test_good_modes(self):
        le = Objective("o", metric="m", target=10.0, mode="le")
        assert le.good(10.0) and not le.good(10.5)
        ge = Objective("o", metric="m", target=0.9, mode="ge")
        assert ge.good(0.95) and not ge.good(0.5)

    def test_serve_objectives_set(self):
        objs = serve_objectives(slo_rounds=24)
        names = [o.name for o in objs]
        assert names == ["completion_p99_rounds", "shed_rate", "heal_rate"]
        assert [o.admission_signal for o in objs] == [True, False, False]
        wall = serve_objectives(slo_rounds=24, wall_s=2.0)
        assert wall[1].name == "completion_p99_wall_s"
        assert not wall[1].admission_signal  # wall-clock never steers


class TestSLOEngine:
    def _eng(self, **obj_kw):
        obj_kw.setdefault("fast_window", 4)
        obj_kw.setdefault("slow_window", 8)
        obj_kw.setdefault("goal", 0.5)
        obj = Objective("rounds_p", metric="rounds", target=10.0, **obj_kw)
        reg = telemetry.Registry()
        return SLOEngine([obj], registry=reg, log=EventLog()), reg

    def test_burn_math(self):
        eng, _ = self._eng()
        for v in [1.0] * 4 + [99.0] * 4:  # half bad, budget 0.5
            eng.record("rounds", v)
        st = eng.evaluate(0)["rounds_p"]
        assert st["burn_slow"] == pytest.approx(1.0)  # exactly on budget
        assert st["burn_fast"] == pytest.approx(2.0)  # fast window all bad
        assert st["good_ratio"] == pytest.approx(0.5)

    def test_no_verdict_before_fast_window_fills(self):
        eng, _ = self._eng()
        eng.record("rounds", 99.0)  # one bad first observation
        st = eng.evaluate(0)["rounds_p"]
        assert st["burn_fast"] == pytest.approx(2.0)  # over threshold...
        assert not st["firing"]  # ...but unwarmed: one bad obs can't page

    def test_multi_window_needs_both(self):
        eng, _ = self._eng()
        for _ in range(6):
            eng.record("rounds", 1.0)  # slow window seeded good
        for _ in range(4):
            eng.record("rounds", 99.0)  # fast window all bad
        st = eng.evaluate(1)["rounds_p"]
        assert st["burn_fast"] >= 2.0
        assert st["burn_slow"] < 2.0
        assert not st["firing"]  # the slow window vetoes the page
        for _ in range(8):
            eng.record("rounds", 99.0)  # now the slow window burns too
        assert eng.evaluate(2)["rounds_p"]["firing"]

    def test_transitions_alert_records_counters_gauges(self):
        eng, reg = self._eng()
        for _ in range(8):
            eng.record("rounds", 99.0)
        eng.evaluate(3)
        assert eng.firing() == ["rounds_p"]
        assert reg.value("slo_firing", objective="rounds_p") == 1.0
        assert reg.value("slo_burn_rate", objective="rounds_p",
                         window="fast") == pytest.approx(2.0)
        assert reg.value("slo_alerts_total", objective="rounds_p",
                         transition="fire") == 1
        # A second evaluate while still firing is NOT a new transition.
        eng.evaluate(4)
        assert reg.value("slo_alerts_total", objective="rounds_p",
                         transition="fire") == 1
        for _ in range(8):
            eng.record("rounds", 1.0)
        eng.evaluate(5)
        assert eng.firing() == []
        assert reg.value("slo_alerts_total", objective="rounds_p",
                         transition="resolve") == 1
        alerts = [r for r in eng.log.snapshot() if r.event == "slo_alert"]
        assert [a.data["transition"] for a in alerts] == ["fire", "resolve"]
        assert alerts[0].data["objective"] == "rounds_p"
        assert alerts[0].data["tick"] == 3

    def test_admission_only_filter(self):
        objs = [Objective("det", metric="rounds", target=1.0, goal=0.5,
                          fast_window=2, slow_window=2,
                          admission_signal=True),
                Objective("wall", metric="wall", target=1.0, goal=0.5,
                          fast_window=2, slow_window=2)]
        eng = SLOEngine(objs, registry=telemetry.Registry())
        for _ in range(2):
            eng.record("rounds", 9.0)
            eng.record("wall", 9.0)
        eng.evaluate(0)
        assert sorted(eng.firing()) == ["det", "wall"]
        assert eng.firing(admission_only=True) == ["det"]

    def test_record_unjudged_stream_dropped(self):
        eng, _ = self._eng()
        eng.record("unknown_stream", 1.0)  # no ring, no crash
        assert eng.evaluate(0)["rounds_p"]["samples"] == 0

    def test_snapshot_before_and_after_evaluate(self):
        eng, _ = self._eng()
        snap = eng.snapshot()
        assert not snap["objectives"]["rounds_p"]["firing"]
        assert snap["objectives"]["rounds_p"]["metric"] == "rounds"
        for _ in range(8):
            eng.record("rounds", 99.0)
        eng.evaluate(7)
        snap = eng.snapshot()
        assert snap["objectives"]["rounds_p"]["firing"]
        assert snap["alerts"][-1]["data"]["transition"] == "fire"
        json.dumps(snap)

    def test_evaluate_is_pure_in_observations(self):
        runs = []
        for _ in range(2):
            eng, _ = self._eng()
            for v in [1.0, 99.0, 3.0, 99.0, 99.0, 1.0, 99.0, 99.0]:
                eng.record("rounds", v)
            runs.append(eng.evaluate(0))
        assert runs[0] == runs[1]


# ------------------------------------------------- tick-phase profiler


class TestTickPhases:
    def test_profile_populates_and_dashboard_slice(self, ws256):
        reg = telemetry.Registry()
        svc = _svc(ws256, registry=reg)
        for s in (1, 2, 3):
            svc.submit(s)
        for _ in range(4):
            svc.tick()
        tp = svc.tick_phases()
        assert tp["ticks"] == 4
        assert set(tp["per_phase"]) == set(TICK_PHASES)
        for ph in TICK_PHASES:
            st = tp["per_phase"][ph]
            assert st["total_s"] >= st["max_s"] >= st["last_s"] >= 0.0
            assert st["mean_s"] == pytest.approx(st["total_s"] / 4)
        assert len(tp["recent"]) == 4
        assert all(set(row) >= set(TICK_PHASES) for row in tp["recent"])
        # Joinable with the history ring: last-tick gauges per phase.
        assert reg.value("serve_tick_phase_wall_s", phase="dispatch") \
            is not None
        snap = reg.snapshot()
        assert "serve_tick_phase_seconds" in snap
        ds = svc.dashboard_slice()
        assert set(ds) == {"stats", "tick_phases"}
        assert ds["stats"]["tick"] == 4
        svc.close()

    def test_phase_spans_under_serve_tick(self, ws256, tracer):
        svc = _svc(ws256)
        svc.submit(1)
        svc.tick()
        svc.close()
        ticks = tracer.find("serve_tick")
        assert ticks, "one serve_tick span per tick when traced"
        children = {sp.name for sp in tracer.spans()
                    if sp.parent_id == ticks[0].span_id}
        assert {f"tick_{ph}" for ph in TICK_PHASES} <= children
        (pt,) = [sp for sp in tracer.spans()
                 if sp.name == "tick_phases"
                 and sp.parent_id == ticks[0].span_id]
        assert set(pt.args) >= set(TICK_PHASES)

    def test_ring_bounded(self, ws256):
        svc = _svc(ws256, capacity=4, chunk_rounds=1)
        for _ in range(140):
            svc.tick()  # idle ticks still profile
        tp = svc.tick_phases()
        assert tp["ticks"] == 140
        assert len(tp["recent"]) == 32  # snapshot tail
        with svc._phase_lock:
            assert len(svc._phase_ring) == 128  # ring bound
        svc.close()


# -------------------------------------------------- SLO -> AIMD signal


class TestSLOAdmission:
    def test_firing_admission_objective_halves_budget(self, ws256):
        # A tight deterministic objective (every completion "bad") must
        # fire once warmed and multiplicatively decrease the admit
        # budget — the explicit SLO signal beside the slo_rounds rule.
        reg = telemetry.Registry()
        slo = SLOEngine(
            [Objective("tight_rounds", metric="completion_rounds",
                       target=0.5, goal=0.5, fast_window=2, slow_window=4,
                       burn_threshold=2.0, admission_signal=True)],
            registry=reg, log=EventLog())
        svc = _svc(ws256, registry=reg, slo=slo)
        start_budget = svc.stats()["admit_budget"]
        for s in range(1, 9):
            svc.submit(s)
        for _ in range(10):
            svc.tick()
        assert slo.firing(admission_only=True) == ["tight_rounds"]
        assert svc.stats()["admit_budget"] < start_budget
        assert reg.value("slo_firing", objective="tight_rounds") == 1.0
        assert reg.value("slo_alerts_total", objective="tight_rounds",
                         transition="fire") == 1
        svc.close()

    def test_healthy_run_keeps_budget(self, ws256):
        reg = telemetry.Registry()
        slo = SLOEngine(serve_objectives(slo_rounds=1024),
                        registry=reg, log=EventLog())
        svc = _svc(ws256, registry=reg, slo=slo)
        start_budget = svc.stats()["admit_budget"]
        for s in range(1, 5):
            svc.submit(s)
        for _ in range(6):
            svc.tick()
        assert slo.firing() == []
        assert svc.stats()["admit_budget"] >= start_budget
        svc.close()

    def test_shed_and_heal_streams_fed(self, ws256):
        from p2pnetwork_tpu.serve.service import Rejected
        reg = telemetry.Registry()
        slo = SLOEngine(serve_objectives(slo_rounds=1024),
                        registry=reg, log=EventLog())
        svc = _svc(ws256, capacity=4, queue_depth=1, registry=reg, slo=slo)
        shed = 0
        for s in range(1, 20):
            try:
                svc.submit(s)
            except Rejected:
                shed += 1
        assert shed > 0
        svc.tick()
        snap = slo.snapshot()["objectives"]
        assert snap["shed_rate"]["samples"] == 19  # every submit observed
        assert snap["heal_rate"]["samples"] == 1   # one dispatching tick
        svc.close()


# ------------------------------------- chaos-under-load acceptance row


class TestChaosPerfettoAcceptance:
    def _drive(self, svc, n_tickets=3, ticks=8):
        tids = [svc.submit(s) for s in range(1, n_tickets + 1)]
        for _ in range(ticks):
            svc.tick()
        recs = [svc.poll(t) for t in tids]
        svc.close()
        return tids, recs

    def test_faulted_ticket_one_trace_tree_bit_identical(
            self, ws256, monkeypatch, no_dispatch_chaos):
        heal = RetryPolicy(max_attempts=3, backoff_base_s=0.0)
        # Reference: heal-configured, UNfaulted, UNinstrumented.
        ref = _svc(ws256, heal=heal, record_seen_hash=True)
        ref_tids, ref_recs = self._drive(ref)
        assert all(r["status"] == "done" for r in ref_recs)

        # Chaos run: a one-shot silent corruption of the first chunk's
        # carry (zeroed seen words -> monotonicity IntegrityViolation)
        # plus an armed chip-loss at a later dispatch; tracer on.
        real = engine.run_batch_until_coverage
        armed = {"on": True}

        def corrupting(graph, protocol, batch, key, **kw):
            b, out = real(graph, protocol, batch, key, **kw)
            if armed["on"]:
                armed["on"] = False
                b = dataclasses.replace(b, seen=jnp.zeros_like(b.seen))
            return b, out

        monkeypatch.setattr(engine, "run_batch_until_coverage", corrupting)
        install_dispatch_chaos(DispatchChaos(preempt_at=(2,)))
        t = spans.Tracer("chaos-serve")
        prev = spans.install_tracer(t)
        try:
            reg = telemetry.Registry()
            svc = _svc(ws256, heal=heal, record_seen_hash=True,
                       registry=reg)
            tids, recs = self._drive(svc)
        finally:
            spans.install_tracer(prev)
        # Per-ticket results bit-identical to the unfaulted,
        # uninstrumented reference (seen hashes included).
        assert tids == ref_tids
        assert recs == ref_recs
        assert reg.value("quake_integrity_failures_total",
                         kind="monotonicity") == 1
        assert reg.value("heal_rollbacks_total", source="retained") >= 1
        assert reg.value("serve_healed_ticks_total") == 2

        # One Perfetto document per faulted ticket: the whole lifecycle
        # under a single trace id.
        tr = ticket_trace(tids[0])
        doc = t.to_chrome(trace_id=tr)
        json.dumps(doc)  # Perfetto-loadable
        names = [e["name"] for e in doc["traceEvents"]]
        assert all(e["args"]["trace_id"] == tr for e in doc["traceEvents"])
        chain = ["ticket_submit", "ticket_admit", "ticket_chunk",
                 "ticket_fault", "ticket_integrity_fail",
                 "ticket_heal_retry", "ticket_done"]
        first = {n: names.index(n) for n in chain}
        assert [first[n] for n in chain] == sorted(first[n] for n in chain)
        assert "ticket_heal_recovered" in names
        fails = [e for e in doc["traceEvents"]
                 if e["name"] == "ticket_integrity_fail"]
        assert fails[0]["args"]["kind"] == "monotonicity"
        assert fails[0]["args"]["leaf"] == "seen"
        kinds = {e["args"]["kind"] for e in doc["traceEvents"]
                 if e["name"] == "ticket_fault"}
        assert kinds == {"integrity", "preempt"}
        # The chunk events name their faulted ticks.
        chunk_faulted = [e["args"]["faulted"] for e in doc["traceEvents"]
                         if e["name"] == "ticket_chunk"]
        assert chunk_faulted.count(True) == 2
        # The heal plane's own (non-ticket) events landed too.
        assert t.find("heal_retry") and t.find("heal_rollback")
        assert t.find("heal_recovered") and t.find("dispatch_fault")

    def test_heal_report_driver_confined_shape(self, ws256, monkeypatch,
                                               no_dispatch_chaos):
        heal = RetryPolicy(max_attempts=3, backoff_base_s=0.0)
        real = engine.run_batch_until_coverage
        armed = {"on": True}

        def corrupting(graph, protocol, batch, key, **kw):
            b, out = real(graph, protocol, batch, key, **kw)
            if armed["on"]:
                armed["on"] = False
                b = dataclasses.replace(b, seen=jnp.zeros_like(b.seen))
            return b, out

        monkeypatch.setattr(engine, "run_batch_until_coverage", corrupting)
        svc = _svc(ws256, heal=heal)
        svc.submit(1)
        svc.tick()
        rep = svc._healer.last_report
        assert rep["healed"] and not rep["exhausted"]
        assert rep["attempts"] == 2 and not rep["fallback"]
        (ev,) = rep["events"]
        assert ev["failure"] == "integrity"
        assert ev["integrity_kind"] == "monotonicity"
        assert ev["leaf"] == "seen"
        assert ev["attempt"] == 1
        svc.close()


# --------------------------------------------- determinism satellites


class TestBitIdentityUnderTrace:
    def test_traced_chaos_healed_drive_matches_untraced(
            self, ws256, no_dispatch_chaos):
        # Satellite 4: tracer-on == tracer-off for a chaos-healed serve
        # run over seeded traffic (per-ticket records, hashes included).
        pattern = TrafficPattern(ticks=8, rate=2.0, coverage_target=0.9)
        sched = generate(pattern, ws256.n_nodes, seed=7)
        heal = RetryPolicy(max_attempts=3, backoff_base_s=0.0)

        ref = _svc(ws256, heal=heal, record_seen_hash=True)
        drive(ref, sched)
        ref.close()

        install_dispatch_chaos(DispatchChaos(wedge_at=(1,)))
        t = spans.Tracer("traced-drive")
        prev = spans.install_tracer(t)
        try:
            svc = _svc(ws256, heal=heal, record_seen_hash=True)
            drive(svc, sched)
            svc.close()
        finally:
            spans.install_tracer(prev)
        assert svc.tickets() == ref.tickets()
        ticket_traces = [tid for tid in t.traces() if tid.startswith("tkt-")]
        assert len(ticket_traces) == len(ref.tickets())

    def test_sight_scenario_registered_builtin(self):
        from p2pnetwork_tpu.analysis.race.scenarios import builtin_names
        assert "sight_scrape_under_serve" in builtin_names()


class TestEngineBatchSummaryEvent:
    def test_batch_summary_point_inside_batch_run(self, ws256, tracer):
        from p2pnetwork_tpu.models.messagebatch import BatchFlood
        proto = BatchFlood()
        batch = proto.init(ws256, [1, 2], capacity=4)
        _, out = engine.run_batch_until_coverage(
            ws256, proto, batch, KEY, max_rounds=64, donate=False)
        (ev,) = tracer.find("batch_summary")
        assert ev.args["rounds"] == int(out["rounds"])
        assert ev.args["newly_completed"] == 2
        (run,) = tracer.find("batch_run")
        assert ev.parent_id == run.span_id


# ------------------------------------------------------ overhead ratchet


class TestOverheadRatchet:
    @pytest.mark.slow
    def test_instrumented_serve_tick_within_ratchet(self, ws256,
                                                    no_dispatch_chaos):
        # Acceptance: tracer+SLO+profiler on <= 1.10x off for the serve
        # tick path (ratio-based, interleaved best-of-7 — the PR-12
        # flight-recorder ratchet extended to the serving plane).
        g = G.watts_strogatz(20_000, 8, 0.1, seed=0)

        def run(instrumented):
            t = prev = slo = None
            if instrumented:
                t = spans.Tracer("ratchet", max_spans=200_000)
                prev = spans.install_tracer(t)
                slo = SLOEngine(serve_objectives(slo_rounds=1024),
                                registry=telemetry.Registry(),
                                log=EventLog())
            try:
                svc = _svc(g, capacity=32, chunk_rounds=8,
                           slo=slo)
                # A rolling submit stream keeps every timed tick
                # dispatching a real batch — idle ticks would let the
                # fixed per-tick instrumentation dominate the ratio.
                src = 1
                t0 = time.perf_counter()
                for _ in range(6):
                    for _ in range(8):
                        svc.submit(src)
                        src += 1
                    svc.tick()
                wall = time.perf_counter() - t0
                svc.close()
            finally:
                if instrumented:
                    spans.install_tracer(prev)
            return wall

        run(False)  # warm the engine program before timing
        run(True)
        offs, ons = [], []
        for _ in range(7):  # interleaved best-of-7, CPU-noise-robust
            offs.append(run(False))
            ons.append(run(True))
        ratio = min(ons) / min(offs)
        assert ratio <= 1.10, (
            f"graftsight serve-tick overhead {ratio:.3f}x exceeds the "
            f"1.10x ratchet (off {min(offs):.4f}s on {min(ons):.4f}s)")

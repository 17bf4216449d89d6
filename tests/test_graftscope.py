"""graftscope tests: flight recorder, trace plane, history ring, wiring.

Covers the PR-12 observability plane end to end:

- flight-recorder parity: recorder-on runs bit-identical to recorder-off
  across engine (run_from / coverage_from / batch) and sharded (flood +
  batch, BOTH comm backends), ring contents sane, wrap semantics, ring
  donation honored, and the slow-marked <= 1.10x overhead ratchet on a
  100k-node WS flood;
- trace plane: span trees, thread-local nesting, Chrome/Perfetto +
  JSONL exporters, lane lifecycle events
  (submit/admit/resume/complete/freeze/retire), supervise chunk
  boundaries, and the batched-run Perfetto schema acceptance;
- history ring: sampling, capacity bound, per-run auto-sampling, and
  the ``/history`` + ``/trace`` endpoints (incl. an N-thread concurrent
  scrape hammer and a graftrace-seam scrape storm);
- satellites: Prometheus label/help escaping pin, jaxhooks install
  idempotence, bench profiler bracket.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2pnetwork_tpu import concurrency, telemetry
from p2pnetwork_tpu.models.flood import Flood
from p2pnetwork_tpu.models.messagebatch import BatchFlood
from p2pnetwork_tpu.sim import engine, flightrec
from p2pnetwork_tpu.sim import graph as G
from p2pnetwork_tpu.telemetry import export, history, jaxhooks, spans

pytestmark = pytest.mark.scope


@pytest.fixture
def fresh_registry():
    fresh = telemetry.Registry()
    prev = telemetry.set_default_registry(fresh)
    yield fresh
    telemetry.set_default_registry(prev)


@pytest.fixture
def fresh_history():
    fresh = history.History()
    prev = history.set_default_history(fresh)
    yield fresh
    history.set_default_history(prev)


@pytest.fixture
def tracer():
    t = spans.Tracer("test-run")
    prev = spans.install_tracer(t)
    yield t
    spans.install_tracer(prev)


@pytest.fixture(scope="module")
def ws_graph():
    return G.watts_strogatz(512, 4, 0.1, seed=0)


def _assert_batch_equal(b1, b2):
    import dataclasses

    for f in dataclasses.fields(b1):
        a = np.asarray(getattr(b1, f.name))
        b = np.asarray(getattr(b2, f.name))
        assert np.array_equal(a, b), f"batch leaf {f.name} diverges"


def _assert_out_equal(o1, o2):
    assert set(o1) == set(o2)
    for k in o1:
        v1, v2 = o1[k], o2[k]
        if isinstance(v1, np.ndarray):
            assert np.array_equal(v1, v2), k
        else:
            assert v1 == v2, (k, v1, v2)


# ------------------------------------------------------ flight recorder unit


class TestFlightRecorderUnit:
    def test_capacity_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            flightrec.FlightRecorder(capacity=0)

    def test_init_shape_and_dtype(self):
        ring = flightrec.FlightRecorder(capacity=5).init()
        assert ring.shape == (5, len(flightrec.REC_COLS))
        assert ring.dtype == jnp.float32

    def test_trim_no_wrap(self):
        ring = np.arange(40, dtype=np.float32).reshape(8, 5)
        fr = flightrec.trim(ring, 3)
        assert fr.rows.shape == (3, 5)
        assert fr.dropped == 0 and fr.rounds == 3
        assert np.array_equal(fr.rows, ring[:3])

    def test_trim_wrap_keeps_last_capacity_rounds(self):
        # 10 rounds into a 4-deep ring: rounds 7..10 survive, slot
        # 10 % 4 = 2 is the oldest surviving row's position.
        cap, rounds = 4, 10
        ring = np.zeros((cap, len(flightrec.REC_COLS)), dtype=np.float32)
        for r in range(rounds):
            ring[r % cap, 0] = r + 1  # the round column
        fr = flightrec.trim(ring, rounds)
        assert fr.dropped == rounds - cap
        assert fr.column("round").tolist() == [7.0, 8.0, 9.0, 10.0]

    def test_as_dict_roundtrips_json(self):
        fr = flightrec.trim(
            np.ones((4, len(flightrec.REC_COLS)), np.float32), 2)
        doc = json.loads(json.dumps(fr.as_dict()))
        assert doc["rounds"] == 2 and doc["capacity"] == 4
        assert set(doc["columns"]) == set(flightrec.REC_COLS)
        assert len(doc["columns"]["round"]) == 2


# ------------------------------------------------------ engine recorder


class TestEngineRecorder:
    def test_coverage_from_parity_and_record(self, ws_graph):
        g = ws_graph
        proto = Flood(source=0)
        key = jax.random.key(0)
        s1, o1 = engine.run_until_coverage_from(
            g, proto, proto.init(g, key), key, donate=False, max_rounds=64)
        s2, o2 = engine.run_until_coverage_from(
            g, proto, proto.init(g, key), key, donate=False, max_rounds=64,
            recorder=flightrec.FlightRecorder(capacity=128))
        fr = o2.pop("flight_record")
        _assert_out_equal(o1, o2)
        assert np.array_equal(np.asarray(s1.seen), np.asarray(s2.seen))
        assert np.array_equal(np.asarray(s1.frontier),
                              np.asarray(s2.frontier))
        # Ring contents: rounds rows, monotone round index, message
        # totals cumulative, final coverage at/above target.
        assert fr.rows.shape[0] == o1["rounds"] and fr.dropped == 0
        assert fr.column("round").tolist() == [
            float(i + 1) for i in range(o1["rounds"])]
        assert np.all(np.diff(fr.column("total")) >= 0)
        assert fr.column("total")[-1] == float(o1["messages"])
        assert fr.column("coverage")[-1] >= 0.99
        assert np.all(fr.column("ici_bytes") == 0)
        assert np.all(fr.column("active_lanes") == 1)

    def test_coverage_from_recorder_wraps(self, ws_graph):
        g = ws_graph
        proto = Flood(source=0)
        key = jax.random.key(0)
        _, o = engine.run_until_coverage_from(
            g, proto, proto.init(g, key), key, donate=False, max_rounds=64,
            recorder=flightrec.FlightRecorder(capacity=4))
        fr = o["flight_record"]
        assert o["rounds"] > 4  # the premise: this run wraps
        assert fr.rows.shape[0] == 4
        assert fr.dropped == o["rounds"] - 4
        assert fr.column("round").tolist() == [
            float(r) for r in range(o["rounds"] - 3, o["rounds"] + 1)]

    def test_coverage_from_steps_per_round_parity(self, ws_graph):
        g = ws_graph
        proto = Flood(source=0)
        key = jax.random.key(3)
        s1, o1 = engine.run_until_coverage_from(
            g, proto, proto.init(g, key), key, donate=False, max_rounds=64,
            steps_per_round=4)
        s2, o2 = engine.run_until_coverage_from(
            g, proto, proto.init(g, key), key, donate=False, max_rounds=64,
            steps_per_round=4,
            recorder=flightrec.FlightRecorder(capacity=64))
        fr = o2.pop("flight_record")
        _assert_out_equal(o1, o2)
        assert np.array_equal(np.asarray(s1.seen), np.asarray(s2.seen))
        # Frozen sub-steps of the final super-step write no rows: row
        # count equals APPLIED rounds exactly.
        assert fr.rows.shape[0] == o1["rounds"]
        assert fr.column("round").tolist() == [
            float(i + 1) for i in range(o1["rounds"])]

    def test_run_from_parity_and_record(self, ws_graph):
        g = ws_graph
        proto = Flood(source=2)
        key = jax.random.key(1)
        s1, stats1 = engine.run_from(g, proto, proto.init(g, key), key, 6,
                                     donate=False)
        s2, stats2, fr = engine.run_from(
            g, proto, proto.init(g, key), key, 6, donate=False,
            recorder=flightrec.FlightRecorder(capacity=16))
        assert np.array_equal(np.asarray(s1.seen), np.asarray(s2.seen))
        for k in stats1:
            assert np.array_equal(np.asarray(stats1[k]),
                                  np.asarray(stats2[k])), k
        # The ring's per-round columns ARE the scan stats, recorded
        # device-side.
        assert np.array_equal(
            fr.column("new"),
            np.asarray(stats1["messages"]).astype(np.float32))
        assert np.array_equal(
            fr.column("coverage"),
            np.asarray(stats1["coverage"]).astype(np.float32))
        assert np.array_equal(
            fr.column("occupancy"),
            np.asarray(stats1["frontier_occupancy"]).astype(np.float32))

    def test_batch_parity_and_record(self, ws_graph):
        g = ws_graph
        proto = BatchFlood()
        key = jax.random.key(2)
        sources = np.arange(40, dtype=np.int32) * 7 % 512
        b1 = proto.init(g, sources)
        b2 = proto.init(g, sources)
        r1, o1 = engine.run_batch_until_coverage(
            g, proto, b1, key, donate=False, max_rounds=64)
        r2, o2 = engine.run_batch_until_coverage(
            g, proto, b2, key, donate=False, max_rounds=64,
            recorder=flightrec.FlightRecorder(capacity=128))
        fr = o2.pop("flight_record")
        _assert_out_equal(o1, o2)
        _assert_batch_equal(r1, r2)
        assert fr.rows.shape[0] == o1["rounds"]
        # active_lanes starts at B and ends at the summary's count.
        assert fr.column("active_lanes")[0] == float(len(sources))
        assert fr.column("active_lanes")[-1] == float(o1["active_lanes"])
        assert fr.column("total")[-1] == float(o1["messages"])

    def test_recorder_ring_donated_and_honored(self, ws_graph):
        from p2pnetwork_tpu.analysis.ir.donation import check_aliasing

        g = ws_graph
        proto = BatchFlood()
        batch = proto.init(g, np.arange(32, dtype=np.int32) * 5 % 512)
        counts = check_aliasing(
            engine._batch_loop_rec_donating,
            (g, proto, batch, jax.random.key(0),
             flightrec.FlightRecorder(capacity=32).init()),
            10, {"max_rounds": 64})
        assert counts["requested"] == counts["honored"] == 10

    def test_recorder_donation_invalidates_state(self, ws_graph):
        g = ws_graph
        proto = Flood(source=0)
        key = jax.random.key(0)
        state = proto.init(g, key)
        # one undonated step first so leaves are distinct buffers
        state, _ = engine.run_from(g, proto, state, key, 1, donate=False)
        engine.run_until_coverage_from(
            g, proto, state, key, max_rounds=4,
            recorder=flightrec.FlightRecorder(capacity=8))
        with pytest.raises(ValueError, match="donated"):
            engine.run_until_coverage_from(g, proto, state, key,
                                           max_rounds=4)

    @pytest.mark.slow
    def test_recorder_overhead_ratchet(self):
        # Acceptance: recorder-on wall <= 1.10x recorder-off on a
        # 100k-node WS flood (ratio-based — no absolute wall clocks).
        g = G.watts_strogatz(100_000, 10, 0.1, seed=0)
        proto = Flood(source=0)
        key = jax.random.key(0)
        rec = flightrec.FlightRecorder(capacity=256)

        def run(recorder):
            state = proto.init(g, key)
            t0 = __import__("time").perf_counter()
            _, out = engine.run_until_coverage_from(
                g, proto, state, key, donate=False, max_rounds=64,
                recorder=recorder)
            return __import__("time").perf_counter() - t0, out

        run(None)  # warm both compiled programs before timing
        run(rec)
        offs, ons = [], []
        for _ in range(7):  # interleaved best-of-7, CPU-noise-robust
            offs.append(run(None)[0])
            ons.append(run(rec)[0])
        ratio = min(ons) / min(offs)
        assert ratio <= 1.10, (
            f"flight recorder overhead {ratio:.3f}x exceeds the 1.10x "
            f"ratchet (off {min(offs):.4f}s on {min(ons):.4f}s)")


# ------------------------------------------------------ sharded recorder


@pytest.fixture(scope="module")
def sharded_setup():
    from p2pnetwork_tpu.parallel import mesh as M
    from p2pnetwork_tpu.parallel import sharded as SH

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    g = G.watts_strogatz(512, 4, 0.1, seed=0)
    mesh = M.ring_mesh(8)
    sg = SH.shard_graph(g, mesh)
    return g, mesh, sg


class TestShardedRecorder:
    @pytest.mark.parametrize("comm", ["ppermute", "pallas"])
    def test_flood_parity_and_ici_column(self, sharded_setup, comm):
        from p2pnetwork_tpu.parallel import sharded as SH

        g, mesh, sg = sharded_setup
        s1, o1 = SH.flood_until_coverage(
            sg, mesh, 0, coverage_target=0.99, max_rounds=64, comm=comm)
        s2, o2 = SH.flood_until_coverage(
            sg, mesh, 0, coverage_target=0.99, max_rounds=64, comm=comm,
            recorder=flightrec.FlightRecorder(capacity=64))
        fr = o2.pop("flight_record")
        _assert_out_equal(o1, o2)
        assert np.array_equal(np.asarray(s1), np.asarray(s2))
        assert fr.rows.shape[0] == o1["rounds"]
        # The ici column carries the static per-round comm-census
        # estimate — nonzero, constant, and backend-agnostic in price
        # (PR 11 pins pallas DMA pricing == ppermute pricing).
        ici = fr.column("ici_bytes")
        assert ici[0] > 0 and np.all(ici == ici[0])
        # coverage column is the psum'd covered-node count here.
        assert fr.column("coverage")[-1] >= 0.99 * 512

    @pytest.mark.parametrize("comm", ["ppermute", "pallas"])
    def test_batch_parity_both_backends(self, sharded_setup, comm):
        from p2pnetwork_tpu.parallel import sharded as SH

        g, mesh, sg = sharded_setup
        proto = BatchFlood()
        sources = np.arange(40, dtype=np.int32) * 3 % 512
        b1 = proto.init(g, sources)
        b2 = proto.init(g, sources)
        r1, o1 = SH.run_batch_until_coverage(
            sg, mesh, proto, b1, max_rounds=64, comm=comm, donate=False)
        r2, o2 = SH.run_batch_until_coverage(
            sg, mesh, proto, b2, max_rounds=64, comm=comm, donate=False,
            recorder=flightrec.FlightRecorder(capacity=64))
        fr = o2.pop("flight_record")
        _assert_out_equal(o1, o2)
        _assert_batch_equal(r1, r2)
        assert fr.column("ici_bytes")[0] > 0

    def test_sharded_rows_match_engine_rows(self, sharded_setup):
        # The sharded batch loop's ring rows must equal the engine
        # loop's on the same batch — every column except the ici
        # estimate (single-chip records 0 there).
        from p2pnetwork_tpu.parallel import sharded as SH

        g, mesh, sg = sharded_setup
        proto = BatchFlood()
        sources = np.arange(40, dtype=np.int32) * 3 % 512
        rec = flightrec.FlightRecorder(capacity=64)
        _, oe = engine.run_batch_until_coverage(
            g, proto, proto.init(g, sources), jax.random.key(0),
            donate=False, max_rounds=64, recorder=rec)
        _, os_ = SH.run_batch_until_coverage(
            sg, mesh, proto, proto.init(g, sources), max_rounds=64,
            donate=False, recorder=rec)
        re_, rs = oe["flight_record"], os_["flight_record"]
        ici_col = flightrec.REC_COLS.index("ici_bytes")
        assert np.array_equal(re_.rows[:, :ici_col], rs.rows[:, :ici_col])

    def test_adaptive_path_refuses_recorder(self, sharded_setup):
        from p2pnetwork_tpu.parallel import sharded as SH

        g, mesh, _ = sharded_setup
        sg = SH.shard_graph(g, mesh, source_csr=True)
        with pytest.raises(ValueError, match="adaptive"):
            SH.flood_until_coverage(
                sg, mesh, 0, adaptive_k=64,
                recorder=flightrec.FlightRecorder())


# ------------------------------------------------------------ trace plane


class TestTracer:
    def test_span_tree_and_parent_links(self):
        clock = iter(float(i) for i in range(100))
        t = spans.Tracer("root", clock=lambda: next(clock))
        with t.span("outer", kind="a") as outer:
            t.point("inner-event", lane=3)
            with t.span("inner") as inner:
                pass
        by_id = {sp.span_id: sp for sp in t.spans()}
        names = {sp.name: sp for sp in t.spans()}
        assert names["outer"].parent_id == t.root
        assert names["inner-event"].parent_id == outer
        assert names["inner"].parent_id == outer
        assert by_id[inner].t1 is not None
        assert names["root"].parent_id is None
        assert names["inner-event"].args == {"lane": 3}

    def test_thread_local_current_stack(self):
        t = spans.Tracer("root")
        seen = {}

        def worker():
            # A foreign thread has no enclosing span context: its
            # events parent to the ROOT, not whatever the main thread
            # currently has open.
            seen["sid"] = t.point("from-thread")

        with t.span("main-only"):
            th = concurrency.thread(target=worker, name="spans-worker")
            th.start()
            th.join(timeout=10)
        sp = [s for s in t.spans() if s.span_id == seen["sid"]][0]
        assert sp.parent_id == t.root

    def test_to_chrome_schema(self):
        t = spans.Tracer("root")
        with t.span("work", step=1):
            t.point("evt")
        t.close()
        doc = t.to_chrome()
        assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
        assert doc["metadata"]["dropped_spans"] == 0  # graftsight's honesty
        assert doc["metadata"]["spans"] == len(doc["traceEvents"])
        for ev in doc["traceEvents"]:
            assert ev["ph"] == "X" and ev["cat"] == "graftscope"
            assert ev["dur"] >= 0 and ev["ts"] > 0
            assert "span_id" in ev["args"] and "parent_id" in ev["args"]
            assert ev["args"]["trace_id"] == t.trace_id
        json.dumps(doc)  # must serialize

    def test_to_records_shared_jsonl_schema(self, tmp_path):
        t = spans.Tracer("root")
        with t.span("work"):
            pass
        recs = t.to_records()
        for rec in recs:
            assert rec["type"] == "event"
            assert set(rec) == {"type", "name", "ts", "labels", "data"}
            assert rec["labels"]["trace"] == t.trace_id
        path = str(tmp_path / "trace.jsonl")
        n = t.write_jsonl(path)
        lines = open(path, encoding="utf-8").read().splitlines()
        assert len(lines) == n == len(recs)
        json.loads(lines[0])

    def test_emit_noop_without_tracer(self):
        assert spans.current_tracer() is None or True  # state-agnostic
        prev = spans.uninstall_tracer()
        try:
            spans.emit("nobody-listening", lane=1)
            with spans.span("nobody") as sid:
                assert sid is None
        finally:
            spans.install_tracer(prev)

    def test_max_spans_bound_drops_oldest_keeps_root(self):
        t = spans.Tracer("root", max_spans=3)
        for i in range(6):
            t.point(f"e{i}")
        assert [s.name for s in t.spans()] == ["root", "e3", "e4", "e5"]
        assert t.dropped_spans == 3
        t.close()
        assert [s for s in t.spans() if s.name == "root"][0].t1 is not None

    def test_install_returns_previous(self):
        t1, t2 = spans.Tracer("a"), spans.Tracer("b")
        prev0 = spans.install_tracer(t1)
        try:
            assert spans.install_tracer(t2) is t1
            assert spans.current_tracer() is t2
        finally:
            spans.install_tracer(prev0)


class TestLaneLifecycleEvents:
    def test_admit_retire_emit(self, ws_graph, tracer):
        proto = BatchFlood()
        batch = proto.init(ws_graph, [1, 2], capacity=40)
        submits = tracer.find("lane_submit")
        assert sorted(s.args["lane"] for s in submits) == [0, 1]
        assert {s.args["source"] for s in submits} == {1, 2}
        proto.retire(batch, [1])
        retires = tracer.find("lane_retire")
        assert [s.args["lane"] for s in retires] == [1]

    def test_admit_under_tracer_keeps_batch_identical(self, ws_graph):
        # Regression: the lane_submit emit loop once shadowed the `src`
        # device array, so tracing-on admits scattered the LAST source
        # id into every lane's metadata. Tracing must change NOTHING
        # about the batch.
        proto = BatchFlood()
        sources = [3, 7, 11]
        b_off = proto.init(ws_graph, sources, capacity=8)
        t = spans.Tracer("admit-regression")
        prev = spans.install_tracer(t)
        try:
            b_on = proto.init(ws_graph, sources, capacity=8)
        finally:
            spans.install_tracer(prev)
        assert np.asarray(b_on.source)[:3].tolist() == sources
        _assert_batch_equal(b_off, b_on)

    def test_run_emits_admit_complete_under_run_span(self, ws_graph,
                                                     tracer, fresh_registry,
                                                     fresh_history):
        proto = BatchFlood()
        batch = proto.init(ws_graph, np.arange(8, dtype=np.int32) + 1)
        engine.run_batch_until_coverage(
            ws_graph, proto, batch, jax.random.key(0), donate=True,
            max_rounds=64)
        runs = tracer.find("batch_run")
        assert len(runs) == 1 and runs[0].args["loop"] == "engine"
        admits = tracer.find("lane_admit")
        completes = tracer.find("lane_complete")
        assert sorted(a.args["lane"] for a in admits) == list(range(8))
        assert sorted(c.args["lane"] for c in completes) == list(range(8))
        for ev in admits + completes:
            assert ev.parent_id == runs[0].span_id
        assert tracer.find("lane_freeze") == []

    def test_freeze_and_resume_events(self, ws_graph, tracer,
                                      fresh_registry, fresh_history):
        proto = BatchFlood()
        batch = proto.init(ws_graph, np.arange(8, dtype=np.int32) + 1)
        # max_rounds=1 cuts every lane off -> freeze events, no completes
        batch, _ = engine.run_batch_until_coverage(
            ws_graph, proto, batch, jax.random.key(0), donate=True,
            max_rounds=1)
        assert sorted(s.args["lane"]
                      for s in tracer.find("lane_freeze")) == list(range(8))
        assert tracer.find("lane_complete") == []
        # second call resumes the cut lanes -> resume + complete
        engine.run_batch_until_coverage(
            ws_graph, proto, batch, jax.random.key(1), donate=True,
            max_rounds=64)
        assert sorted(s.args["lane"]
                      for s in tracer.find("lane_resume")) == list(range(8))
        assert sorted(s.args["lane"]
                      for s in tracer.find("lane_complete")) == list(range(8))


class TestSuperviseSpans:
    def test_chunk_checkpoint_resume_events(self, tmp_path, tracer,
                                            fresh_registry, fresh_history):
        from p2pnetwork_tpu.supervise.runner import SupervisedRun

        g = G.watts_strogatz(128, 4, 0.1, seed=1)
        proto = Flood(source=0)
        key = jax.random.key(0)
        store = str(tmp_path / "trail")
        run = SupervisedRun(g, proto, store, chunk_rounds=3)
        run.run_rounds(key, 9)
        sup = tracer.find("supervised_run")
        assert len(sup) == 1 and sup[0].args["mode"] == "rounds"
        chunks = tracer.find("chunk")
        assert len(chunks) == 3
        assert all(c.parent_id == sup[0].span_id for c in chunks)
        assert [c.args["round"] for c in chunks] == [3, 6, 9]
        assert len(tracer.find("checkpoint")) >= 1
        assert tracer.find("resume") == []
        # a second harness over the same trail resumes -> resume event
        run2 = SupervisedRun(g, proto, store, chunk_rounds=3)
        run2.run_rounds(key, 12)
        resumes = tracer.find("resume")
        assert len(resumes) == 1 and resumes[0].args["round"] == 9


# ------------------------------------------------------------ history ring


class TestHistory:
    def test_sample_gauges_only_and_series(self):
        reg = telemetry.Registry()
        reg.gauge("h_gauge", "g", ("who",)).labels("a").set(1.0)
        reg.counter("h_counter", "c").inc(5)
        h = history.History(reg, capacity=8)
        h.sample(ts=1.0)
        reg.gauge("h_gauge", "g", ("who",)).labels("a").set(2.5)
        h.sample(ts=2.0)
        assert h.series("h_gauge", "a") == [(1.0, 1.0), (2.0, 2.5)]
        assert h.series("h_counter") == []  # counters are not sampled
        assert h.series("h_gauge", "zz") == []  # unknown child

    def test_capacity_bound(self):
        reg = telemetry.Registry()
        g = reg.gauge("b_gauge", "g")
        h = history.History(reg, capacity=3)
        for i in range(7):
            g.set(float(i))
            h.sample(ts=float(i))
        assert [ts for ts, _ in h.series("b_gauge")] == [4.0, 5.0, 6.0]
        assert len(h.rows()) == 3

    def test_snapshot_json_shape(self):
        reg = telemetry.Registry()
        reg.gauge("s_gauge", "g", ("l",)).labels("x").set(7.0)
        h = history.History(reg, capacity=4)
        h.sample(ts=3.0)
        doc = json.loads(json.dumps(h.snapshot()))
        assert doc["capacity"] == 4 and doc["samples"] == 1
        series = doc["series"]["s_gauge"]
        assert series == [{"labels": ["x"], "points": [[3.0, 7.0]]}]

    def test_none_registry_follows_default_swaps(self):
        h = history.History(None, capacity=4)
        fresh = telemetry.Registry()
        prev = telemetry.set_default_registry(fresh)
        try:
            fresh.gauge("follow_gauge", "g").set(9.0)
            h.sample(ts=1.0)
        finally:
            telemetry.set_default_registry(prev)
        assert h.series("follow_gauge") == [(1.0, 9.0)]

    def test_engine_runs_auto_sample(self, ws_graph, fresh_registry,
                                     fresh_history):
        proto = BatchFlood()
        batch = proto.init(ws_graph, [3, 4, 5])
        engine.run_batch_until_coverage(ws_graph, proto, batch,
                                        jax.random.key(0), max_rounds=64)
        series = fresh_history.series("sim_batch_active_lanes")
        assert len(series) == 1 and series[0][1] == 0.0


# --------------------------------------------------------- httpd endpoints


class TestHttpdEndpoints:
    def _get(self, port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, r.read().decode("utf-8")

    def test_history_and_trace_endpoints(self, fresh_registry):
        reg = fresh_registry
        reg.gauge("sim_batch_active_lanes", "x").set(3.0)
        hist = history.History(reg, capacity=8)
        hist.sample(ts=1.0)
        tracer = spans.Tracer("serve")
        with tracer.span("work"):
            pass
        with telemetry.MetricsServer(reg, port=0, history=hist,
                                     tracer=tracer) as srv:
            code, body = self._get(srv.port, "/history")
            assert code == 200
            doc = json.loads(body)
            assert doc["series"]["sim_batch_active_lanes"][0]["points"] \
                == [[1.0, 3.0]]
            code, body = self._get(srv.port, "/trace")
            assert code == 200
            doc = json.loads(body)
            assert {e["name"] for e in doc["traceEvents"]} >= {"work"}

    def test_trace_endpoint_empty_without_tracer(self, fresh_registry):
        prev = spans.uninstall_tracer()
        try:
            with telemetry.MetricsServer(fresh_registry, port=0) as srv:
                code, body = self._get(srv.port, "/trace")
        finally:
            spans.install_tracer(prev)
        assert code == 200
        assert json.loads(body)["traceEvents"] == []

    def test_concurrent_scrape_hammer(self, fresh_registry):
        # Satellite: N threads hammering /metrics, /history and
        # /metrics.json while counters/gauges mutate — every response
        # 200 and parseable.
        reg = fresh_registry
        hist = history.History(reg, capacity=32)
        stop = concurrency.event()
        errors = []

        def mutate():
            c = reg.counter("hammer_total", "c", ("who",))
            g = reg.gauge("hammer_gauge", "g")
            i = 0
            while not stop.is_set():
                c.labels("a").inc()
                g.set(float(i))
                hist.sample()
                i += 1

        def scrape(port, path):
            try:
                for _ in range(20):
                    code, body = self._get(port, path)
                    assert code == 200
                    if path == "/metrics":
                        for line in body.splitlines():
                            assert line.startswith("#") or " " in line
                    else:
                        json.loads(body)
            except Exception as e:  # surfaced after joins
                errors.append(f"{path}: {type(e).__name__}: {e}")

        with telemetry.MetricsServer(reg, port=0, history=hist) as srv:
            mut = concurrency.thread(target=mutate, name="hammer-mutate")
            mut.start()
            scrapers = [
                concurrency.thread(target=scrape, args=(srv.port, path),
                                   name=f"hammer-{i}")
                for i, path in enumerate(
                    ["/metrics", "/history", "/metrics.json"] * 3)
            ]
            for t in scrapers:
                t.start()
            for t in scrapers:
                t.join(timeout=60)
            stop.set()
            mut.join(timeout=10)
        assert errors == []

    def test_scrape_storm_under_graftrace_seam(self):
        # Satellite: the scrape-side snapshot paths (to_prometheus,
        # history sample/snapshot) driven through the graftrace
        # concurrency seam while counters mutate — no HB race findings,
        # no deadlocks, across seeds.
        from p2pnetwork_tpu.analysis.race import explore
        from p2pnetwork_tpu.analysis.race.detector import watch

        def body():
            reg = watch(telemetry.Registry())
            hist = watch(history.History(reg, capacity=8))

            def mutate():
                g = reg.gauge("storm_gauge", "g")
                c = reg.counter("storm_total", "c", ("who",))
                for i in range(3):
                    g.set(float(i))
                    c.labels("a").inc()

            def scrape():
                for _ in range(2):
                    export.to_prometheus(reg)
                    hist.sample(ts=1.0)
                    hist.snapshot()

            ts = [concurrency.thread(target=f, name=nm)
                  for nm, f in (("mutate", mutate), ("scrape-a", scrape),
                                ("scrape-b", scrape))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

        for seed in (0, 1, 2):
            res = explore(body, seed=seed)
            assert res.errors == [], res.errors
            assert res.findings == [], [f.message for f in res.findings]


# -------------------------------------------------- perfetto acceptance


class TestPerfettoAcceptance:
    def test_batched_run_span_tree_and_history(self, ws_graph, tracer,
                                               fresh_registry,
                                               fresh_history):
        """Acceptance: a batched run (B >= 32, staggered admit/retire +
        one resume) exports Perfetto trace-event JSON whose span tree
        validates — every lane has admit -> complete/freeze spans
        nested under its run span — and /history serves the sampled
        sim_batch_active_lanes series for the same run."""
        g = ws_graph
        proto = BatchFlood()
        key = jax.random.key(0)
        sources = (np.arange(32, dtype=np.int32) * 11 % 500) + 1
        batch = proto.init(g, sources, capacity=40)
        # run 1: cut off at 1 round (stragglers freeze)...
        batch, o1 = engine.run_batch_until_coverage(
            g, proto, batch, key, max_rounds=1)
        assert o1["active_lanes"] == 32
        # ...resume to completion (one resume), then staggered
        # retire + a second admit wave into recycled lanes.
        batch, o2 = engine.run_batch_until_coverage(
            g, proto, batch, jax.random.key(1), max_rounds=64)
        assert o2["active_lanes"] == 0
        batch = proto.retire(batch, [0, 1, 2, 3])
        batch, lanes = proto.admit(g, batch, [7, 8, 9])
        batch, o3 = engine.run_batch_until_coverage(
            g, proto, batch, jax.random.key(2), max_rounds=64)
        tracer.close()

        doc = json.loads(json.dumps(tracer.to_chrome()))
        events = doc["traceEvents"]
        by_id = {e["args"]["span_id"]: e for e in events}

        def ancestors(ev):
            while ev["args"]["parent_id"] is not None:
                ev = by_id[ev["args"]["parent_id"]]
                yield ev

        runs = [e for e in events if e["name"] == "batch_run"]
        assert len(runs) == 3
        root = [e for e in events if e["args"]["parent_id"] is None]
        assert len(root) == 1  # one tree
        for e in runs:
            assert e["args"]["parent_id"] == root[0]["args"]["span_id"]

        def lane_events(name):
            return [e for e in events if e["name"] == name]

        # Every admitted lane: an admit span and a complete-or-freeze
        # span, both nested under a batch_run span, ordered in time.
        # (Lane ids recycle across retire/admit, so each end event must
        # be preceded by SOME admit of that lane, not the latest one.)
        admits = {}
        for e in lane_events("lane_admit"):
            admits.setdefault(e["args"]["lane"], []).append(e)
        ends = {}
        for e in lane_events("lane_complete") + lane_events("lane_freeze"):
            ends.setdefault(e["args"]["lane"], []).append(e)
        all_lanes = set(range(32)) | set(lanes.tolist())
        assert set(admits) == all_lanes
        for lane in all_lanes:
            assert lane in ends, f"lane {lane} never completed or froze"
            for e in admits[lane] + ends[lane]:
                anc = {a["name"] for a in ancestors(e)}
                assert "batch_run" in anc, (
                    f"{e['name']} of lane {lane} not nested under a "
                    f"batch_run span")
            for end in ends[lane]:
                assert any(a["ts"] <= end["ts"] for a in admits[lane]), (
                    f"lane {lane} has an end event before any admit")
        # every frozen lane later resumed
        frozen = {e["args"]["lane"] for e in lane_events("lane_freeze")}
        resumed = {e["args"]["lane"] for e in lane_events("lane_resume")}
        assert frozen == resumed == set(range(32))
        # completes carry the cumulative per-lane round count
        for e in lane_events("lane_complete"):
            assert e["args"]["rounds"] >= 1
        # retire + submit control-plane events present
        assert {e["args"]["lane"]
                for e in lane_events("lane_retire")} == {0, 1, 2, 3}
        assert len(lane_events("lane_submit")) == 32 + 3

        # /history serves the sampled sim_batch_active_lanes series for
        # the same run: one point per batched call, tracking 32 -> 0.
        with telemetry.MetricsServer(fresh_registry, port=0,
                                     history=fresh_history) as srv:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/history",
                    timeout=10) as r:
                hdoc = json.loads(r.read().decode("utf-8"))
        series = hdoc["series"]["sim_batch_active_lanes"][0]["points"]
        assert [v for _, v in series] == [32.0, 0.0, 0.0]


# ------------------------------------------------------------- satellites


class TestPrometheusEscaping:
    def test_label_values_escaped_per_exposition_format(self):
        reg = telemetry.Registry()
        reg.counter("esc_total", "h", ("l",)).labels('a"b\nc\\d').inc()
        text = export.to_prometheus(reg)
        line = [ln for ln in text.splitlines()
                if ln.startswith("esc_total{")][0]
        assert line == 'esc_total{l="a\\"b\\nc\\\\d"} 1'

    def test_help_escaped(self):
        reg = telemetry.Registry()
        reg.gauge("esc_gauge", "line one\nline two \\ done").set(1)
        text = export.to_prometheus(reg)
        assert "# HELP esc_gauge line one\\nline two \\\\ done" \
            in text.splitlines()

    def test_no_raw_newlines_leak_into_exposition(self):
        reg = telemetry.Registry()
        reg.counter("leak_total", "h\n", ("l",)).labels("x\ny").inc()
        text = export.to_prometheus(reg)
        # every line is a comment or `name{...} value` — a raw newline
        # in a label would produce a parse-breaking orphan line.
        for ln in text.splitlines():
            if not ln:
                continue
            assert ln.startswith("#") or ln.startswith("leak_total"), ln


class TestJaxhooksIdempotence:
    def test_repeated_install_single_count(self):
        # Satellite: repeated install() must not double-count compile
        # seconds (the module documents the no-unregister caveat: ONE
        # process listener, subscription-set semantics). A jit may emit
        # more than one backend_compile event, so the oracle is a
        # SINGLE-installed registry observing the same compiles: a
        # double-registered listener would give the twice-installed
        # registry exactly 2x its counts.
        once, twice = telemetry.Registry(), telemetry.Registry()
        assert jaxhooks.install(once)
        assert jaxhooks.install(twice)
        assert jaxhooks.install(twice)  # repeated install — idempotent
        try:
            jax.jit(lambda x: x * 3.5 + 17)(
                jnp.arange(13, dtype=jnp.float32)).block_until_ready()
            n_once = once.value("jax_compiles_total")
            n_twice = twice.value("jax_compiles_total")
            s_once = jaxhooks.compile_seconds(once)
            s_twice = jaxhooks.compile_seconds(twice)
        finally:
            jaxhooks.uninstall(once)
            jaxhooks.uninstall(twice)
        assert n_once >= 1.0
        assert n_twice == n_once
        assert s_twice == s_once > 0.0
        # the process listener itself is registered exactly once
        import jax.monitoring as monitoring

        listeners = getattr(monitoring, "_event_duration_secs_listeners",
                            None)
        if listeners is not None:  # private, but pin when present
            assert sum(1 for cb in listeners
                       if cb is jaxhooks._on_event_duration) == 1
        # and after uninstall, new compiles stop counting
        jax.jit(lambda x: x * 2.5 - 3)(
            jnp.arange(17, dtype=jnp.float32)).block_until_ready()
        assert twice.value("jax_compiles_total") == n_twice


class TestBenchProfileBracket:
    def test_noop_without_env(self, monkeypatch):
        import bench

        monkeypatch.delenv("BENCH_PROFILE_DIR", raising=False)
        with bench._maybe_profile("1m"):
            pass  # no profiler started, nothing written

    def test_writes_trace_or_warns(self, tmp_path, monkeypatch, capsys):
        import bench

        monkeypatch.setenv("BENCH_PROFILE_DIR", str(tmp_path))
        with bench._maybe_profile("1m"):
            jax.jit(lambda x: x + 1)(jnp.ones(8)).block_until_ready()
        err = capsys.readouterr().err
        wrote = (tmp_path / "1m").exists() and any(
            (tmp_path / "1m").rglob("*"))
        assert wrote or "bench_profile" in err

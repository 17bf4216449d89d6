"""graftquake: device-plane fault injection, integrity checking and
self-healing recovery.

The sockets plane has a chaos plane; until this PR the DEVICE plane — the
sharded ring engine and graftserve — had zero fault coverage. These tests
pin the three halves and their composition:

- **Injection** (chaos/device.py): seeded `FaultSchedule` halo-hop faults
  through the `_RingComm` seam (`FaultSpec` as a ``comm=`` value) —
  byte-replayable, bit-identical across comm backends, keyed on the
  GLOBAL round so chunked runs hit the same sites as unchunked ones, and
  exactly counted into ``chaos_device_faults_total``; one-shot
  `DispatchChaos` chip-preemption/wedge faults at the engine/serve chunk
  dispatch gates.
- **Detection** (supervise/heal.py): template/finiteness audits,
  batch-plane monotonicity invariants, checksum cross-validation against
  a replicated reference fold — typed `IntegrityViolation`.
- **Recovery**: `RetryPolicy` (seeded deterministic backoff,
  per-failure-class routing) driving `Healer` rollback-and-retry —
  healed runs BIT-IDENTICAL to unfaulted ones — adopted by graftserve's
  tick loop and `SupervisedRun`; plus the satellites (payload-template
  `CommPayloadMismatch`, manifest-missing store accounting, bench probe
  backoff) and the slow-marked 100k chaos soak.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from p2pnetwork_tpu import telemetry  # noqa: E402
from p2pnetwork_tpu.chaos.device import (  # noqa: E402
    FAULT_KINDS, ChipLost, DispatchChaos, FaultSchedule, FaultSpec,
    WedgedDispatch, install_dispatch_chaos, record_faults)
from p2pnetwork_tpu.models.flood import Flood  # noqa: E402
from p2pnetwork_tpu.models.messagebatch import BatchFlood  # noqa: E402
from p2pnetwork_tpu.parallel import commviz, sharded  # noqa: E402
from p2pnetwork_tpu.parallel import mesh as M  # noqa: E402
from p2pnetwork_tpu.serve import (  # noqa: E402
    SimService, TrafficPattern, drive, generate)
from p2pnetwork_tpu.serve.service import Preempted  # noqa: E402
from p2pnetwork_tpu.sim import engine, failures  # noqa: E402
from p2pnetwork_tpu.sim import graph as G  # noqa: E402
from p2pnetwork_tpu.supervise import (  # noqa: E402
    CheckpointStore, SupervisedRun)
from p2pnetwork_tpu.supervise.heal import (  # noqa: E402
    Healer, IntegrityViolation, RetryPolicy, audit_state, check_monotonic,
    classify_failure, state_checksum)

pytestmark = pytest.mark.quake

S = 8
needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < S, reason=f"needs {S} devices (virtual CPU mesh)")

KEY = jax.random.key(0)


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < S:
        pytest.skip(f"needs {S} devices")
    return M.ring_mesh(S)


@pytest.fixture(scope="module")
def ws256():
    return G.watts_strogatz(256, 4, 0.2, seed=0)


@pytest.fixture(scope="module")
def sg256(mesh, ws256):
    return sharded.shard_graph(ws256, mesh)


@pytest.fixture()
def no_dispatch_chaos():
    """Guarantee the process-global injector is restored."""
    prev = install_dispatch_chaos(None)
    yield
    install_dispatch_chaos(prev)


def _batch(g, sources, capacity=8, target=0.95):
    proto = BatchFlood()
    b = proto.empty(g, capacity)
    b, _ = proto.admit(g, b, list(sources), coverage_target=target)
    return proto, b


# ------------------------------------------------------- fault schedules


class TestFaultSchedule:
    def test_validation(self):
        with pytest.raises(ValueError, match="probabilities"):
            FaultSchedule(corrupt=0.7, zero=0.4)
        with pytest.raises(ValueError, match="probabilities"):
            FaultSchedule(delay=-0.1)
        with pytest.raises(ValueError, match="corrupt_density"):
            FaultSchedule(corrupt_density=0.0)
        with pytest.raises(ValueError, match="kind"):
            FaultSchedule(sites=((0, 0, 0, "explode"),))

    def test_active(self):
        assert not FaultSchedule(seed=9).active
        assert FaultSchedule(zero=0.1).active
        assert FaultSchedule(sites=((2, 0, 1, "delay"),)).active

    def test_sites_between_replayable_and_windowed(self):
        sched = FaultSchedule(seed=4, corrupt=0.1, zero=0.1, delay=0.1,
                              start_round=2, stop_round=5)
        a = sched.sites_between(0, 8, S - 1, S)
        b = sched.sites_between(0, 8, S - 1, S)
        assert a == b and a  # byte-replayable, non-empty at these rates
        assert all(2 <= r < 5 for r, _, _, _ in a)
        assert all(k in FAULT_KINDS for _, _, _, k in a)
        # window slices compose: [0, 8) == [0, 3) + [3, 8)
        assert a == (sched.sites_between(0, 3, S - 1, S)
                     + sched.sites_between(3, 8, S - 1, S))

    def test_explicit_sites_override_window(self):
        sched = FaultSchedule(seed=0, sites=((7, 2, 3, "zero"),))
        assert sched.sites_between(0, 10, S - 1, S) == [(7, 2, 3, "zero")]

    def test_counts_match_sites(self):
        sched = FaultSchedule(seed=1, zero=0.2, delay=0.1)
        sites = sched.sites_between(0, 6, S - 1, S)
        counts = sched.counts_between(0, 6, S - 1, S)
        for kind in FAULT_KINDS:
            assert counts[kind] == sum(1 for s in sites if s[3] == kind)

    def test_corrupt_payload_shape_dtype_and_determinism(self):
        sched = FaultSchedule(seed=2, corrupt=1.0, corrupt_density=0.25)
        for arr in (jnp.arange(64, dtype=jnp.uint32),
                    jnp.linspace(0.0, 1.0, 64, dtype=jnp.float32),
                    jnp.zeros(64, bool)):
            out1 = sched.corrupt_payload(arr, 1, 2, 3)
            out2 = sched.corrupt_payload(arr, 1, 2, 3)
            assert out1.shape == arr.shape and out1.dtype == arr.dtype
            np.testing.assert_array_equal(np.asarray(out1),
                                          np.asarray(out2))
            assert not np.array_equal(np.asarray(out1), np.asarray(arr))


class TestFaultSpec:
    def test_backend_validation(self):
        with pytest.raises(ValueError, match="resolve 'auto'"):
            FaultSpec(FaultSchedule(), backend="auto")

    def test_hashable_cache_key(self):
        a = FaultSpec(FaultSchedule(seed=1, zero=0.1), "ppermute")
        b = FaultSpec(FaultSchedule(seed=1, zero=0.1), "ppermute")
        assert a == b and hash(a) == hash(b)
        assert {a: 1}[b] == 1


# ------------------------------------------------- halo-hop injection


class TestHaloInjection:
    def test_empty_schedule_bit_identical_to_bare_backend(self, mesh,
                                                          sg256):
        seen0, out0 = sharded.flood_until_coverage(sg256, mesh, 3)
        spec = FaultSpec(FaultSchedule(seed=9), "ppermute")
        seen1, out1 = sharded.flood_until_coverage(sg256, mesh, 3,
                                                   comm=spec)
        np.testing.assert_array_equal(np.asarray(seen0), np.asarray(seen1))
        assert out0 == out1

    def test_faulted_flood_deterministic_and_degraded(self, mesh, sg256):
        _, clean = sharded.flood_until_coverage(sg256, mesh, 3)
        spec = FaultSpec(FaultSchedule(seed=7, zero=0.15, delay=0.1),
                         "ppermute")
        sa, oa = sharded.flood_until_coverage(sg256, mesh, 3, comm=spec)
        sb, ob = sharded.flood_until_coverage(sg256, mesh, 3, comm=spec)
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
        assert oa == ob
        # Lost/stalled hops cost rounds; coverage still completes (zero
        # and delay faults cannot mint spurious seen bits).
        assert oa["rounds"] > clean["rounds"]
        assert oa["coverage"] >= clean["coverage"] * 0.99

    def test_cross_backend_faulted_parity(self, mesh):
        # The fault math rides ABOVE the halo transfer, and the two
        # backends are bit-identical peers — so the same schedule on
        # ppermute and pallas (interpret mode) must stay bit-identical.
        g = G.watts_strogatz(192, 4, 0.2, seed=0)
        sg = sharded.shard_graph(g, mesh)
        sched = FaultSchedule(seed=5, corrupt=0.05, zero=0.1, delay=0.1)
        sp, op = sharded.flood_until_coverage(
            sg, mesh, 2, comm=FaultSpec(sched, "ppermute"))
        sl, ol = sharded.flood_until_coverage(
            sg, mesh, 2, comm=FaultSpec(sched, "pallas"))
        np.testing.assert_array_equal(np.asarray(sp), np.asarray(sl))
        assert op == ol

    def test_windowed_blackout_round_changes_the_run(self, mesh, sg256):
        # Round 1 loses EVERY halo hop (zero=1.0 over [1, 2)): only
        # intra-shard edges deliver that round, so the trajectory must
        # diverge from clean — and stay byte-replayable.
        clean_seen, clean = sharded.flood_until_coverage(
            sg256, mesh, 3, max_rounds=4)
        spec = FaultSpec(FaultSchedule(seed=0, zero=1.0, start_round=1,
                                       stop_round=2), "ppermute")
        s1, o1 = sharded.flood_until_coverage(sg256, mesh, 3, max_rounds=4,
                                              comm=spec)
        s2, o2 = sharded.flood_until_coverage(sg256, mesh, 3, max_rounds=4,
                                              comm=spec)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        assert o1 == o2
        assert not np.array_equal(np.asarray(s1), np.asarray(clean_seen)) \
            or o1 != clean

    def test_chunked_equals_unchunked_via_fault_round0(self, mesh, ws256,
                                                       sg256):
        # THE determinism pin: a chunked serving-style drive that
        # threads fault_round0 hits byte-identical fault sites — final
        # per-lane state bit-identical to one unchunked faulted run.
        proto, batch = _batch(ws256, [3, 10, 77])
        spec = FaultSpec(FaultSchedule(seed=3, zero=0.2, delay=0.05),
                         "ppermute")
        bu, ou = sharded.run_batch_until_coverage(
            sg256, mesh, proto, batch, donate=False, comm=spec,
            max_rounds=64)
        bc, r = batch, 0
        for _ in range(32):
            bc, oc = sharded.run_batch_until_coverage(
                sg256, mesh, proto, bc, donate=False, comm=spec,
                max_rounds=4, fault_round0=r)
            r += oc["rounds"]
            if oc["rounds"] == 0 or not oc["active_lanes"]:
                break
        assert state_checksum(bc) == state_checksum(bu)
        assert r == ou["rounds"]

    def test_counter_reflects_schedule_exactly(self, mesh, sg256):
        sched = FaultSchedule(seed=11, zero=0.2, delay=0.1)
        spec = FaultSpec(sched, "ppermute")
        reg = telemetry.default_registry()
        before = {k: reg.value("chaos_device_faults_total", kind=k)
                  for k in FAULT_KINDS}
        _, out = sharded.flood_until_coverage(sg256, mesh, 3, comm=spec)
        counts = sched.counts_between(0, out["rounds"], S - 1, S)
        for k in FAULT_KINDS:
            assert (reg.value("chaos_device_faults_total", kind=k)
                    - before[k]) == counts[k]

    def test_adaptive_path_refuses_fault_specs(self, mesh):
        g = G.watts_strogatz(256, 4, 0.2, seed=0, source_csr=True)
        sg = sharded.shard_graph(g, mesh, source_csr=True)
        with pytest.raises(ValueError, match="adaptive"):
            sharded.flood_until_coverage(
                sg, mesh, 3, adaptive_k=16,
                comm=FaultSpec(FaultSchedule(zero=0.1), "ppermute"))

    def test_record_faults_host_replay(self):
        reg = telemetry.Registry()
        sched = FaultSchedule(seed=1, zero=0.3)
        counts = record_faults(sched, rounds=5, n_steps=S - 1, n_shards=S,
                               registry=reg)
        assert counts == sched.counts_between(0, 5, S - 1, S)
        assert reg.value("chaos_device_faults_total",
                         kind="zero") == counts["zero"]


# ------------------------------------------------- dispatch chaos


class TestDispatchChaos:
    def test_engine_batch_gate_preempts_once(self, ws256,
                                             no_dispatch_chaos):
        proto, batch = _batch(ws256, [3, 9])
        reg = telemetry.Registry()
        install_dispatch_chaos(DispatchChaos(preempt_at=(0,), registry=reg))
        with pytest.raises(ChipLost) as e:
            engine.run_batch_until_coverage(ws256, proto, batch, KEY,
                                            donate=False)
        assert e.value.dispatch_index == 0
        assert reg.value("chaos_device_faults_total", kind="preempt") == 1
        # One-shot: the retry dispatch lands clean.
        _, out = engine.run_batch_until_coverage(ws256, proto, batch, KEY,
                                                 donate=False)
        assert out["completed"] == 2

    def test_coverage_and_sharded_gates_wedge(self, mesh, ws256, sg256,
                                              no_dispatch_chaos):
        proto, batch = _batch(ws256, [3])
        install_dispatch_chaos(DispatchChaos(wedge_at=(0, 1)))
        with pytest.raises(WedgedDispatch):
            engine.run_until_coverage_from(
                ws256, Flood(source=0), Flood(source=0).init(ws256, KEY),
                KEY, donate=False, max_rounds=4)
        with pytest.raises(WedgedDispatch):
            sharded.run_batch_until_coverage(sg256, mesh, proto, batch,
                                             donate=False)

    def test_uninstalled_gate_is_a_noop(self, ws256, no_dispatch_chaos):
        proto, batch = _batch(ws256, [3])
        _, out = engine.run_batch_until_coverage(ws256, proto, batch, KEY,
                                                 donate=False)
        assert out["completed"] == 1

    def test_install_returns_previous(self, no_dispatch_chaos):
        a, b = DispatchChaos(), DispatchChaos()
        assert install_dispatch_chaos(a) is None
        assert install_dispatch_chaos(b) is a
        assert install_dispatch_chaos(None) is b


# ------------------------------------------------- payload templates


class TestCommPayloadMismatch:
    def test_mismatch_raises_typed_at_trace_time(self, mesh):
        def body(x):
            rc = sharded._RingComm("ppermute", "shards", S)
            out = rc.shift(x[0])
            rc.shift(x[0][: x.shape[1] // 2])  # half-width payload
            return out[None]

        fn = sharded.shard_map(body, mesh=mesh, in_specs=(P("shards"),),
                               out_specs=P("shards"))
        x = jnp.zeros((S, 16), jnp.float32)
        with pytest.raises(sharded.CommPayloadMismatch, match="template"):
            jax.jit(fn)(x)

    def test_directions_own_separate_templates(self):
        rc = sharded._RingComm("ppermute", "shards", S)
        rc._check_payload(jnp.zeros(8, bool), "shift")
        rc._check_payload(jnp.zeros(8, jnp.int32), "shift_back")  # ok
        rc._check_payload(jnp.zeros(8, bool), "shift")  # repeat ok
        with pytest.raises(sharded.CommPayloadMismatch):
            rc._check_payload(jnp.zeros(8, jnp.int32), "shift")
        with pytest.raises(sharded.CommPayloadMismatch):
            rc._check_payload(jnp.zeros(4, jnp.int32), "shift_back")

    def test_typed_as_type_error(self):
        assert issubclass(sharded.CommPayloadMismatch, TypeError)


# ------------------------------------------------- integrity checks


class TestIntegrityChecks:
    def test_audit_state_passes_and_detects(self):
        tpl = {"a": np.zeros((4,), np.float32), "b": np.zeros(2, np.int32)}
        audit_state({"a": np.ones(4, np.float32),
                     "b": np.ones(2, np.int32)}, tpl)  # clean
        with pytest.raises(IntegrityViolation, match="template"):
            audit_state({"a": np.zeros(5, np.float32),
                         "b": np.zeros(2, np.int32)}, tpl)
        with pytest.raises(IntegrityViolation, match="template"):
            audit_state({"a": np.zeros(4, np.float64),
                         "b": np.zeros(2, np.int32)}, tpl)
        with pytest.raises(IntegrityViolation) as e:
            audit_state({"a": np.array([1.0, np.nan, 0.0, 0.0],
                                       np.float32),
                         "b": np.zeros(2, np.int32)}, tpl)
        assert e.value.kind == "nonfinite" and "a" in e.value.leaf

    def test_monotonicity_invariants(self, ws256):
        proto, b0 = _batch(ws256, [3, 9])
        b1, _ = engine.run_batch_until_coverage(ws256, proto, b0, KEY,
                                                max_rounds=2, donate=False)
        check_monotonic(b0, b1)  # forward progress is clean
        with pytest.raises(IntegrityViolation, match="seen"):
            check_monotonic(b1, b0)  # reversed: seen bits lost
        import dataclasses
        bad = dataclasses.replace(
            b1, rounds=np.asarray(b1.rounds) - 1)
        with pytest.raises(IntegrityViolation, match="rounds"):
            check_monotonic(b1, bad)
        done_b = dataclasses.replace(
            b1, done=np.zeros_like(np.asarray(b1.done)))
        if np.asarray(b1.done).any():
            with pytest.raises(IntegrityViolation, match="done"):
                check_monotonic(b1, done_b)
        check_monotonic((1, 2), (3, 4))  # non-batch states pass through

    def test_state_checksum_bit_sensitivity(self):
        a = {"x": np.arange(16, dtype=np.uint32)}
        b = {"x": np.arange(16, dtype=np.uint32)}
        assert state_checksum(a) == state_checksum(b)
        b["x"][7] ^= 1
        assert state_checksum(a) != state_checksum(b)

    def test_classify_failure(self):
        from p2pnetwork_tpu.supervise.watchdog import StallTimeout

        assert classify_failure(IntegrityViolation("checksum")) \
            == "integrity"
        assert classify_failure(ChipLost(0)) == "preempt"
        assert classify_failure(WedgedDispatch(1)) == "wedged"
        assert classify_failure(StallTimeout("x", 1.0, 0.5)) == "wedged"
        assert classify_failure(ValueError("nope")) is None


# ------------------------------------------------- retry policy


class TestRetryPolicy:
    def test_backoff_deterministic_and_bounded(self):
        p = RetryPolicy(max_attempts=5, backoff_base_s=0.1,
                        backoff_max_s=0.5, jitter=0.5, seed=42)
        q = RetryPolicy(max_attempts=5, backoff_base_s=0.1,
                        backoff_max_s=0.5, jitter=0.5, seed=42)
        assert p.delays(5) == q.delays(5)
        for a in range(1, 6):
            base = min(0.1 * 2 ** (a - 1), 0.5)
            d = p.backoff_s(a)
            assert base * 0.75 <= d <= base * 1.25
        assert p.delays(3, salt=1) != p.delays(3, salt=2)
        assert RetryPolicy(seed=1).delays(3) != RetryPolicy(seed=2).delays(3)

    def test_validation_and_routing(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(jitter=2.0)
        with pytest.raises(ValueError, match="route"):
            RetryPolicy(routes={"integrity": "pray"})
        p = RetryPolicy()
        assert p.action_for("integrity") == "fallback"
        assert p.action_for("preempt") == "retry"
        assert p.action_for("wedged") == "retry"
        assert p.action_for("unknown") == "raise"
        assert p.action_for(None) == "raise"
        with pytest.raises(ValueError, match="1-based"):
            p.backoff_s(0)


# ------------------------------------------------- healer


class TestHealer:
    def _policy(self, **kw):
        kw.setdefault("backoff_base_s", 0.0)
        return RetryPolicy(**kw)

    def test_heals_one_shot_fault_and_counts(self):
        reg = telemetry.Registry()
        calls = []

        def dispatch(s):
            calls.append(s)
            if len(calls) == 1:
                raise ChipLost(0)
            return s + 1, {"ok": True}

        h = Healer(self._policy(max_attempts=3), registry=reg)
        state, out = h.run_chunk(dispatch, 10, chunk_index=0)
        assert state == 11 and out == {"ok": True}
        assert len(calls) == 2 and calls[1] == 10  # retained rollback
        assert reg.value("heal_retries_total", outcome="retry") == 1
        assert reg.value("heal_retries_total", outcome="healed") == 1

    def test_exhausted_budget_raises(self):
        reg = telemetry.Registry()

        def dispatch(s):
            raise WedgedDispatch(0)

        h = Healer(self._policy(max_attempts=2), registry=reg)
        with pytest.raises(WedgedDispatch):
            h.run_chunk(dispatch, 0, chunk_index=0)
        assert reg.value("heal_retries_total", outcome="exhausted") == 1
        assert reg.value("heal_retries_total", outcome="retry") == 1

    def test_unroutable_errors_propagate_untouched(self):
        def dispatch(s):
            raise KeyError("caller bug, not a device fault")

        h = Healer(self._policy())
        with pytest.raises(KeyError):
            h.run_chunk(dispatch, 0)

    def test_integrity_routes_to_fallback(self):
        reg = telemetry.Registry()
        tpl = {"x": np.zeros(4, np.float32)}

        def bad(s):  # mints NaNs — semantically-consistent corruption
            return {"x": np.full(4, np.nan, np.float32)}, {}

        def good(s):
            return {"x": np.ones(4, np.float32)}, {}

        h = Healer(self._policy(max_attempts=3), template=tpl,
                   fallback_dispatch=good, registry=reg)
        state, _ = h.run_chunk(bad, {"x": np.zeros(4, np.float32)},
                               chunk_index=1)
        np.testing.assert_array_equal(state["x"], np.ones(4, np.float32))
        assert reg.value("heal_retries_total", outcome="fallback") == 1
        assert reg.value("heal_retries_total", outcome="healed") == 1

    def test_checksum_verify_catches_silent_corruption(self, mesh, ws256,
                                                       sg256):
        # Bit-flip corruption can mint SPURIOUS seen bits — individually
        # well-formed state that no local invariant rejects. Only the
        # replicated reference fold catches it; the heal must then land
        # bit-identical to the clean path. This is the no-silent-wrong-
        # answers acceptance pin.
        proto, batch = _batch(ws256, [3, 9])
        spec = FaultSpec(FaultSchedule(seed=11, corrupt=0.3), "ppermute")

        def faulty(b):
            return sharded.run_batch_until_coverage(
                sg256, mesh, proto, b, donate=False, comm=spec)

        def clean(b):
            return sharded.run_batch_until_coverage(
                sg256, mesh, proto, b, donate=False)

        reg = telemetry.Registry()
        h = Healer(self._policy(max_attempts=3), fallback_dispatch=clean,
                   verify_dispatch=clean, registry=reg)
        healed, _ = h.run_chunk(faulty, batch, chunk_index=0)
        ref, _ = clean(batch)
        assert state_checksum(healed) == state_checksum(ref)
        assert reg.value("heal_retries_total", outcome="fallback") == 1
        assert reg.value("heal_retries_total", outcome="healed") == 1

    def test_store_rollback_prefers_durable_entry(self, tmp_path):
        store = CheckpointStore(str(tmp_path), registry=telemetry.Registry())
        tpl = {"x": np.zeros(4, np.int32)}
        durable = {"x": np.arange(4, dtype=np.int32)}
        store.save(durable, KEY, 3, 30)
        inputs = []

        def dispatch(s):
            inputs.append(np.asarray(s["x"]).copy())
            if len(inputs) == 1:
                raise ChipLost(0)
            return s, {}

        h = Healer(self._policy(max_attempts=2), template=tpl, store=store,
                   monotonic=False, registry=telemetry.Registry())
        h.run_chunk(dispatch, {"x": np.zeros(4, np.int32)}, chunk_index=0)
        np.testing.assert_array_equal(inputs[1], durable["x"])


# ------------------------------------------------- serve + supervise


class TestServeHealing:
    def _svc(self, g, **kw):
        kw.setdefault("capacity", 16)
        kw.setdefault("chunk_rounds", 4)
        kw.setdefault("seed", 0)
        kw.setdefault("record_seen_hash", True)
        kw.setdefault("registry", telemetry.Registry())
        kw.setdefault("heal", RetryPolicy(max_attempts=3,
                                          backoff_base_s=0.0))
        return SimService(g, **kw)

    def test_wedged_tick_heals_transparently(self, ws256,
                                             no_dispatch_chaos):
        pattern = TrafficPattern(ticks=8, rate=2.0, coverage_target=0.9)
        sched = generate(pattern, ws256.n_nodes, seed=7)
        ref = self._svc(ws256)
        drive(ref, sched)
        ref.close()

        reg = telemetry.Registry()
        chaos_reg = telemetry.Registry()
        svc = self._svc(ws256, registry=reg)
        install_dispatch_chaos(DispatchChaos(wedge_at=(1,),
                                             registry=chaos_reg))
        drive(svc, sched)
        svc.close()
        assert svc.tickets() == ref.tickets()  # seen hashes included
        assert chaos_reg.value("chaos_device_faults_total",
                               kind="wedge") == 1
        assert reg.value("heal_retries_total", outcome="healed") == 1

    def test_chip_loss_mid_traffic_loses_no_lane(self, ws256,
                                                 no_dispatch_chaos):
        pattern = TrafficPattern(ticks=6, rate=3.0, coverage_target=0.9)
        sched = generate(pattern, ws256.n_nodes, seed=3)
        ref = self._svc(ws256)
        drive(ref, sched)
        ref.close()

        svc = self._svc(ws256)
        install_dispatch_chaos(DispatchChaos(preempt_at=(0, 2)))
        out = drive(svc, sched)
        svc.close()
        assert svc.tickets() == ref.tickets()
        done = [r for r in out["tickets"].values()
                if r and r["status"] == "done"]
        assert len(done) == len(out["tickets"])  # zero lost lanes

    def test_service_preemption_not_swallowed(self, ws256):
        # Healing covers DETECTED device faults; the supervise plane's
        # deterministic kill must still escape (resume owns recovery).
        svc = self._svc(ws256)
        svc.submit(3)
        svc.arm_preemption(1)
        with pytest.raises(Preempted):
            svc.tick()


class TestSupervisedHealing:
    def test_chip_loss_mid_run_heals_bit_identical(self, tmp_path,
                                                   no_dispatch_chaos):
        g = G.watts_strogatz(512, 6, 0.1, seed=1)
        ref = SupervisedRun(g, Flood(source=0), str(tmp_path / "ref"),
                            chunk_rounds=3)
        st_ref, sum_ref = ref.run_until_coverage(KEY, max_rounds=64)

        reg = telemetry.Registry()
        run = SupervisedRun(g, Flood(source=0), str(tmp_path / "heal"),
                            chunk_rounds=3,
                            heal=RetryPolicy(max_attempts=3,
                                             backoff_base_s=0.0),
                            registry=reg)
        install_dispatch_chaos(DispatchChaos(preempt_at=(1,)))
        st, summary = run.run_until_coverage(KEY, max_rounds=64)
        np.testing.assert_array_equal(np.asarray(st.seen),
                                      np.asarray(st_ref.seen))
        assert summary["rounds"] == sum_ref["rounds"]
        assert summary["messages"] == sum_ref["messages"]
        assert reg.value("heal_retries_total", outcome="healed") == 1


# ------------------------------------------------- store satellites


class TestStoreManifestMissing:
    def _fill(self, store, rounds):
        state = {"x": np.arange(8, dtype=np.int32)}
        for r in rounds:
            state = {"x": state["x"] + 1}
            store.save(state, KEY, r, r * 10)

    def test_scan_fallback_counted_and_logged(self, tmp_path):
        reg = telemetry.Registry()
        store = CheckpointStore(str(tmp_path), retain=3, registry=reg)
        self._fill(store, [1, 2])
        os.unlink(tmp_path / "manifest.json")
        # Corrupt the newest entry too: the scan fallback must still
        # resume from the older good entry (satellite acceptance).
        newest = sorted(n for n in os.listdir(tmp_path)
                        if n.endswith(".npz"))[-1]
        path = tmp_path / newest
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        with pytest.warns(RuntimeWarning, match="directory scan"):
            got = store.load_latest({"x": np.zeros(8, np.int32)})
        assert got is not None and got[2] == 1
        assert reg.value("supervise_checkpoints_skipped_total",
                         reason="manifest-missing") == 1
        assert reg.value("supervise_checkpoints_skipped_total",
                         reason="corrupt") == 1

    def test_fresh_directory_counts_nothing(self, tmp_path):
        reg = telemetry.Registry()
        store = CheckpointStore(str(tmp_path), registry=reg)
        assert store.load_latest({"x": np.zeros(1)}) is None
        assert reg.value("supervise_checkpoints_skipped_total",
                         reason="manifest-missing") == 0


class TestFaultStormResume:
    def test_preempt_corrupt_manifest_loss_resumes_bit_identical(
            self, tmp_path, no_dispatch_chaos):
        # The full storm: deterministic preemption, then the newest
        # checkpoint entry corrupted AND the manifest deleted, then a
        # healed chip loss during the resumed run — the final state must
        # still be bit-identical to an uninterrupted run (PRNG-dependent
        # protocol, so the per-chunk key discipline is what's proven).
        from p2pnetwork_tpu.models import SIR

        g = G.watts_strogatz(512, 6, 0.1, seed=3)
        proto = SIR(beta=0.4, gamma=0.15)
        ref = SupervisedRun(g, proto, str(tmp_path / "ref"),
                            chunk_rounds=4)
        st_ref, sum_ref = ref.run_rounds(jax.random.key(5), 16)

        run = SupervisedRun(g, proto, str(tmp_path / "storm"),
                            chunk_rounds=4, retain=4,
                            heal=RetryPolicy(max_attempts=3,
                                             backoff_base_s=0.0))
        failures.preempt(run, at_round=12)
        with pytest.raises(Preempted):
            run.run_rounds(jax.random.key(5), 16)
        newest = run.store.entries()[-1]
        assert newest["round"] == 8
        path = os.path.join(run.store.directory, newest["file"])
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        os.unlink(os.path.join(run.store.directory, "manifest.json"))
        install_dispatch_chaos(DispatchChaos(preempt_at=(0,)))
        with pytest.warns(RuntimeWarning, match="directory scan"):
            st, summary = run.run_rounds(jax.random.key(5), 16)
        assert summary["resumed_from"] == 4
        assert summary["rounds"] == sum_ref["rounds"] == 16
        assert summary["messages"] == sum_ref["messages"]
        assert state_checksum(jax.device_get(st)) \
            == state_checksum(jax.device_get(st_ref))


# ------------------------------------------------- comm census pricing


class TestCommCensus:
    def test_faulted_path_never_prices_as_zero_ici(self, mesh, sg256):
        # graftaudit/commviz gate: the FaultyComm wrapper delegates the
        # real transfer to the inner backend, so the census prices an
        # injected ring exactly like the clean ring it wraps — an
        # injected path can never read as zero ICI bytes.
        block = sg256.block
        common_shapes = (
            jnp.float32(0.99), sg256.bkt_src, sg256.bkt_dst, sg256.bkt_mask,
            *sharded._dyn_or_empty(sg256), *sharded._mxu_or_empty(sg256),
            sharded._diag_masks_or_empty(sg256), sg256.node_mask,
            sg256.out_degree,
            jnp.zeros((S, block), bool), jnp.zeros((S, block), bool),
        )
        clean_fn = sharded._flood_cov_fn(mesh, "shards", S, block, 8)
        clean = commviz.ici_bytes_estimate(clean_fn, common_shapes, S)
        spec = FaultSpec(FaultSchedule(seed=1, zero=0.2), "ppermute")
        fault_fn = sharded._flood_cov_fn(mesh, "shards", S, block, 8,
                                         comm=spec)
        faulted = commviz.ici_bytes_estimate(
            fault_fn, (*common_shapes, jnp.int32(0)), S)
        assert clean > 0
        assert faulted >= clean


# ------------------------------------------------- overhead + soak


@pytest.mark.slow
class TestOverheadRatchet:
    def test_integrity_checks_within_1_10x(self, ws256):
        # Recorder-style ratchet: a healed (undonated + checked) chunk
        # loop must stay within 1.10x of the bare donating loop on a
        # 100k-node batch drive (ratio-based, interleaved best-of-N —
        # no absolute wall clocks).
        import time as _time

        g = G.watts_strogatz(100_000, 10, 0.1, seed=0)
        proto = BatchFlood()
        healer = Healer(RetryPolicy(backoff_base_s=0.0), monotonic=True)

        def run(heal):
            b = proto.empty(g, 32)
            b, _ = proto.admit(g, b, list(range(1, 25)),
                               coverage_target=0.95)
            t0 = _time.perf_counter()
            for chunk in range(8):
                if heal:
                    b, out = healer.run_chunk(
                        lambda s: engine.run_batch_until_coverage(
                            g, proto, s, KEY, max_rounds=4, donate=False),
                        b, chunk_index=chunk)
                else:
                    b, out = engine.run_batch_until_coverage(
                        g, proto, b, KEY, max_rounds=4, donate=False)
                if out["rounds"] == 0:
                    break
            return _time.perf_counter() - t0

        run(False), run(True)  # warm both programs before timing
        offs, ons = [], []
        for _ in range(5):
            offs.append(run(False))
            ons.append(run(True))
        ratio = min(ons) / min(offs)
        assert ratio <= 1.10, (
            f"integrity-check overhead {ratio:.3f}x exceeds the 1.10x "
            f"ratchet (off {min(offs):.4f}s on {min(ons):.4f}s)")


@pytest.mark.slow
class TestChaosSoak:
    """The acceptance soak: 100k-node seeded traffic through a storm of
    comm corruption and two chunk-boundary preemptions — served to
    completion with zero lost admitted lanes, per-ticket results
    bit-identical to an uninterrupted run, and the fault/heal counters
    reflecting the schedule exactly."""

    def test_soak_100k(self, tmp_path, no_dispatch_chaos):
        g = G.watts_strogatz(100_000, 6, 0.1, seed=0)
        pattern = TrafficPattern(ticks=10, rate=2.0, hot_fraction=0.5,
                                 hot_keys=4, coverage_target=0.95)
        sched = generate(pattern, g.n_nodes, seed=13)
        policy = RetryPolicy(max_attempts=4, backoff_base_s=0.0)

        def svc(**kw):
            kw.setdefault("capacity", 32)
            kw.setdefault("chunk_rounds", 4)
            kw.setdefault("seed", 1)
            kw.setdefault("record_seen_hash", True)
            kw.setdefault("heal", policy)
            kw.setdefault("registry", telemetry.Registry())
            return SimService(g, **kw)

        # Uninterrupted reference.
        ref = svc()
        drive(ref, sched)
        ref.close()
        assert ref.tickets(), "soak needs traffic"

        # Storm: a healed chip loss + a healed wedge mid-traffic, plus
        # TWO service preemptions with resume from the store.
        chaos_reg = telemetry.Registry()
        heal_reg = telemetry.Registry()
        install_dispatch_chaos(DispatchChaos(
            preempt_at=(1,), wedge_at=(3,), registry=chaos_reg))
        storm = svc(store=str(tmp_path), resume=False, registry=heal_reg)
        storm.arm_preemption(4)
        with pytest.raises(Preempted):
            drive(storm, sched)
        storm2 = svc(store=str(tmp_path), resume=True, registry=heal_reg)
        storm2.arm_preemption(8)
        with pytest.raises(Preempted):
            drive(storm2, sched)
        final = svc(store=str(tmp_path), resume=True, registry=heal_reg)
        out = drive(final, sched)
        final.close()

        # Zero lost admitted lanes; every ticket bit-identical
        # (seen-hash witnesses included in the records).
        assert final.tickets() == ref.tickets()
        assert all(r["status"] == "done"
                   for r in final.tickets().values())
        assert out["completed"] + len(out["shed"]) >= out["submitted"]

        # Counters reflect the storm exactly: one chip loss, one wedge,
        # each healed by exactly one policy retry.
        assert chaos_reg.value("chaos_device_faults_total",
                               kind="preempt") == 1
        assert chaos_reg.value("chaos_device_faults_total",
                               kind="wedge") == 1
        assert heal_reg.value("heal_retries_total", outcome="retry") == 2
        assert heal_reg.value("heal_retries_total", outcome="healed") == 2
        assert heal_reg.value("heal_retries_total", outcome="exhausted") == 0

    @needs_mesh
    def test_soak_100k_comm_corruption_sharded(self, mesh):
        # The comm-corruption half on the multi-chip plane: a corrupt
        # storm over the 100k-node ring batch, detected by the checksum
        # cross-validation and healed onto the clean path — final lanes
        # bit-identical, faults counted exactly per the schedule replay.
        g = G.watts_strogatz(100_000, 6, 0.1, seed=0)
        sg = sharded.shard_graph(g, mesh)
        proto, batch = _batch(g, [3, 999, 54_321], capacity=32,
                              target=0.95)
        sched = FaultSchedule(seed=17, corrupt=0.05)
        spec = FaultSpec(sched, "ppermute")

        reg = telemetry.default_registry()
        before = reg.value("chaos_device_faults_total", kind="corrupt")
        faulted, of = sharded.run_batch_until_coverage(
            sg, mesh, proto, batch, donate=False, comm=spec)
        counts = sched.counts_between(0, of["rounds"], S - 1, S)
        assert (reg.value("chaos_device_faults_total", kind="corrupt")
                - before) == counts["corrupt"] > 0

        def dispatch_faulty(b):
            return sharded.run_batch_until_coverage(
                sg, mesh, proto, b, donate=False, comm=spec)

        def dispatch_clean(b):
            return sharded.run_batch_until_coverage(
                sg, mesh, proto, b, donate=False)

        heal_reg = telemetry.Registry()
        healer = Healer(RetryPolicy(max_attempts=3, backoff_base_s=0.0),
                        fallback_dispatch=dispatch_clean,
                        verify_dispatch=dispatch_clean, registry=heal_reg)
        healed, _ = healer.run_chunk(dispatch_faulty, batch, chunk_index=0)
        ref, _ = dispatch_clean(batch)
        assert state_checksum(healed) == state_checksum(ref)
        assert heal_reg.value("heal_retries_total", outcome="healed") == 1

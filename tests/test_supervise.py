"""Supervised execution plane: watchdogs, checkpoint store, crash-tolerant
runs, preemption faults, deadline-bounded shutdown.

The crash-recovery core is proven two ways: fast in-process tests drive the
deterministic ``preempt`` fault (a SIGKILL stand-in at an exact round), and
a slow-marked subprocess test SIGKILLs a real ``SupervisedRun`` child —
twice, at different rounds — and asserts the resumed final state is
bit-identical to an uninterrupted run's (PRNG-dependent protocol, so the
per-chunk key discipline is what's under test, not just idempotent state).
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from p2pnetwork_tpu import telemetry  # noqa: E402
from p2pnetwork_tpu.models import SIR, Flood  # noqa: E402
from p2pnetwork_tpu.sim import checkpoint as ckpt  # noqa: E402
from p2pnetwork_tpu.sim import engine  # noqa: E402
from p2pnetwork_tpu.sim import failures  # noqa: E402
from p2pnetwork_tpu.sim import graph as G  # noqa: E402
from p2pnetwork_tpu.supervise import (  # noqa: E402
    CheckpointStore, Preempted, StallTimeout, SupervisedRun, Watchdog)
from tests.helpers import wait_until  # noqa: E402

pytestmark = pytest.mark.supervise

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _state_digest(state) -> str:
    leaves = jax.tree_util.tree_leaves(jax.device_get(state))
    h = hashlib.sha256()
    for leaf in leaves:
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


# ------------------------------------------------------------------ watchdog


class TestWatchdog:
    def test_stall_detected_within_deadline_and_counted(self):
        # The acceptance scenario: an artificially stalled dispatch (the
        # supervised thread simply stops heartbeating) must fire a stall
        # event within its deadline, with the timeout counter incremented.
        reg = telemetry.Registry()
        fired = []
        deadline = 0.2
        with Watchdog(deadline, name="stalled", on_stall=fired.append,
                      registry=reg) as dog:
            t0 = time.monotonic()
            assert wait_until(lambda: fired, timeout=3 * deadline,
                              interval=0.005)
            detect_s = time.monotonic() - t0
        assert detect_s < 2 * deadline
        assert dog.stalls == 1
        assert fired[0] is dog
        assert reg.value("supervise_watchdog_timeouts_total",
                         watchdog="stalled") == 1
        assert dog.last_stall_s >= deadline

    def test_heartbeats_prevent_stall(self):
        reg = telemetry.Registry()
        with Watchdog(0.25, name="alive", on_stall="warn",
                      registry=reg) as dog:
            for _ in range(8):
                dog.heartbeat()
                time.sleep(0.05)
        assert dog.stalls == 0
        assert reg.value("supervise_watchdog_timeouts_total",
                         watchdog="alive") == 0

    def test_raise_mode_raises_at_next_heartbeat(self):
        reg = telemetry.Registry()
        with pytest.raises(StallTimeout) as e:
            with Watchdog(0.1, name="r", registry=reg) as dog:
                assert wait_until(lambda: dog.stalls > 0, timeout=1.0,
                                  interval=0.005)
                dog.heartbeat()  # the pending stall surfaces HERE
                pytest.fail("heartbeat should have raised")
        assert e.value.deadline_s == 0.1
        assert e.value.stalled_s >= 0.1

    def test_raise_mode_raises_at_exit_without_final_heartbeat(self):
        with pytest.raises(StallTimeout):
            with Watchdog(0.1, name="x", registry=telemetry.Registry()) as dog:
                assert wait_until(lambda: dog.stalls > 0, timeout=1.0,
                                  interval=0.005)

    def test_one_event_per_gap_and_gauge_climbs(self):
        reg = telemetry.Registry()
        with Watchdog(0.1, name="g", on_stall=lambda d: None,
                      registry=reg) as dog:
            assert wait_until(lambda: dog.stalls > 0, timeout=1.0,
                              interval=0.005)
            g1 = reg.value("supervise_stall_seconds", watchdog="g")
            time.sleep(0.25)
            g2 = reg.value("supervise_stall_seconds", watchdog="g")
            assert dog.stalls == 1  # same gap: one event, climbing gauge
            assert g2 > g1 > 0
            dog.heartbeat()
            assert reg.value("supervise_stall_seconds", watchdog="g") == 0
            assert wait_until(lambda: dog.stalls == 2, timeout=1.0,
                              interval=0.005)  # new gap: a second event
        # close() resets the gauge: a finished run must not scrape as a
        # still-climbing stall.
        assert reg.value("supervise_stall_seconds", watchdog="g") == 0

    def test_crashing_stall_hook_does_not_kill_the_watchdog(self):
        def bad_hook(dog):
            raise RuntimeError("driver hook bug")

        with pytest.warns(RuntimeWarning, match="on_stall callback raised"):
            with Watchdog(0.08, name="h", on_stall=bad_hook,
                          registry=telemetry.Registry()) as dog:
                assert wait_until(lambda: dog.stalls > 0, timeout=1.0,
                                  interval=0.005)
                dog.heartbeat()
                assert wait_until(lambda: dog.stalls > 1, timeout=1.0,
                                  interval=0.005)  # still watching

    def test_invalid_configuration_rejected(self):
        with pytest.raises(ValueError):
            Watchdog(0)
        with pytest.raises(ValueError):
            Watchdog(1.0, on_stall="explode")


# --------------------------------------------- checkpoint integrity (file)


class TestCheckpointIntegrity:
    def _save_one(self, tmp_path):
        path = str(tmp_path / "c.npz")
        state = {"a": np.arange(6, dtype=np.int32),
                 "b": np.ones(3, dtype=np.float32)}
        ckpt.save(path, state, jax.random.key(7), 5, 42)
        return path, state

    def test_roundtrip_with_hash(self, tmp_path):
        path, state = self._save_one(tmp_path)
        got, key, rnd, msgs = ckpt.load(path, state)
        assert rnd == 5 and msgs == 42
        np.testing.assert_array_equal(np.asarray(got["a"]), state["a"])

    def test_truncated_file_raises_checkpoint_corrupt(self, tmp_path):
        path, state = self._save_one(tmp_path)
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        with pytest.raises(ckpt.CheckpointCorrupt) as e:
            ckpt.load(path, state)
        assert e.value.path == path

    def test_garbage_file_raises_checkpoint_corrupt(self, tmp_path):
        path = str(tmp_path / "junk.npz")
        with open(path, "wb") as f:
            f.write(b"not a zip at all")
        with pytest.raises(ckpt.CheckpointCorrupt):
            ckpt.load(path, {"a": np.zeros(1)})

    def test_content_tamper_reports_expected_and_actual_hash(self, tmp_path):
        path, state = self._save_one(tmp_path)
        # Rewrite the npz with one leaf modified but the ORIGINAL digest:
        # the zip container stays valid, so only the content hash can
        # catch it.
        with np.load(path) as data:
            payload = {k: np.asarray(data[k]) for k in data.files}
        payload["leaf_0"] = payload["leaf_0"] + 1
        with open(path, "wb") as f:
            np.savez(f, **payload)
        with pytest.raises(ckpt.CheckpointCorrupt) as e:
            ckpt.load(path, state)
        assert e.value.expected is not None
        assert e.value.actual is not None
        assert e.value.expected != e.value.actual
        assert "hash mismatch" in str(e.value)

    def test_legacy_hashless_file_still_loads(self, tmp_path):
        # Old-format back-compat: files written before the integrity hash
        # landed have no __sha256__ entry and must load unverified.
        path, state = self._save_one(tmp_path)
        with np.load(path) as data:
            payload = {k: np.asarray(data[k]) for k in data.files
                       if k != "__sha256__"}
        with open(path, "wb") as f:
            np.savez(f, **payload)
        got, key, rnd, msgs = ckpt.load(path, state)
        assert rnd == 5 and msgs == 42

    def test_template_mismatch_stays_value_error(self, tmp_path):
        path, state = self._save_one(tmp_path)
        with pytest.raises(ValueError) as e:
            ckpt.load(path, {"different": np.zeros(2)})
        assert not isinstance(e.value, ckpt.CheckpointCorrupt)


# ------------------------------------------------------------------- store


class TestCheckpointStore:
    def _fill(self, store, rounds):
        key = jax.random.key(0)
        state = {"x": np.arange(8, dtype=np.int32)}
        for r in rounds:
            state = {"x": state["x"] + 1}
            store.save(state, key, r, r * 10)
        return state

    def test_manifest_updated_atomically_and_points_to_latest(self, tmp_path):
        store = CheckpointStore(str(tmp_path), retain=5,
                                registry=telemetry.Registry())
        self._fill(store, [1, 2, 3])
        with open(tmp_path / "manifest.json", encoding="utf-8") as f:
            doc = json.load(f)
        assert doc["latest"] == doc["entries"][-1]["file"]
        assert [e["round"] for e in doc["entries"]] == [1, 2, 3]
        for e in doc["entries"]:
            assert (tmp_path / e["file"]).exists()
        # No half-written temp artifacts survive a completed save.
        assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]

    def test_retention_prunes_oldest(self, tmp_path):
        store = CheckpointStore(str(tmp_path), retain=2,
                                registry=telemetry.Registry())
        self._fill(store, [1, 2, 3, 4])
        assert [e["round"] for e in store.entries()] == [3, 4]
        files = [n for n in os.listdir(tmp_path) if n.endswith(".npz")]
        assert len(files) == 2

    def test_corrupt_latest_entry_skipped_on_load(self, tmp_path):
        reg = telemetry.Registry()
        store = CheckpointStore(str(tmp_path), retain=3, registry=reg)
        self._fill(store, [1, 2, 3])
        newest = tmp_path / store.entries()[-1]["file"]
        with open(newest, "r+b") as f:
            f.truncate(os.path.getsize(newest) // 2)
        template = {"x": np.zeros(8, np.int32)}
        state, key, rnd, msgs, path = store.load_latest(template)
        assert rnd == 2 and msgs == 20
        assert reg.value("supervise_checkpoints_skipped_total",
                         reason="hash_mismatch") == 1

    def test_missing_entry_file_skipped(self, tmp_path):
        reg = telemetry.Registry()
        store = CheckpointStore(str(tmp_path), retain=3, registry=reg)
        self._fill(store, [1, 2])
        os.unlink(tmp_path / store.entries()[-1]["file"])
        state, key, rnd, msgs, path = store.load_latest(
            {"x": np.zeros(8, np.int32)})
        assert rnd == 1
        assert reg.value("supervise_checkpoints_skipped_total",
                         reason="missing") == 1

    def test_lost_manifest_falls_back_to_directory_scan(self, tmp_path):
        store = CheckpointStore(str(tmp_path), retain=3,
                                registry=telemetry.Registry())
        self._fill(store, [1, 2])
        os.unlink(tmp_path / "manifest.json")
        got = store.load_latest({"x": np.zeros(8, np.int32)})
        assert got is not None and got[2] == 2

    def test_empty_store_loads_none(self, tmp_path):
        store = CheckpointStore(str(tmp_path),
                                registry=telemetry.Registry())
        assert store.load_latest({"x": np.zeros(1)}) is None
        assert store.latest_round() is None

    def test_save_never_prunes_its_own_entry(self, tmp_path):
        # Regression: a save whose round sorts below a stale higher-round
        # trail used to have ITS OWN entry retention-pruned as written
        # (and returned a path to an already-deleted file).
        store = CheckpointStore(str(tmp_path), retain=3,
                                registry=telemetry.Registry())
        self._fill(store, [20, 24, 28])
        key = jax.random.key(0)
        path = store.save({"x": np.full(8, 7, np.int32)}, key, 8, 80)
        assert os.path.exists(path)
        rounds = [e["round"] for e in store.entries()]
        assert 8 in rounds and len(rounds) == 3  # oldest survivor evicted

    def test_clear_resets_to_empty(self, tmp_path):
        store = CheckpointStore(str(tmp_path), retain=3,
                                registry=telemetry.Registry())
        self._fill(store, [1, 2])
        store.clear()
        assert store.entries() == []
        assert not [n for n in os.listdir(tmp_path)
                    if n.endswith(".npz") or n == "manifest.json"]

    def test_concurrent_saves_lose_no_entry(self, tmp_path):
        # Regression: the manifest read-modify-write races a concurrent
        # emergency_checkpoint from the watchdog thread without the save
        # lock — the last writer won with a stale entries list.
        import threading

        store = CheckpointStore(str(tmp_path), retain=64,
                                registry=telemetry.Registry())
        key = jax.random.key(0)

        def writer(base):
            for i in range(8):
                store.save({"x": np.full(4, base + i, np.int32)},
                           key, base + i, 0)

        threads = [threading.Thread(target=writer, args=(b,))
                   for b in (100, 200)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rounds = sorted(e["round"] for e in store.entries())
        assert rounds == sorted(list(range(100, 108)) +
                                list(range(200, 208)))


# ----------------------------------------------------------- supervised run


class TestSupervisedRun:
    def test_chunked_flood_bit_identical_to_unchunked_engine(self, tmp_path):
        # Flood is PRNG-independent, so the chunked supervised run must
        # reproduce the one-program engine loop bit-for-bit.
        g = G.watts_strogatz(1024, 8, 0.1, seed=1)
        run = SupervisedRun(g, Flood(source=0), str(tmp_path),
                            chunk_rounds=3)
        st, summary = run.run_until_coverage(
            jax.random.key(0), coverage_target=0.99, max_rounds=64)
        st_ref, out_ref = engine.run_until_coverage(
            g, Flood(source=0), jax.random.key(0),
            coverage_target=0.99, max_rounds=64)
        np.testing.assert_array_equal(np.asarray(st.seen),
                                      np.asarray(st_ref.seen))
        assert summary["rounds"] == int(out_ref["rounds"])
        assert summary["messages"] == int(out_ref["messages"])
        assert summary["checkpoints"] >= 1
        assert summary["resumed_from"] is None
        assert os.path.exists(summary["checkpoint_path"])

    def test_preempt_twice_then_resume_bit_identical_prng_protocol(
            self, tmp_path):
        # SIR draws randomness every round: the resumed run is only
        # bit-identical if the per-chunk key discipline is exact.
        g = G.watts_strogatz(512, 6, 0.1, seed=3)
        proto = SIR(beta=0.4, gamma=0.15)
        ref = SupervisedRun(g, proto, str(tmp_path / "ref"), chunk_rounds=4)
        st_ref, sum_ref = ref.run_rounds(jax.random.key(5), 20)

        run = SupervisedRun(g, proto, str(tmp_path / "killed"),
                            chunk_rounds=4)
        # Preemption fires BEFORE the checkpoint due at its boundary (a
        # SIGKILL would not have waited for the save): a kill at round 4
        # leaves NO trail, a kill at round 12 leaves rounds 4 and 8.
        failures.preempt(run, at_round=4)
        with pytest.raises(Preempted) as e:
            run.run_rounds(jax.random.key(5), 20)
        assert e.value.round_index == 4
        assert run.store.latest_round() is None
        failures.preempt(run, at_round=12)
        with pytest.raises(Preempted):
            run.run_rounds(jax.random.key(5), 20)
        assert run.store.latest_round() == 8
        st, summary = run.run_rounds(jax.random.key(5), 20)

        assert summary["rounds"] == sum_ref["rounds"] == 20
        assert summary["messages"] == sum_ref["messages"]
        assert summary["resumed_from"] == 8
        assert _state_digest(st) == _state_digest(st_ref)

    def test_preempt_counts_injection(self, tmp_path):
        g = G.ring(64)
        run = SupervisedRun(g, Flood(source=0), str(tmp_path))
        before = telemetry.default_registry().value(
            "sim_injected_failures_total", kind="preempt")
        failures.preempt(run, at_round=2)
        after = telemetry.default_registry().value(
            "sim_injected_failures_total", kind="preempt")
        assert after == before + 1

    def test_resume_skips_corrupt_latest_checkpoint(self, tmp_path):
        g = G.watts_strogatz(512, 6, 0.1, seed=3)
        proto = SIR(beta=0.4, gamma=0.15)
        ref = SupervisedRun(g, proto, str(tmp_path / "ref"), chunk_rounds=4)
        st_ref, _ = ref.run_rounds(jax.random.key(5), 16)

        run = SupervisedRun(g, proto, str(tmp_path / "dmg"), chunk_rounds=4,
                            retain=4)
        failures.preempt(run, at_round=12)
        with pytest.raises(Preempted):
            run.run_rounds(jax.random.key(5), 16)
        # Damage the newest surviving entry (round 8 — the preemption fired
        # before the round-12 save, like a real kill): resume must fall
        # back to the round-4 entry and still match bit-exactly.
        newest = run.store.entries()[-1]
        assert newest["round"] == 8
        path = os.path.join(run.store.directory, newest["file"])
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        st, summary = run.run_rounds(jax.random.key(5), 16)
        assert summary["resumed_from"] == 4
        assert _state_digest(st) == _state_digest(st_ref)

    def test_time_cadence_and_final_checkpoint(self, tmp_path):
        g = G.ring(128)
        # Huge time cadence, no round cadence: only the final checkpoint.
        run = SupervisedRun(g, Flood(source=0), str(tmp_path / "t1"),
                            chunk_rounds=2, checkpoint_every_s=3600.0)
        _, summary = run.run_rounds(jax.random.key(0), 8)
        assert summary["checkpoints"] == 1
        assert run.store.latest_round() == 8
        # Zero time cadence: every chunk boundary checkpoints.
        run2 = SupervisedRun(g, Flood(source=0), str(tmp_path / "t2"),
                             chunk_rounds=2, checkpoint_every_s=0.0)
        _, summary2 = run2.run_rounds(jax.random.key(0), 8)
        assert summary2["checkpoints"] == summary2["chunks"] == 4

    def test_donation_between_chunks_fallback_at_boundaries(
            self, tmp_path, monkeypatch):
        # PR 3's donation semantics across chunks: mid-cadence chunks
        # donate their carry; the chunk feeding a checkpoint runs
        # donate=False. Observable contract: when a boundary chunk's
        # dispatch dies, its (undonated) input state is emergency-
        # checkpointed, so the store resumes from the boundary instead of
        # the previous cadence point.
        g = G.watts_strogatz(512, 6, 0.1, seed=2)
        donate_flags = []
        real = engine.run_from

        def spy(graph, protocol, state, key, rounds, *, donate=True):
            donate_flags.append(donate)
            if len(donate_flags) == 4:  # the 4th chunk feeds a checkpoint
                raise RuntimeError("simulated dispatch death")
            return real(graph, protocol, state, key, rounds, donate=donate)

        monkeypatch.setattr(engine, "run_from", spy)
        run = SupervisedRun(g, Flood(source=0), str(tmp_path),
                            chunk_rounds=2, checkpoint_every_rounds=4)
        with pytest.raises(RuntimeError, match="simulated dispatch death"):
            run.run_rounds(jax.random.key(0), 16)
        # Chunks 1-2 cover rounds 0-4 (chunk 2 feeds the round-4 save);
        # chunk 3 donates; chunk 4 (rounds 6-8) feeds the next save and
        # died — its input (round 6) must have been emergency-saved.
        assert donate_flags == [True, False, True, False]
        assert run.store.latest_round() == 6

    def test_watchdog_stall_during_run_counted_in_summary(self, tmp_path):
        g = G.ring(256)
        reg = telemetry.Registry()
        stalls = []
        slept = []

        def slow_chunk(run, info):
            if not slept:  # one artificial stall, mid-run
                slept.append(True)
                time.sleep(0.5)

        run = SupervisedRun(g, Flood(source=0), str(tmp_path),
                            chunk_rounds=1, deadline_s=0.15,
                            on_stall=stalls.append, on_chunk=slow_chunk,
                            registry=reg)
        _, summary = run.run_until_coverage(
            jax.random.key(0), coverage_target=0.99, max_rounds=64)
        assert summary["stalls"] >= 1
        assert len(stalls) >= 1
        assert reg.value("supervise_watchdog_timeouts_total",
                         watchdog="supervised-coverage") >= 1

    def test_fresh_start_clears_stale_trail(self, tmp_path):
        # resume=False into a directory holding a previous trail: the
        # fresh run owns the directory — stale entries are cleared, the
        # fresh trail is durable, and a subsequent resume continues the
        # FRESH run (not the stale one whose rounds were higher).
        g = G.watts_strogatz(512, 6, 0.1, seed=2)
        run = SupervisedRun(g, Flood(source=0), str(tmp_path),
                            chunk_rounds=4)
        run.run_rounds(jax.random.key(0), 24)
        assert run.store.latest_round() == 24
        run2 = SupervisedRun(g, Flood(source=0), str(tmp_path),
                             chunk_rounds=4)
        failures.preempt(run2, at_round=8)
        with pytest.raises(Preempted):
            run2.run_rounds(jax.random.key(1), 12, resume=False)
        assert run2.store.latest_round() == 4  # fresh trail, stale gone
        _, summary = run2.run_rounds(jax.random.key(1), 12)
        assert summary["resumed_from"] == 4
        assert summary["rounds"] == 12

    def test_resume_on_finished_run_is_noop(self, tmp_path):
        g = G.watts_strogatz(512, 6, 0.1, seed=1)
        run = SupervisedRun(g, Flood(source=0), str(tmp_path),
                            chunk_rounds=4)
        st1, s1 = run.run_until_coverage(jax.random.key(0),
                                         coverage_target=0.99, max_rounds=64)
        st2, s2 = run.run_until_coverage(jax.random.key(0),
                                         coverage_target=0.99, max_rounds=64)
        assert s2["rounds"] == s1["rounds"]
        assert s2["resumed_from"] == s1["rounds"]
        assert s2["chunks"] == 1  # one zero-round probe chunk, no rework
        assert _state_digest(st1) == _state_digest(st2)

    def test_invalid_configuration_rejected(self, tmp_path):
        g = G.ring(16)
        with pytest.raises(ValueError):
            SupervisedRun(g, Flood(source=0), str(tmp_path), chunk_rounds=0)
        with pytest.raises(ValueError):
            SupervisedRun(g, Flood(source=0), str(tmp_path),
                          checkpoint_every_rounds=0)
        with pytest.raises(ValueError):
            CheckpointStore(str(tmp_path), retain=0)


# -------------------------------------- engine: double-resume donation guard


class TestDonatedStateDetection:
    def test_run_from_deleted_state_raises_clear_error(self):
        # Regression: this used to surface as an opaque XLA deleted-buffer
        # error from inside the dispatch.
        g = G.watts_strogatz(256, 4, 0.2, seed=2)
        state = Flood(source=0).init(g, jax.random.key(0))
        state, _ = engine.run_from(g, Flood(source=0), state,
                                   jax.random.key(1), 2)
        # Donate the buffers away...
        engine.run_from(g, Flood(source=0), state, jax.random.key(2), 2)
        # ...then resume the same state again.
        with pytest.raises(ValueError, match="donate=False"):
            engine.run_from(g, Flood(source=0), state, jax.random.key(3), 2)

    def test_coverage_and_converged_resumes_also_guarded(self):
        g = G.watts_strogatz(256, 4, 0.2, seed=2)
        state = Flood(source=0).init(g, jax.random.key(0))
        state, _ = engine.run_from(g, Flood(source=0), state,
                                   jax.random.key(1), 2)
        engine.run_until_coverage_from(g, Flood(source=0), state,
                                       jax.random.key(2), max_rounds=2)
        with pytest.raises(ValueError, match="donate=False"):
            engine.run_until_coverage_from(g, Flood(source=0), state,
                                           jax.random.key(3), max_rounds=2)

    def test_donate_false_keeps_state_resumable(self):
        g = G.watts_strogatz(256, 4, 0.2, seed=2)
        state = Flood(source=0).init(g, jax.random.key(0))
        state, _ = engine.run_from(g, Flood(source=0), state,
                                   jax.random.key(1), 2)
        a, _ = engine.run_from(g, Flood(source=0), state, jax.random.key(2),
                               2, donate=False)
        b, _ = engine.run_from(g, Flood(source=0), state, jax.random.key(2),
                               2, donate=False)
        np.testing.assert_array_equal(np.asarray(a.seen), np.asarray(b.seen))


# ----------------------------------------------------- chaos preempt mirror


class TestChaosPreempt:
    def test_preempt_and_revive_lifecycle(self):
        from p2pnetwork_tpu.chaos import ChaosPlane

        reg = telemetry.Registry()
        plane = ChaosPlane(seed=1, registry=reg)
        plane.preempt(["a", "b"])
        assert not plane.link_ok("a", "c")
        assert not plane.link_ok("c", "b")
        assert reg.value("chaos_injected_failures_total", kind="preempt") == 2
        assert reg.value("chaos_active_faults", kind="preempted_nodes") == 2
        assert reg.value("chaos_active_faults", kind="dead_nodes") == 2
        revived = plane.revive_preempted()
        assert revived == ["a", "b"]
        assert plane.link_ok("a", "c") and plane.link_ok("c", "b")
        assert reg.value("chaos_injected_failures_total",
                         kind="preempt_revive") == 2
        assert reg.value("chaos_active_faults", kind="preempted_nodes") == 0

    def test_revive_nodes_also_clears_preempted(self):
        from p2pnetwork_tpu.chaos import ChaosPlane

        reg = telemetry.Registry()
        plane = ChaosPlane(seed=1, registry=reg)
        plane.preempt(["a"])
        plane.kill_nodes(["b"])
        plane.revive_nodes(["a"])
        assert plane.link_ok("a", "c")
        assert not plane.link_ok("b", "c")
        assert plane.revive_preempted() == []

    def test_kill_stays_dead_across_revive_preempted(self):
        from p2pnetwork_tpu.chaos import ChaosPlane

        plane = ChaosPlane(seed=1, registry=telemetry.Registry())
        plane.kill_nodes(["k"])
        plane.preempt(["p"])
        plane.revive_preempted()
        assert not plane.link_ok("k", "x")  # a kill is a decision
        assert plane.link_ok("p", "x")      # a preemption comes back


# -------------------------------------------- Node.stop(deadline=) drain


class TestNodeStopDeadline:
    def test_undrained_peer_counted_and_stop_bounded(self):
        import socket as socket_mod

        from p2pnetwork_tpu import Node
        from p2pnetwork_tpu.config import NodeConfig

        reg = telemetry.Registry()
        node = Node("127.0.0.1", 0, id="drainer", registry=reg,
                    config=NodeConfig(max_send_buffer=256 * 1024 * 1024))
        node.start()
        raw = socket_mod.create_connection(("127.0.0.1", node.port))
        try:
            raw.sendall(b"peer:12345")
            raw.recv(4096)  # node's id — handshake complete
            assert wait_until(lambda: len(node.nodes_inbound) == 1)
            # A peer that stops reading: flood it far past the socket
            # buffers so bytes are still queued at stop time.
            blob = b"x" * (1 << 20)
            for _ in range(64):
                node.send_to_nodes(blob)
            conn = node.nodes_inbound[0]
            assert wait_until(
                lambda: (conn.writer.transport is not None and
                         conn.writer.transport.get_write_buffer_size() > 0),
                timeout=10.0)
            t0 = time.monotonic()
            node.stop(deadline=0.3)
            node.join(timeout=15.0)
            assert not node.is_alive()
            # Bounded: far under the legacy 10 s-per-connection close wait.
            assert time.monotonic() - t0 < 8.0
            assert reg.value("p2p_shutdown_undelivered_total",
                             node="drainer") > 0
            events = [e for e in node.event_log.snapshot()
                      if e.event == "shutdown_undelivered"]
            assert events and events[0].data["bytes"] > 0
        finally:
            raw.close()
            node.stop()

    def test_drained_peer_counts_nothing(self):
        from p2pnetwork_tpu import Node
        from tests.helpers import stop_all

        reg = telemetry.Registry()
        a = Node("127.0.0.1", 0, id="a", registry=reg)
        b = Node("127.0.0.1", 0, id="b", registry=reg)
        a.start()
        b.start()
        try:
            assert a.connect_with_node("127.0.0.1", b.port)
            a.send_to_nodes("bye")
            assert wait_until(lambda: b.message_count_recv == 1)
            a.stop(deadline=2.0)
            a.join(timeout=10.0)
            assert reg.value("p2p_shutdown_undelivered_total", node="a") == 0
        finally:
            stop_all([a, b])


# --------------------------------------- SIGKILL crash-recovery subprocess

_CHILD = """
import hashlib, sys, time

import numpy as np

sys.path.insert(0, {repo!r})
import jax

from p2pnetwork_tpu.models import SIR
from p2pnetwork_tpu.sim import graph as G
from p2pnetwork_tpu.supervise import SupervisedRun

store_dir, sleep_s = sys.argv[1], float(sys.argv[2])
g = G.watts_strogatz(512, 6, 0.1, seed=3)


def on_chunk(run, info):
    if sleep_s:
        time.sleep(sleep_s)  # widen the SIGKILL window per chunk


run = SupervisedRun(g, SIR(beta=0.4, gamma=0.15), store_dir,
                    chunk_rounds=2, retain=50, on_chunk=on_chunk)
state, summary = run.run_rounds(jax.random.key(5), 30)
leaves = jax.tree_util.tree_leaves(jax.device_get(state))
h = hashlib.sha256()
for leaf in leaves:
    h.update(np.ascontiguousarray(leaf).tobytes())
print("DONE", h.hexdigest(), summary["rounds"], summary["resumed_from"],
      flush=True)
"""


@pytest.mark.slow
class TestSigkillRecovery:
    def _spawn(self, script, store_dir, sleep_s):
        return subprocess.Popen(
            [sys.executable, str(script), str(store_dir), str(sleep_s)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=REPO)

    def _entries(self, store_dir):
        try:
            with open(os.path.join(store_dir, "manifest.json"),
                      encoding="utf-8") as f:
                return json.load(f)["entries"]
        except (OSError, ValueError, KeyError):
            return []

    def _kill_at_round(self, script, store_dir, at_round):
        """Run the child until its checkpoint trail reaches ``at_round``,
        then SIGKILL it mid-run. Returns False (never fails) if the child
        finished before the kill landed — the box was too fast, and the
        other kill point still exercises the path."""
        p = self._spawn(script, store_dir, sleep_s=0.3)
        try:
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                rounds = [e["round"] for e in self._entries(store_dir)]
                if rounds and max(rounds) >= at_round:
                    os.kill(p.pid, signal.SIGKILL)
                    p.wait(timeout=30)
                    return True
                if p.poll() is not None:
                    return False  # finished before the kill landed
                time.sleep(0.02)
            pytest.fail("child never reached the kill point")
        finally:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)

    def test_sigkill_twice_resumed_state_bit_identical(self, tmp_path):
        script = tmp_path / "child.py"
        script.write_text(_CHILD.format(repo=REPO))

        # Reference: one uninterrupted child run.
        ref_dir = tmp_path / "ref"
        p = self._spawn(script, ref_dir, sleep_s=0.0)
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        ref_line = [ln for ln in out.splitlines() if ln.startswith("DONE")][0]
        _, ref_digest, ref_rounds, _ = ref_line.split()

        # Killed run: SIGKILL mid-chunk at two different points of the
        # trail, then run to completion.
        kill_dir = tmp_path / "killed"
        killed_first = self._kill_at_round(script, kill_dir, 4)
        rounds_after_first = [e["round"] for e in self._entries(kill_dir)]
        killed_second = self._kill_at_round(script, kill_dir, 12)
        p = self._spawn(script, kill_dir, sleep_s=0.0)
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        line = [ln for ln in out.splitlines() if ln.startswith("DONE")][0]
        _, digest, rounds, resumed_from = line.split()

        assert rounds == ref_rounds == "30"
        assert digest == ref_digest, (
            "resumed final state diverged from the uninterrupted run")
        if killed_first or killed_second:
            assert resumed_from != "None"  # at least one real resume
        if killed_first and rounds_after_first:
            # The second attempt resumed a partial trail, not round 0.
            assert max(rounds_after_first) < 30

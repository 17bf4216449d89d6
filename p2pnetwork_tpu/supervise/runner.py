"""SupervisedRun: crash-tolerant chunked execution of the sim engine.

The engine's run-to-* loops (sim/engine.py) are single device programs —
maximally fast, and maximally fragile: a preemption or a wedged device
dispatch mid-run loses everything since the last *manual*
``sim/checkpoint.py`` save. :class:`SupervisedRun` drives those same loops
in round chunks and owns everything around them:

- **auto-checkpoint** every N rounds or T seconds into a
  :class:`~p2pnetwork_tpu.supervise.store.CheckpointStore` (atomic entries,
  manifest latest-pointer, retention, corrupt-skip resume);
- **resume**: a run killed at any point — SIGKILL included — restarts from
  the newest loadable entry and produces a final state **bit-identical**
  to an uninterrupted supervised run (tests/test_supervise.py proves it
  under double SIGKILL);
- **watchdog**: a deadline thread fed heartbeats at chunk boundaries
  (supervise/watchdog.py) turns a wedged dispatch into a structured stall
  event at runtime, not just at bench probe time;
- **deterministic preemption**: ``arm_preemption`` / ``failures.preempt``
  kill the harness at an exact round (:class:`Preempted`), and the next
  ``run_*`` call revives it from the last durable checkpoint.

Determinism contract: the PRNG chain is keyed per chunk as
``fold_in(base_key, chunk_start_round + 1)``, and chunk boundaries are a
pure function of (chunk_rounds, start round). Checkpoints only land at
chunk boundaries, so a resumed run re-enters exactly the boundary schedule
the uninterrupted run walked — same chunk keys, same states. (Chunked runs
differ from *unchunked* ``engine.run_until_coverage`` only in RNG chain;
PRNG-independent protocols like Flood are bit-identical to those too.)

Donation across chunks preserves PR 3's semantics: the state carry is
donated between chunks (one live copy in HBM), EXCEPT the chunk that feeds
a checkpoint save, which runs ``donate=False`` — its input state stays
alive as the in-memory fallback, so a dispatch that dies at a checkpoint
boundary (exactly where stalls get killed) still leaves the harness a
valid state to emergency-checkpoint before unwinding
(:meth:`SupervisedRun.emergency_checkpoint`, also safe to call from an
``on_stall`` hook).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Union

import jax
import numpy as np

from p2pnetwork_tpu import concurrency, telemetry
from p2pnetwork_tpu.sim import engine
from p2pnetwork_tpu.supervise.store import CheckpointStore
from p2pnetwork_tpu.supervise.watchdog import Watchdog
from p2pnetwork_tpu.telemetry import spans

__all__ = ["SupervisedRun", "Preempted"]


class Preempted(RuntimeError):
    """The harness was deterministically killed at a round boundary
    (``failures.preempt`` / ``arm_preemption``). Revive by calling the
    same ``run_*`` entry again — it resumes from the last durable
    checkpoint, never from this exception's in-memory state."""

    def __init__(self, round_index: int):
        self.round_index = round_index
        super().__init__(
            f"supervised run preempted at round {round_index} "
            f"(resume from the checkpoint store to revive)")


class SupervisedRun:
    """Crash-tolerant harness over ``engine.run_from`` /
    ``engine.run_until_coverage_from``.

    Parameters
    ----------
    graph, protocol:
        Exactly the engine's contract.
    store:
        A :class:`CheckpointStore`, or a directory path (a store with
        ``retain`` entries is created there).
    chunk_rounds:
        Rounds per device dispatch. Smaller chunks mean finer checkpoint
        and heartbeat granularity at the cost of more host round-trips;
        the per-chunk overhead is one dispatch plus one packed-summary
        transfer (coverage mode).
    checkpoint_every_rounds / checkpoint_every_s:
        Auto-checkpoint cadence, whichever fires first, evaluated at
        chunk boundaries. Defaults to every chunk when neither is set.
    deadline_s / on_stall:
        Watchdog deadline per chunk dispatch and its stall mode
        (``"raise"`` / ``"warn"`` / callable, like ``retrace_guard``).
        ``None`` disables the watchdog.
    heal:
        A :class:`~p2pnetwork_tpu.supervise.heal.RetryPolicy`
        (graftquake self-healing): every chunk dispatch runs undonated
        under a :class:`~p2pnetwork_tpu.supervise.heal.Healer`, so a
        detected DISPATCH fault (injected chip preemption, wedged
        dispatch, watchdog stall surfaced inside the dispatch) rolls
        the chunk back to its retained input and re-executes with the
        SAME chunk key — the healed run is bit-identical to an
        unfaulted one. Integrity DETECTION (template audit, checksum
        cross-validation) needs a template/verify dispatch the generic
        runner cannot derive — drive
        :meth:`~p2pnetwork_tpu.supervise.heal.Healer.run_chunk`
        directly to add those. Costs one extra live state copy;
        ``None`` (default) keeps mid-cadence chunk donation.
    on_chunk:
        Optional ``callable(run, info)`` fired after every chunk with
        ``{"round", "executed", "coverage", "checkpointed"}`` — the
        progress seam (bench telemetry, tests).
    """

    def __init__(self, graph, protocol,
                 store: Union[CheckpointStore, str], *,
                 chunk_rounds: int = 32,
                 checkpoint_every_rounds: Optional[int] = None,
                 checkpoint_every_s: Optional[float] = None,
                 retain: int = 3,
                 deadline_s: Optional[float] = None,
                 on_stall: Union[str, Callable] = "raise",
                 heal=None,
                 on_chunk: Optional[Callable] = None,
                 registry: Optional[telemetry.Registry] = None):
        if chunk_rounds < 1:
            raise ValueError("chunk_rounds must be >= 1")
        if checkpoint_every_rounds is not None and checkpoint_every_rounds < 1:
            raise ValueError("checkpoint_every_rounds must be >= 1")
        self.graph = graph
        self.protocol = protocol
        self.store = store if isinstance(store, CheckpointStore) \
            else CheckpointStore(store, retain=retain, registry=registry)
        self.chunk_rounds = int(chunk_rounds)
        if checkpoint_every_rounds is None and checkpoint_every_s is None:
            checkpoint_every_rounds = self.chunk_rounds
        self.checkpoint_every_rounds = checkpoint_every_rounds
        self.checkpoint_every_s = checkpoint_every_s
        self.deadline_s = deadline_s
        self.on_stall = on_stall
        self.heal = heal
        self.on_chunk = on_chunk
        self._registry = registry
        reg = registry if registry is not None else telemetry.default_registry()
        self._m_chunks = reg.counter(
            "supervise_chunks_total",
            "Device-dispatch chunks executed by supervised runs.")
        self._m_runs = reg.counter(
            "supervise_runs_total",
            "Supervised run invocations, by outcome.", ("outcome",))
        self._m_resumes = reg.counter(
            "supervise_resumes_total",
            "Supervised runs that restored state from the checkpoint store "
            "instead of a fresh protocol init.")
        self._preempt_at: Optional[int] = None
        # Fallback snapshot for emergency checkpoints: the undonated input
        # of a checkpoint-boundary chunk, published for the duration of
        # that chunk's dispatch. Guarded: the watchdog's on_stall hook
        # reads it from the watchdog thread while the run thread swaps it.
        self._fb_lock = concurrency.lock()
        self._fallback: Optional[tuple] = None

    # ----------------------------------------------------------- preemption

    def arm_preemption(self, at_round: int) -> None:
        """Arm a one-shot deterministic kill: the chunk loop raises
        :class:`Preempted` at the first chunk boundary at or past
        ``at_round``, BEFORE taking any checkpoint due there — exactly the
        damage a real SIGKILL at that moment inflicts. Prefer arming via
        ``sim.failures.preempt``, which also counts the injection."""
        self._preempt_at = int(at_round)

    # ------------------------------------------------------------ emergency

    def emergency_checkpoint(self) -> Optional[str]:
        """Persist the current fallback state, if one is alive.

        Safe from any thread (an ``on_stall`` hook runs on the watchdog
        thread). Only checkpoint-boundary chunks publish a fallback (their
        input runs undonated); mid-cadence chunks have donated their input
        away, so there is nothing valid to save and this returns ``None``.
        """
        with self._fb_lock:
            fb = self._fallback
        if fb is None:
            return None
        state, base_key, rnd, msgs = fb
        return self.store.save(state, base_key, rnd, msgs)

    def _set_fallback(self, fb: Optional[tuple]) -> None:
        with self._fb_lock:
            self._fallback = fb

    # ----------------------------------------------------------- entrypoints

    def run_until_coverage(self, key, *, coverage_target: float = 0.99,
                           max_rounds: int = 1024, steps_per_round: int = 1,
                           resume: bool = True) -> tuple:
        """Supervised ``engine.run_until_coverage_from``: chunked, auto-
        checkpointed, resumable. Returns ``(state, summary)`` where
        ``summary`` carries ``rounds`` (cumulative, resumed rounds
        included), ``coverage``, exact ``messages``, plus supervision
        fields (``chunks``, ``checkpoints``, ``resumed_from``,
        ``checkpoint_path``, ``stalls``).

        ``key`` seeds a FRESH run only; on resume the checkpoint's stored
        base key is authoritative (the RNG chain must continue the
        interrupted run's, not start a new one). A fresh start into a
        directory still holding a previous trail CLEARS that trail —
        ``resume=False`` means this run owns the directory."""
        return self._drive("coverage", key, max_rounds,
                           coverage_target=coverage_target,
                           steps_per_round=steps_per_round, resume=resume)

    def run_rounds(self, key, rounds: int, *, resume: bool = True) -> tuple:
        """Supervised ``engine.run_from``: execute ``rounds`` total rounds
        (checkpointed progress counts toward the total on resume).
        Returns ``(state, summary)``."""
        return self._drive("rounds", key, rounds, resume=resume)

    # ------------------------------------------------------------ the loop

    def _restore_or_init(self, key, resume: bool):
        template = jax.eval_shape(
            lambda k: self.protocol.init(self.graph, k), key)
        template = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype), template)
        # grow=True: a trail written before a Graph.grow capacity repad is
        # still this run's trail — zero-extend it into the grown template
        # (checkpoint.grow_state) so resume-across-repad is bit-identical
        # to an uninterrupted grown run. Identity when shapes match.
        restored = self.store.load_latest(template, grow=True) \
            if resume else None
        if restored is not None:
            state, base_key, rnd, msgs, path = restored
            # device_put once: checkpoint leaves come back as host numpy,
            # and donating committed host buffers is a silent no-op plus a
            # warning — land them on device so chunk donation is real.
            state = jax.device_put(state)
            self._m_resumes.inc()
            return state, base_key, int(rnd), int(msgs), int(rnd)
        # Fresh start (resume=False, or nothing in the trail loaded): any
        # leftover entries belong to a PREVIOUS run — clear them, or this
        # run's round-N checkpoints would interleave with (and resume
        # under) the stale trail's higher rounds.
        if self.store.entries():
            self.store.clear()
        state = self.protocol.init(self.graph, key)
        return state, key, 0, 0, None

    def _ckpt_due(self, rounds_since: int, t_last: float) -> bool:
        if self.checkpoint_every_rounds is not None \
                and rounds_since >= self.checkpoint_every_rounds:
            return True
        if self.checkpoint_every_s is not None \
                and time.monotonic() - t_last >= self.checkpoint_every_s:
            return True
        return False

    def _drive(self, mode: str, key, total_target: int, *,
               coverage_target: float = 0.99, steps_per_round: int = 1,
               resume: bool = True) -> tuple:
        # graftscope trace plane: one supervised_run span per drive,
        # chunk boundaries / checkpoints / resumes as point events under
        # it (telemetry/spans.py; no-ops when no tracer is installed).
        with spans.span("supervised_run", mode=mode):
            return self._drive_under_span(
                mode, key, total_target, coverage_target=coverage_target,
                steps_per_round=steps_per_round, resume=resume)

    def _drive_under_span(self, mode: str, key, total_target: int, *,
                          coverage_target: float = 0.99,
                          steps_per_round: int = 1,
                          resume: bool = True) -> tuple:
        state, base_key, total, messages, resumed_from = \
            self._restore_or_init(key, resume)
        if resumed_from is not None:
            spans.emit("resume", round=total)
        last_ckpt_round, t_last_ckpt = total, time.monotonic()
        coverage = None
        chunks = n_ckpts = 0
        last_path = None
        outcome = "completed"
        watchdog = None
        if self.deadline_s is not None:
            watchdog = Watchdog(self.deadline_s, name=f"supervised-{mode}",
                                on_stall=self.on_stall,
                                registry=self._registry).start()
        healer = None
        if self.heal is not None:
            from p2pnetwork_tpu.supervise.heal import Healer

            # Rollback authority is the RETAINED chunk input (healing
            # forces donate=False below), never the store: the store's
            # newest entry can be an older boundary, and re-executing
            # one chunk from an older round would corrupt the round
            # accounting this loop owns.
            healer = Healer(self.heal, registry=self._registry)
        try:
            while total < total_target:
                chunk = min(self.chunk_rounds, total_target - total)
                ckpt_feeding = self._ckpt_due(
                    total + chunk - last_ckpt_round, t_last_ckpt) \
                    or (total + chunk >= total_target)
                chunk_key = jax.random.fold_in(base_key, total + 1)
                if watchdog is not None:
                    watchdog.heartbeat()
                if ckpt_feeding:
                    # This chunk feeds a checkpoint save: keep its input
                    # alive (donate=False) as the emergency fallback for
                    # the duration of the dispatch (module docstring).
                    self._set_fallback((state, base_key, total, messages))
                try:
                    donate_chunk = healer is None and not ckpt_feeding
                    if mode == "coverage":
                        def _chunk_cov(s, _key=chunk_key, _n=chunk):
                            return engine.run_until_coverage_from(
                                self.graph, self.protocol, s, _key,
                                coverage_target=coverage_target,
                                max_rounds=_n,
                                steps_per_round=steps_per_round,
                                donate=donate_chunk)

                        if healer is not None:
                            state, out = healer.run_chunk(
                                _chunk_cov, state, chunk_index=chunks)
                        else:
                            state, out = _chunk_cov(state)
                        executed = int(out["rounds"])  # graftlint: ignore[host-sync-in-loop] -- packed summary already transferred by the engine; these are host scalars
                        messages += int(out["messages"])  # graftlint: ignore[host-sync-in-loop] -- host scalar (see above)
                        coverage = float(out["coverage"])  # graftlint: ignore[host-sync-in-loop] -- host scalar (see above)
                    else:
                        def _chunk_rounds(s, _key=chunk_key, _n=chunk):
                            return engine.run_from(
                                self.graph, self.protocol, s, _key,
                                _n, donate=donate_chunk)

                        if healer is not None:
                            state, stats = healer.run_chunk(
                                _chunk_rounds, state, chunk_index=chunks)
                        else:
                            state, stats = _chunk_rounds(state)
                        executed = chunk
                        if "messages" in stats:
                            messages += int(  # graftlint: ignore[host-sync-in-loop] -- one transfer per CHUNK is the supervised design (checkpoint totals need it), not a per-round sync
                                np.asarray(stats["messages"]).sum())
                except BaseException:
                    # The dispatch died mid-chunk. If this was a boundary
                    # chunk its input is still valid — make it durable so
                    # even a crash the periodic cadence missed resumes
                    # from here, then unwind.
                    try:
                        self.emergency_checkpoint()
                    except Exception:
                        pass  # a failing save must not mask the real error
                    raise
                finally:
                    self._set_fallback(None)
                if watchdog is not None:
                    watchdog.heartbeat()
                total += executed
                chunks += 1
                self._m_chunks.inc()
                done = (total >= total_target or
                        (mode == "coverage" and
                         (executed == 0 or
                          (coverage is not None
                           and coverage >= coverage_target))))
                if self._preempt_at is not None \
                        and total >= self._preempt_at:
                    # Deterministic kill: fires BEFORE the checkpoint due
                    # at this boundary, like a real SIGKILL would.
                    self._preempt_at = None
                    outcome = "preempted"
                    raise Preempted(total)
                checkpointed = False
                if done or self._ckpt_due(total - last_ckpt_round,
                                          t_last_ckpt):
                    last_path = self.store.save(
                        state, base_key, total, messages)
                    last_ckpt_round, t_last_ckpt = total, time.monotonic()
                    n_ckpts += 1
                    checkpointed = True
                    spans.emit("checkpoint", round=total, path=last_path)
                spans.emit("chunk", round=total, executed=executed,
                           checkpointed=checkpointed)
                # graftsight: a chunk that needed healing leaves its
                # attempt history on the healer — surface it next to the
                # chunk event (correlated by round) and hand it to the
                # on_chunk observer, so a supervised soak's trace answers
                # "which chunks healed, from what" without log archaeology.
                heal_report = None if healer is None else healer.last_report
                if heal_report is not None and heal_report["events"]:
                    spans.emit("heal_report", round=total,
                               chunk=heal_report["chunk"],
                               attempts=heal_report["attempts"],
                               healed=heal_report["healed"],
                               fallback=heal_report["fallback"])
                if self.on_chunk is not None:
                    self.on_chunk(self, {
                        "round": total, "executed": executed,
                        "coverage": coverage, "checkpointed": checkpointed,
                        "heal": heal_report,
                    })
                if done:
                    break
        except Preempted:
            raise
        except BaseException:
            outcome = "error"
            raise
        finally:
            if watchdog is not None:
                watchdog.close()
            self._m_runs.labels(outcome).inc()
        summary: Dict[str, Any] = {
            "rounds": total, "messages": messages, "chunks": chunks,
            "checkpoints": n_ckpts, "resumed_from": resumed_from,
            "checkpoint_path": last_path,
            "stalls": watchdog.stalls if watchdog is not None else 0,
        }
        if coverage is not None:
            summary["coverage"] = coverage
        return state, summary

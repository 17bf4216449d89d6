"""graftquake device plane: seeded fault injection for the compiled engines.

The sockets backend has a chaos plane (chaos/plane.py) and the thread
plane has graftrace, but until now the DEVICE plane — the sharded ring
engine and the serving driver the production story rides on — had zero
fault coverage: a flipped halo word was silent corruption, a lost chip
an opaque XLA error. This module injects those failures on purpose,
deterministically, through the existing seams:

- **Halo-hop faults** (:class:`FaultSchedule` + :class:`FaultSpec`): a
  ``comm=`` value for parallel/sharded.py entry points that wraps either
  halo backend (``ppermute`` / ``pallas``) in a :class:`FaultyComm`. On
  ring step ``t`` of round ``r``, shard ``d``'s received block is
  corrupted (seeded sparse bit-flips), zeroed (hop lost), or delayed
  (rotation stalls — the shard keeps its own block) when the schedule
  says so. Every decision is ``fold_in(seed, round, step, shard)``
  pure-jax, so fault sites are byte-replayable and host-predictable
  (:meth:`FaultSchedule.sites_between` replays them without a mesh).
  Off by default and zero cost when absent: a plain backend string
  compiles exactly the code it always did.

- **Dispatch faults** (:class:`DispatchChaos`): chunk-boundary chip
  preemption (:class:`ChipLost`) and a wedged-dispatch mode
  (:class:`WedgedDispatch`) raised at the engine/serve chunk dispatch
  gate (``engine.run_batch_until_coverage``,
  ``engine.run_until_coverage_from``, ``engine.run_from``,
  ``sharded.run_batch_until_coverage``). Armings are one-shot, so a
  retry (supervise/heal.py) lands on a healthy dispatch — the
  fail-stop-then-recover shape of a real preemption.

Injections count into ``chaos_device_faults_total{kind}``; the halo
counts are a host replay of the schedule over the rounds a run actually
executed, so the counter reflects the schedule exactly. Recovery is the
other half of the story: supervise/heal.py detects (integrity checks)
and re-executes (rollback + retry policy) — see GETTING_STARTED.md
"Device-plane chaos & self-healing".

Top-level import is stdlib-only (jax is deferred into the fault math)
so the dispatch gate costs the engines one module attribute read plus a
None check when nothing is installed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from p2pnetwork_tpu import concurrency, telemetry
from p2pnetwork_tpu.telemetry import spans

__all__ = [
    "FAULT_KINDS", "FaultSchedule", "FaultSpec", "FaultyComm",
    "ChipLost", "WedgedDispatch", "DispatchChaos", "UnreachableFaultSite",
    "install_dispatch_chaos", "dispatch_gate", "record_faults",
]

#: Halo-hop fault kinds, in device-code order (code = index + 1; 0 = none).
FAULT_KINDS = ("corrupt", "zero", "delay")
_KIND_CODE = {k: i + 1 for i, k in enumerate(FAULT_KINDS)}

#: FaultSpec wraps one of these concrete backends (sharded.COMM_BACKENDS;
#: literal here so this module stays importable without jax — the spec is
#: re-validated by _RingComm construction inside the trace either way).
_BACKENDS = ("ppermute", "pallas")


def _faults_counter(registry: Optional[telemetry.Registry] = None):
    reg = registry if registry is not None else telemetry.default_registry()
    return reg.counter(
        "chaos_device_faults_total",
        "Device-plane faults injected by graftquake, by kind (corrupt / "
        "zero / delay halo hops from a FaultSchedule; preempt / wedge "
        "dispatch faults from DispatchChaos).", ("kind",))


class UnreachableFaultSite(UserWarning):
    """An explicit ``FaultSchedule.sites`` entry can never fire on the
    ring it was handed to: its step or shard index is outside
    ``[0, axis_size)``. The classic way to hit this is live overlay
    growth — a schedule authored against the pre-grow shard count is
    replayed against the regrown ring and some sites fall off the end.
    A site that silently never fires would make a chaos run look
    healthier than it is, so the mismatch is loud (this warning plus a
    ``fault_sites_unreachable`` trace event), but not fatal: the
    in-range sites still inject exactly as scheduled."""


class ChipLost(RuntimeError):
    """An injected chunk-boundary chip preemption: the dispatch never ran
    (the gate raises before any buffer is touched), exactly the damage a
    real mid-job chip loss inflicts at a chunk boundary. Healable — the
    arming is one-shot, so a policy-driven retry lands clean."""

    def __init__(self, dispatch_index: int):
        self.dispatch_index = int(dispatch_index)
        super().__init__(
            f"injected chip preemption at dispatch {dispatch_index} "
            "(chaos/device.DispatchChaos)")


class WedgedDispatch(RuntimeError):
    """An injected wedged device dispatch: stands in for the
    watchdog-detected stall a hung device dispatch produces (the real
    thing hangs holding the GIL — raising is the testable surrogate, the same shape
    supervise/watchdog.py turns a live stall into)."""

    def __init__(self, dispatch_index: int):
        self.dispatch_index = int(dispatch_index)
        super().__init__(
            f"injected wedged dispatch at index {dispatch_index} "
            "(chaos/device.DispatchChaos)")


# ------------------------------------------------------ halo-hop faults


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """A seeded, byte-replayable schedule of halo-hop faults.

    Every (round, step, shard) site draws one uniform from
    ``fold_in(fold_in(fold_in(key(seed), round), step), shard)`` and
    maps it through the ``corrupt``/``zero``/``delay`` probability
    thresholds — the same pure-jax draw inside the compiled loop and in
    the host replay (:meth:`sites_between`), so fault sites are
    identical wherever they are computed. ``round`` is the GLOBAL round
    (chunked drivers pass ``fault_round0`` so resumed/retried chunks
    key the same sites an unchunked run would). ``sites`` adds exact
    explicit placements ``(round, step, shard, kind)`` on top —
    deterministic test vectors; they ignore the round window.

    Kinds, applied to the block shard ``d`` RECEIVES at that hop:

    - ``corrupt``: seeded sparse bit-flips (``corrupt_density`` of the
      payload's elements XOR a random nonzero pattern; bools flip);
    - ``zero``: the whole hop zeroed (payload lost);
    - ``delay``: the rotation stalls — the shard keeps its own
      pre-shift block for this hop.
    """

    seed: int = 0
    corrupt: float = 0.0
    zero: float = 0.0
    delay: float = 0.0
    start_round: int = 0
    stop_round: int = 1 << 30
    corrupt_density: float = 1.0 / 64.0
    sites: Tuple[Tuple[int, int, int, str], ...] = ()

    def __post_init__(self):
        # Coerce list-form sites to tuples: the schedule must stay
        # hashable (FaultSpec keys the lru-cached compiled-loop
        # factories like a backend string does).
        object.__setattr__(self, "sites",
                           tuple(tuple(s) for s in self.sites))
        total = self.corrupt + self.zero + self.delay
        if min(self.corrupt, self.zero, self.delay) < 0 or total > 1.0:
            raise ValueError(
                "fault probabilities must be >= 0 and sum to <= 1, got "
                f"corrupt={self.corrupt} zero={self.zero} "
                f"delay={self.delay}")
        if not 0.0 < self.corrupt_density <= 1.0:
            raise ValueError("corrupt_density must be in (0, 1]")
        for site in self.sites:
            if len(site) != 4 or site[3] not in _KIND_CODE:
                raise ValueError(
                    f"schedule site must be (round, step, shard, kind) "
                    f"with kind in {FAULT_KINDS}, got {site!r}")

    @property
    def active(self) -> bool:
        """False for the empty schedule — FaultyComm then passes every
        hop through untouched (bit-identical to the bare backend)."""
        return bool(self.sites) or (self.corrupt + self.zero
                                    + self.delay) > 0.0

    # ------------------------------------------------------- device side

    def _site_key(self, rnd, step, shard):
        import jax

        k = jax.random.key(self.seed)
        k = jax.random.fold_in(k, rnd)
        k = jax.random.fold_in(k, step)
        return jax.random.fold_in(k, shard)

    def kind_at(self, rnd, step, shard):
        """Fault-kind code (i32: 0 none, 1 corrupt, 2 zero, 3 delay) at
        one site. Pure jax — traceable inside the ring pass and
        vmappable for the host replay."""
        import jax
        import jax.numpy as jnp

        kind = jnp.int32(0)
        p_c, p_z, p_d = self.corrupt, self.zero, self.delay
        if p_c + p_z + p_d > 0.0:
            u = jax.random.uniform(
                jax.random.fold_in(self._site_key(rnd, step, shard), 0))
            kind = jnp.where(
                u < p_c, 1,
                jnp.where(u < p_c + p_z, 2,
                          jnp.where(u < p_c + p_z + p_d, 3, 0)),
            ).astype(jnp.int32)
            in_window = (rnd >= self.start_round) & (rnd < self.stop_round)
            kind = jnp.where(in_window, kind, jnp.int32(0))
        for sr, st, sd, sk in self.sites:
            hit = (rnd == sr) & (step == st) & (shard == sd)
            kind = jnp.where(hit, jnp.int32(_KIND_CODE[sk]), kind)
        return kind

    def corrupt_payload(self, payload, rnd, step, shard):
        """The seeded bit-flipped form of one hop's payload (same shape
        and dtype; a ``corrupt_density`` fraction of elements XOR a
        random nonzero pattern — floats go through a bitcast, so NaN/Inf
        patterns are possible and the integrity audit's finiteness check
        is a real detector)."""
        import jax
        import jax.numpy as jnp

        k = jax.random.fold_in(self._site_key(rnd, step, shard), 1)
        k_mask, k_bits = jax.random.split(k)
        if payload.dtype == jnp.bool_:
            return payload ^ jax.random.bernoulli(
                k_mask, self.corrupt_density, payload.shape)
        itemsize = jnp.dtype(payload.dtype).itemsize
        uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}.get(itemsize)
        if uint is None:
            raise NotImplementedError(
                f"corrupt fault has no bit-flip form for {payload.dtype} "
                "(64-bit payloads need jax x64)")
        words = payload if payload.dtype == uint else \
            jax.lax.bitcast_convert_type(payload, uint)
        flip = jax.random.bernoulli(k_mask, self.corrupt_density,
                                    payload.shape)
        bits = jax.random.bits(k_bits, payload.shape, uint) | uint(1)
        words = jnp.where(flip, words ^ bits, words)
        return words if payload.dtype == uint else \
            jax.lax.bitcast_convert_type(words, payload.dtype)

    # --------------------------------------------------------- host side

    def sites_between(self, round0: int, round1: int, n_steps: int,
                      n_shards: int):
        """Host replay of the device draw: every fault site with
        ``round0 <= round < round1`` over ``n_steps`` hops per round and
        ``n_shards`` shards, as ``[(round, step, shard, kind), ...]``
        sorted by site. Byte-identical across calls and identical to
        what the compiled loop applied (same fold_in chain)."""
        if round1 <= round0 or n_steps <= 0 or n_shards <= 0 \
                or not self.active:
            return []
        import jax
        import numpy as np

        rr, tt, dd = np.meshgrid(
            np.arange(round0, round1), np.arange(n_steps),
            np.arange(n_shards), indexing="ij")
        kinds = np.asarray(jax.vmap(self.kind_at)(
            rr.ravel(), tt.ravel(), dd.ravel()))
        out = []
        for r, t, d, k in zip(rr.ravel().tolist(), tt.ravel().tolist(),
                              dd.ravel().tolist(), kinds.tolist()):
            if k:
                out.append((r, t, d, FAULT_KINDS[k - 1]))
        return out

    def counts_between(self, round0: int, round1: int, n_steps: int,
                       n_shards: int):
        """Fault counts by kind over the same window — what
        :func:`record_faults` feeds ``chaos_device_faults_total``."""
        counts = {k: 0 for k in FAULT_KINDS}
        for _, _, _, kind in self.sites_between(round0, round1, n_steps,
                                                n_shards):
            counts[kind] += 1
        return counts


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """A ``comm=`` value for the sharded entry points: run the ring on
    ``backend`` with ``schedule``'s faults injected at the halo hops.
    Hashable (it keys the same compiled-loop caches a backend string
    does). The fault-wired entries — ``flood_until_coverage`` and
    ``run_batch_until_coverage`` — feed the ring the global round via
    ``fault_round0``; other entries run with round context 0 (every
    round keys the same sites — fine for single-pass calls like
    ``propagate``, wrong for multi-round accounting, so wire before
    relying on counts there). ``backend`` must be concrete ("ppermute"
    or "pallas" — resolve "auto" with parallel/auto.resolve_comm
    first)."""

    schedule: FaultSchedule
    backend: str = "ppermute"

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(
                f"FaultSpec.backend must be one of {_BACKENDS} (resolve "
                f"'auto' before building the spec), got {self.backend!r}")

    def make(self, axis_name: str, axis_size: int) -> "FaultyComm":
        """The sharded._make_ring_comm seam: build this spec's comm
        object for one ring. Rebuilt on every recompile — in particular
        after a live ``Graph.grow`` changes the ring size — so this is
        where explicit schedule sites are checked against the ring they
        will actually run on: a site whose step or shard is outside
        ``[0, axis_size)`` can never fire (ring steps and shard indices
        both range over the axis size) and draws a structured
        :class:`UnreachableFaultSite` warning instead of vanishing."""
        import warnings

        stale = [s for s in self.schedule.sites
                 if not (0 <= s[1] < axis_size and 0 <= s[2] < axis_size)]
        if stale:
            warnings.warn(
                f"{len(stale)} explicit fault site(s) unreachable on "
                f"ring axis {axis_name!r} (size {axis_size}): "
                f"{stale[:8]!r}{' ...' if len(stale) > 8 else ''} — "
                "step/shard must lie in [0, axis_size); a schedule "
                "authored before overlay growth must be re-targeted",
                UnreachableFaultSite, stacklevel=2)
            if spans.current_tracer() is not None:
                spans.emit("fault_sites_unreachable", axis=axis_name,
                           axis_size=int(axis_size), n_stale=len(stale),
                           sites=[list(s) for s in stale[:16]])
        return FaultyComm(self, axis_name, axis_size)


class FaultyComm:
    """A ``_RingComm``-interface wrapper that injects the schedule's
    faults into the forward halo hops. The wrapped inner backend does
    the real transfer (and the payload-template validation); this layer
    only rewrites what the receiving shard sees, keyed on
    ``(round, step, shard)`` — round/step context arrives through
    :meth:`set_context` (the ring bodies call it; ``wants_step`` makes
    ``_ring_pass`` thread the step index through its scan), shard is
    ``lax.axis_index`` at apply time.

    ``shift_back`` (the remask Horner accumulation) stays clean — the
    schedule's sites name forward hops. ``fuses`` is False: the fused
    DMA-under-segment-sum kernel is bit-identical to shift+apply (the
    PR-11 pin), and the unfused form is where the hop payload is
    exposed for injection.
    """

    #: _ring_pass threads its scan's step index to set_context when set.
    wants_step = True
    fuses = False

    def __init__(self, spec: FaultSpec, axis_name: str, axis_size: int):
        from p2pnetwork_tpu.parallel.sharded import _RingComm

        self._inner = _RingComm(spec.backend, axis_name, axis_size)
        self.backend = spec.backend
        self.axis_name = axis_name
        self.axis_size = axis_size
        self.schedule = spec.schedule
        self._round = None
        self._step = None

    def set_context(self, round=None, step=None):
        """Record the device round/step the next hops belong to (trace
        time: the values are tracers closed over by the fault math)."""
        if round is not None:
            self._round = round
        if step is not None:
            self._step = step

    def shift(self, x):
        return self._apply(x, self._inner.shift(x))

    def shift_back(self, x):
        return self._inner.shift_back(x)

    def fused_segment_sum(self, rot, contrib, local_dst, block, exact):
        return None  # force the separate hop so faults can inject

    def _apply(self, prev, shifted):
        import jax
        import jax.numpy as jnp

        sched = self.schedule
        if not sched.active:
            return shifted
        rnd = self._round if self._round is not None else jnp.int32(0)
        step = self._step if self._step is not None else jnp.int32(0)
        shard = jax.lax.axis_index(self.axis_name)
        kind = sched.kind_at(rnd, step, shard)
        out = jnp.where(kind == 1,
                        sched.corrupt_payload(shifted, rnd, step, shard),
                        shifted)
        out = jnp.where(kind == 2, jnp.zeros_like(shifted), out)
        return jnp.where(kind == 3, prev, out)


def record_faults(schedule: FaultSchedule, *, rounds: int, n_steps: int,
                  n_shards: int, round0: int = 0,
                  registry: Optional[telemetry.Registry] = None):
    """Count the faults a finished run's executed window actually hit
    into ``chaos_device_faults_total{kind}`` (host replay — the compiled
    loop carries no counter, and the replay is exact by construction).
    Returns the per-kind counts. The sharded fault-wired entries call
    this after every faulted run."""
    counts = schedule.counts_between(round0, round0 + rounds, n_steps,
                                     n_shards)
    ctr = _faults_counter(registry)
    total = 0
    for kind in FAULT_KINDS:
        if counts[kind]:
            ctr.labels(kind).inc(counts[kind])
            total += counts[kind]
    if total and spans.current_tracer() is not None:
        spans.emit("device_faults", round0=round0, rounds=rounds, **counts)
        # graftsight correlation: each fault SITE as its own point event
        # (round/step/shard/kind), bounded so a dense schedule cannot
        # flood the span store — the aggregate event above always
        # carries the exact totals.
        for rnd, step, shard, kind in schedule.sites_between(
                round0, round0 + rounds, n_steps, n_shards)[:64]:
            spans.emit("device_fault", round=rnd, step=step,
                       shard=shard, kind=kind)
    return counts


# ------------------------------------------------------- dispatch faults


class DispatchChaos:
    """One-shot dispatch faults at the engine/serve chunk boundary.

    ``preempt_at`` / ``wedge_at`` name 0-based dispatch indices (the
    process-wide count of gated dispatches while installed). When the
    gate reaches an armed index it raises :class:`ChipLost` /
    :class:`WedgedDispatch` BEFORE the dispatch touches any state —
    chunk-boundary damage — and disarms that index, so a healing retry
    of the same chunk runs clean. Install with
    :func:`install_dispatch_chaos`; injections count into
    ``chaos_device_faults_total{kind="preempt"|"wedge"}``."""

    def __init__(self, *, preempt_at=(), wedge_at=(),
                 registry: Optional[telemetry.Registry] = None):
        self._lock = concurrency.lock()
        self._preempt = {int(i) for i in preempt_at}
        self._wedge = {int(i) for i in wedge_at}
        self._dispatches = 0
        self._ctr = _faults_counter(registry)

    @property
    def dispatches(self) -> int:
        with self._lock:
            return self._dispatches

    def on_dispatch(self, loop: str) -> None:
        kind = None
        with self._lock:
            n = self._dispatches
            self._dispatches += 1
            if n in self._preempt:
                self._preempt.discard(n)
                kind = "preempt"
            elif n in self._wedge:
                self._wedge.discard(n)
                kind = "wedge"
        if kind is None:
            return
        self._ctr.labels(kind).inc()
        if spans.current_tracer() is not None:
            spans.emit("dispatch_fault", kind=kind, loop=loop, index=n)
        if kind == "preempt":
            raise ChipLost(n)
        raise WedgedDispatch(n)


#: The installed dispatch-fault injector (None = off; the gate is one
#: attribute read + None check — the spans.install_tracer pattern).
_dispatch_chaos: Optional[DispatchChaos] = None


def install_dispatch_chaos(dc: Optional[DispatchChaos]):
    """Install (or clear, with None) the process-wide dispatch-fault
    injector; returns the previous one so tests can restore it."""
    global _dispatch_chaos
    prev = _dispatch_chaos
    _dispatch_chaos = dc
    return prev


def dispatch_gate(loop: str) -> None:
    """The engines' chunk-dispatch hook: raise the armed fault, if any.
    No-op (one None check) when nothing is installed."""
    dc = _dispatch_chaos
    if dc is not None:
        dc.on_dispatch(loop)

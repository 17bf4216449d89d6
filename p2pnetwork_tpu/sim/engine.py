"""Round engine: compiled protocol execution.

The reference's runtime is its thread-and-poll loops (SURVEY.md section 1
"concurrency model"); the sim backend's runtime is this module — ``lax.scan``
over protocol rounds, compiled once, with per-round stats as device-side
reductions, plus a ``lax.while_loop`` variant for run-to-coverage with no
host round-trips (the north-star benchmark loop).

The resume entry points (``run_from`` / ``run_until_coverage_from`` /
``run_until_converged``) DONATE the state carry by default — the caller's
buffers alias the loop's instead of double-buffering in HBM, and are
invalidated (``donate=False`` opts out; see ``run_from``). Protocols that
expose a ``frontier_occupancy`` stat (the flood family) get its per-run
mean packed into the summary and recorded into the
``sim_frontier_occupancy`` histogram.

The BATCHED message plane rides the same loop discipline at B messages
per program: :func:`run_batch_until_coverage` advances a lane-packed
:class:`~p2pnetwork_tpu.models.messagebatch.MessageBatch` (32 concurrent
broadcast states per uint32 word — models/messagebatch.py) with one
donated-carry ``lax.while_loop``, per-message completion detection via
lane-masked popcounts against per-message coverage targets, completed
lanes frozen out of the batch frontier, and the whole per-lane summary
back in ONE packed transfer. Staggered admission happens BETWEEN calls
through ``BatchFlood.admit`` — the serving front-end's seam. Per-batch
occupancy and completion land in the ``sim_batch_active_lanes`` gauge
and ``sim_batch_completion_rounds`` histogram.

The QUERY plane generalizes the batch loop past boolean floods:
:func:`run_queries_until_done` advances a
:class:`~p2pnetwork_tpu.models.querybatch.QueryBatch` of K non-boolean
query lanes (min-plus route lookups, DHT successor chases, push-sum
aggregations — f32/i32 carriers budgeted BY BYTES via
``ops/lanes.lane_budget``) with the same donated-carry discipline,
per-lane freeze, and a packed summary that additionally carries every
lane's ANSWER back in the one transfer.

graftscope rides the resume/batch/query loops: ``recorder=`` on
:func:`run_from`, :func:`run_until_coverage_from`,
:func:`run_batch_until_coverage` and :func:`run_queries_until_done` (a
:class:`~p2pnetwork_tpu.sim.flightrec.FlightRecorder`) accumulates a
bounded per-round record ring INSIDE the compiled carry — donated like
the state, bit-identical results, one extra fetch per run — and, when a
trace plane is installed (telemetry/spans.py), batched runs emit
``batch_run`` spans with per-lane lifecycle events. Each run-to-* call
splits into a ``run_dispatch`` span (entry until the jitted loop call
returns: stats check, retrace, lowering, compile or cache load, launch;
``sim_dispatch_seconds_total{loop}``) and a ``run_summary`` span (the
one blocking transfer; ``sim_transfer_seconds_total``), both on the
profiler timeline too. Run summaries also
sample the default history ring (telemetry/history.py) so ``/history``
serves per-run gauge series with zero extra wiring.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from p2pnetwork_tpu import concurrency, telemetry
from p2pnetwork_tpu.chaos import device as chaos_device
from p2pnetwork_tpu.ops import bitset
from p2pnetwork_tpu.sim import flightrec
from p2pnetwork_tpu.sim.graph import Graph
from p2pnetwork_tpu.telemetry import history, jaxhooks, spans
from p2pnetwork_tpu.utils import accum

# Compile/recompile accounting rides jax.monitoring's lowering-duration
# events into the default registry (jax_compiles_total /
# jax_compile_seconds_total{stage}) — a run-to-* loop whose shapes churn
# shows up as a climbing compile count, not just mysterious wall time.
jaxhooks.install()


#: Occupancy is a fraction of live nodes in [0, 1]; geometric buckets from
#: ~0.1% up resolve the sparse tail where the frontier fast path pays off.
_OCCUPANCY_BUCKETS = telemetry.exponential_buckets(1 / 1024, 2.0, 11)
#: Cardinality bound for sim_frontier_occupancy's (loop, protocol) children
#: — a sweep over many protocol configs must not grow the family without
#: limit (the per-peer-gauge pruning rule, telemetry/registry.py).
_OCCUPANCY_MAX_CHILDREN = 16
#: Recency order of observed (loop, protocol) pairs — pruning evicts the
#: LEAST-RECENTLY-observed child, not the oldest-registered: a histogram
#: is cumulative, and a long-lived protocol's history must not be zeroed
#: because 16 one-shot sweep configs registered after it. Guarded by its
#: own lock: run summaries bridge from whatever thread finished the run
#: (several JaxSimNodes in one process), and the registry's internal
#: locking does not cover this side-table.
_occupancy_recency: dict = {}
_occupancy_lock = concurrency.lock()


def _observe_occupancy(loop: str, protocol_name: str, value: float) -> None:
    """Record one run's mean per-round frontier occupancy, pruning the
    least-recently-observed labeled children past the cardinality bound."""
    hist = telemetry.default_registry().histogram(
        "sim_frontier_occupancy",
        "Mean per-round frontier occupancy (active fraction of live nodes) "
        "per run-to-* invocation.",
        ("loop", "protocol"), buckets=_OCCUPANCY_BUCKETS)
    key = (loop, protocol_name)
    with _occupancy_lock:
        # observe INSIDE the lock: outside it, a concurrent prune at the
        # bound could evict this child between observe and re-insert,
        # dropping the sample just recorded.
        hist.labels(*key).observe(value)  # graftlint: ignore[lock-open-call] -- must be atomic with the recency re-insert (comment above); metric locks never take this one
        _occupancy_recency.pop(key, None)
        _occupancy_recency[key] = None  # re-insert = move to most-recent
        # Drop recency entries for children gone from the (possibly
        # swapped) registry, then evict the coldest down to the bound.
        live = {c.labels for c in hist.children()}  # graftlint: ignore[lock-open-call] -- same atomicity; children() is a leaf lock
        for stale in [k for k in _occupancy_recency if k not in live]:
            del _occupancy_recency[stale]
        while len(_occupancy_recency) > _OCCUPANCY_MAX_CHILDREN:
            coldest = next(iter(_occupancy_recency))
            del _occupancy_recency[coldest]
            hist.remove(*coldest)


def _record_run_summary(loop: str, wall_s: float, dispatch_s: float,
                        transfer_s: float, transfer_bytes: int, out: dict,
                        protocol_name: str = "") -> None:
    """Bridge one host-side run summary into the registry post-transfer.

    The compiled loops are pure device programs — the only host hooks are
    their entry and the packed-summary transfer, so that is where the
    telemetry plane observes the sim backend."""
    reg = telemetry.default_registry()
    reg.counter("sim_dispatch_seconds_total",
                "Host seconds from a run-to-* entry until its jitted loop "
                "call returned: stats check, retrace, lowering, compile or "
                "cache load, launch.", ("loop",)).labels(loop).inc(dispatch_s)
    reg.counter("sim_runs_total", "Completed run-to-* loop invocations.",
                ("loop",)).labels(loop).inc()
    reg.counter("sim_rounds_total", "Protocol rounds executed on device.",
                ("loop",)).labels(loop).inc(float(out["rounds"]))
    reg.counter("sim_messages_total",
                "Messages moved by protocol rounds (exact two-limb totals).",
                ("loop",)).labels(loop).inc(float(out["messages"]))
    reg.histogram("sim_run_seconds",
                  "Wall seconds per run-to-* invocation (dispatch through "
                  "summary transfer).", ("loop",)).labels(loop).observe(wall_s)
    reg.counter("sim_transfer_seconds_total",
                "Seconds blocked on device->host summary transfers (includes "
                "waiting out the device program on async backends)."
                ).inc(transfer_s)
    reg.counter("sim_transfer_bytes_total",
                "Bytes moved by device->host summary transfers."
                ).inc(transfer_bytes)
    if loop.startswith("coverage") and "coverage" in out:
        # (the converged loop reuses the packed f32 slot for its stat, so
        # its summary also carries a "coverage" key — not a coverage)
        reg.gauge("sim_last_coverage", "Coverage reached by the most recent "
                  "run-to-coverage loop.", ("loop",)).labels(loop).set(
                      float(out["coverage"]))
    if "frontier_occupancy_mean" in out:
        _observe_occupancy(loop, protocol_name,
                           float(out["frontier_occupancy_mean"]))


def _timed_summary(loop: str, t0: float, state, packed,
                   protocol_name: str = "", has_occupancy: bool = False,
                   ring=None):
    """Unpack the packed one-transfer summary, timing the transfer, and
    record the whole invocation into the registry. ``has_occupancy`` says
    whether the protocol's stats carried ``frontier_occupancy`` — only
    then does the packed fifth slot mean anything (it is zero-filled for
    protocols without the stat, which must not pollute the histogram).
    ``ring`` is the flight-recorder carry when the run recorded one —
    fetched in the SAME blocking ``device_get`` as the summary (still
    one sync point per run) and attached as ``out["flight_record"]``.
    ``t0`` opened the caller's ``run_dispatch`` span: ``t1 - t0`` is its
    dispatch, ``t2 - t1`` this ``run_summary`` span's transfer."""
    t1 = time.perf_counter()
    with spans.span("run_summary", loop=loop):
        nbytes = sum(int(getattr(leaf, "nbytes", 0))
                     for leaf in jax.tree_util.tree_leaves((packed, ring)))
        if ring is not None:
            packed, ring = jax.device_get((packed, ring))
        out = _unpack_summary(packed)
    extra = out.pop("extra", None)
    if has_occupancy and extra is not None:
        out["frontier_occupancy_mean"] = extra
    if ring is not None:
        out["flight_record"] = flightrec.trim(ring, out["rounds"])
    t2 = time.perf_counter()
    _record_run_summary(loop, t2 - t0, t1 - t0, t2 - t1, nbytes, out,
                        protocol_name)
    history.default_history().sample()
    return state, out


def _scan_rounds(graph: Graph, protocol, state, key: jax.Array, rounds: int,
                 ring=None):
    """The shared scan body of :func:`run` / :func:`run_from`. One body
    for the recording and non-recording forms (trace-time ``ring``
    branch, the ``_stat_while`` pattern) so the RNG chain and state math
    CANNOT diverge between them: ``ring`` (sim/flightrec.py) adds a
    per-round row write to the carry and a third return value."""

    def body(carry, round_key):
        st = carry[0]
        st, stats = protocol.step(graph, st, round_key)
        if ring is None:
            return (st,), stats
        _, rg, r, tot = carry
        msgs = jnp.float32(stats.get("messages", 0.0))
        tot = tot + msgs
        rg = flightrec.write_row(
            rg, r, occupancy=stats.get("frontier_occupancy", 0.0),
            new=msgs, total=tot, coverage=stats.get("coverage", 0.0),
            active_lanes=1, ici_bytes=0.0)
        return (st, rg, r + 1, tot), stats

    keys = jax.random.split(jax.random.fold_in(key, 1), rounds)
    init = (state,) if ring is None \
        else (state, ring, jnp.int32(0), jnp.float32(0.0))
    carry, stats = jax.lax.scan(body, init, keys)
    if ring is None:
        return carry[0], stats
    return carry[0], stats, carry[1]


@functools.partial(jax.jit, static_argnames=("protocol", "rounds"))
def run(graph: Graph, protocol, key: jax.Array, rounds: int):
    """Run ``rounds`` synchronous rounds from the protocol's initial state;
    returns (final_state, stacked stats).

    Stats come back as arrays of shape [rounds] per entry — the full
    per-round history of the device-side counters in one transfer.
    """
    return _scan_rounds(graph, protocol, protocol.init(graph, key), key,
                        rounds)


_run_from_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "rounds"),
    donate_argnames=("state",))(_scan_rounds)
_run_from_keeping = functools.partial(  # graftlint: ignore[carry-no-donate] -- the deliberate donate=False escape hatch (aliased-leaf states, double-resume); the donating twin is the default
    jax.jit, static_argnames=("protocol", "rounds"))(_scan_rounds)


def _scan_rounds_rec(graph: Graph, protocol, state, key: jax.Array,
                     rounds: int, ring: jax.Array):
    """The recording form of :func:`_scan_rounds` (same body — this
    wrapper only exists so the jit variants can name ``ring`` in
    ``donate_argnames``): the ring is a donated carry leaf of the
    donating variant, like the state."""
    return _scan_rounds(graph, protocol, state, key, rounds, ring)


_run_from_rec_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "rounds"),
    donate_argnames=("state", "ring"))(_scan_rounds_rec)
_run_from_rec_keeping = functools.partial(  # graftlint: ignore[carry-no-donate] -- same donate=False escape hatch as the non-recording twin
    jax.jit, static_argnames=("protocol", "rounds"))(_scan_rounds_rec)


def _donatable(state, *others) -> bool:
    """False when two leaves of ``state`` are the SAME array — XLA rejects
    donating one buffer twice, and protocol inits routinely alias (Flood's
    seed IS both ``seen`` and ``frontier``) — or when a state leaf is also
    a leaf of a NON-donated argument (LeaderElection's state carries
    ``graph.node_mask`` itself: `f(a, donate(a))` is equally rejected).
    Such states ride the non-donating path transparently; after one real
    step the leaves are distinct buffers and donation kicks in."""
    leaves = jax.tree_util.tree_leaves(state)
    ids = {id(leaf) for leaf in leaves}
    if len(ids) < len(leaves):
        return False
    other_ids = {id(leaf) for o in others
                 for leaf in jax.tree_util.tree_leaves(o)}
    return not (ids & other_ids)


def _check_not_donated(state) -> None:
    """Resuming from a state whose buffers a previous donating run already
    consumed surfaces, without this check, as an opaque XLA "Buffer has
    been deleted or donated" error from deep inside the dispatch. Detect
    deleted leaves up front and name the actual fix."""
    for leaf in jax.tree_util.tree_leaves(state):
        if (isinstance(leaf, jax.Array)
                and not isinstance(leaf, jax.core.Tracer)
                and leaf.is_deleted()):
            raise ValueError(
                "resume state has deleted device buffers — they were "
                "donated to a previous run_from / run_until_coverage_from "
                "/ run_until_converged call (donate=True is the default). "
                "To resume the same state more than once pass "
                "donate=False to the earlier call, or reload the state "
                "from a checkpoint."
            )


def _pick_loop(donating, keeping, donate, state, graph, key):
    """The one donation gate all three resume entry points share: the
    donating jit variant only when asked AND the state's buffers are
    cleanly donatable against the non-donated args."""
    _check_not_donated(state)
    return donating if donate and _donatable(state, graph, key) else keeping


def run_from(graph: Graph, protocol, state, key: jax.Array, rounds: int, *,
             donate: bool = True, recorder=None):
    """Run ``rounds`` rounds continuing from an existing ``state`` (resume
    path — e.g. after loading a checkpoint, or incremental stepping from
    JaxSimNode).

    ``donate=True`` (the default) donates the ``state`` buffers to the
    compiled loop: the caller's copy stops double-buffering in HBM
    alongside the loop carry — at 10M nodes that is tens of MB per
    predicate — and is INVALIDATED (reading the passed-in state
    afterwards raises). Pass ``donate=False`` to keep it (e.g. to resume
    the same state twice), and checkpoint a pre-run state BEFORE the
    donating call — ``sim/checkpoint.py`` copies to host at save time,
    so save-then-run is safe, run-then-save-the-old-state is not. A
    state whose leaves alias one buffer (fresh protocol inits do) skips
    donation automatically rather than trip XLA's double-donate check.

    ``recorder`` (a :class:`~p2pnetwork_tpu.sim.flightrec.FlightRecorder`,
    default off) accumulates the per-round flight ring inside the scan
    carry — results stay bit-identical — and changes the return to
    ``(state, stats, FlightRecord)`` (the record fetch is the one extra
    sync the recorder adds, at the END of the run).
    """
    # graftquake chunk-dispatch gate (see run_until_coverage_from).
    chaos_device.dispatch_gate("engine-rounds")
    if recorder is None:
        fn = _pick_loop(_run_from_donating, _run_from_keeping, donate,
                        state, graph, key)
        return fn(graph, protocol, state, key, rounds)
    fn = _pick_loop(_run_from_rec_donating, _run_from_rec_keeping, donate,
                    state, graph, key)
    state, stats, ring = fn(graph, protocol, state, key, rounds,
                            recorder.init())
    return state, stats, flightrec.trim(np.asarray(ring), rounds)


def run_until_coverage(
    graph: Graph,
    protocol,
    key: jax.Array,
    *,
    coverage_target: float = 0.99,
    max_rounds: int = 1024,
    steps_per_round: int = 1,
):
    """Run until ``stats['coverage'] >= coverage_target`` (or max_rounds).

    Device-side early exit via ``lax.while_loop`` — the whole
    run-to-99%-coverage measurement executes as one XLA program (init
    included) with zero host synchronization per round. Returns
    (final_state, dict with ``rounds``, ``coverage``, ``messages`` totals;
    ``messages`` is an exact Python int — see
    :func:`run_until_coverage_from`).

    Requires the protocol's stats to include ``coverage`` and ``messages``
    (e.g. models.flood.Flood). Protocols that also expose
    ``frontier_occupancy`` (the flood family) get its per-run mean back as
    ``frontier_occupancy_mean`` and recorded into the
    ``sim_frontier_occupancy`` histogram.
    """
    t0 = time.perf_counter()
    with spans.span("run_dispatch", loop="coverage"):
        keys = _require_stats(graph, protocol, None, key,
                              ("coverage", "messages"))
        state, packed = _coverage_with_init(
            graph, protocol, key,
            coverage_target=coverage_target, max_rounds=max_rounds,
            steps_per_round=steps_per_round,
        )
    return _timed_summary("coverage", t0, state, packed,
                          type(protocol).__name__,
                          "frontier_occupancy" in keys)


def run_until_coverage_from(
    graph: Graph,
    protocol,
    state0,
    key: jax.Array,
    *,
    coverage_target: float = 0.99,
    max_rounds: int = 1024,
    steps_per_round: int = 1,
    donate: bool = True,
    recorder=None,
):
    """Run-to-coverage continuing from an existing ``state0`` (resume path).

    If the protocol exposes ``coverage(graph, state)`` (Flood, SIR do), the
    loop starts from the true coverage of ``state0`` — resuming an
    already-finished run executes zero rounds instead of one spurious one.

    ``messages`` in the returned dict is an exact Python int: the loop
    accumulates device-side in a two-limb (hi, lo) counter (utils/accum.py)
    so totals past 2^31 — routine at 10M-node scale — do not wrap int32.
    The whole summary (rounds, coverage, both limbs) comes back in ONE
    packed transfer.

    ``donate=True`` (default) hands ``state0``'s buffers to the loop and
    invalidates the caller's copy (see :func:`run_from` for the full
    donation contract); pass ``donate=False`` to resume the same state
    more than once.

    ``recorder`` (a :class:`~p2pnetwork_tpu.sim.flightrec.FlightRecorder`,
    default off) rides the per-round flight ring in the while carry
    (donated alongside the state) and attaches the host-side
    :class:`~p2pnetwork_tpu.sim.flightrec.FlightRecord` as
    ``out["flight_record"]`` — run results stay bit-identical to
    recorder-off runs, still with zero per-round host sync.
    """
    # graftquake chunk-dispatch gate: an armed DispatchChaos fault
    # (chip preemption / wedged dispatch) fires HERE, before any buffer
    # is touched — one attribute read + None check when nothing is
    # installed (chaos/device.py).
    chaos_device.dispatch_gate("engine-coverage")
    t0 = time.perf_counter()
    with spans.span("run_dispatch", loop="coverage_from"):
        keys = _require_stats(graph, protocol, state0, key,
                              ("coverage", "messages"))
        if recorder is None:
            loop_fn = _pick_loop(_coverage_loop_donating,
                                 _coverage_loop_keeping, donate, state0,
                                 graph, key)
            state, packed = loop_fn(
                graph, protocol, state0, key,
                coverage_target=coverage_target, max_rounds=max_rounds,
                steps_per_round=steps_per_round,
            )
            ring = None
        else:
            loop_fn = _pick_loop(_coverage_loop_rec_donating,
                                 _coverage_loop_rec_keeping, donate, state0,
                                 graph, key)
            state, packed, ring = loop_fn(
                graph, protocol, state0, key, recorder.init(),
                coverage_target=coverage_target, max_rounds=max_rounds,
                steps_per_round=steps_per_round,
            )
    return _timed_summary("coverage_from", t0, state, packed,
                          type(protocol).__name__,
                          "frontier_occupancy" in keys, ring=ring)


# One-transfer run summaries, shared with the sharded coverage loops.
_pack_summary = accum.pack_summary
_unpack_summary = accum.unpack_summary


def run_until_converged(
    graph: Graph,
    protocol,
    key: jax.Array,
    *,
    stat: str,
    threshold: float,
    max_rounds: int = 1024,
    state0=None,
    steps_per_round: int = 1,
    donate: bool = True,
):
    """Run until the scalar ``stats[stat]`` drops BELOW ``threshold`` — the
    run-to-coverage loop's sibling for convergence-style protocols
    (PageRank to a residual, PushSum/Gossip to a variance), as one
    device-side ``lax.while_loop`` with the packed single-transfer summary.

    Returns ``(state, dict(rounds, value, messages))`` where ``value`` is
    the stat after the final round (inf if zero rounds ran) and
    ``messages`` an exact Python int. Pass ``state0`` to resume.

    Thresholds have an f32 floor: an L1 residual summed over N ranks
    bottoms out around N * eps * scale (measured ~1.4e-8 at 50K nodes), so
    an unreachable threshold runs to ``max_rounds`` — size it to the
    population, or watch ``value`` in the summary.

    ``donate=True`` (default) hands a non-None ``state0``'s buffers to the
    loop and invalidates the caller's copy (see :func:`run_from`)."""
    t0 = time.perf_counter()
    with spans.span("run_dispatch", loop="converged"):
        keys = _require_stats(graph, protocol, state0, key,
                              (stat, "messages"))
        loop_fn = _pick_loop(_converged_loop_donating,
                             _converged_loop_keeping, donate, state0, graph,
                             key)
        state, packed = loop_fn(
            graph, protocol, state0, key, stat=stat, threshold=threshold,
            max_rounds=max_rounds, steps_per_round=steps_per_round,
        )
    state, out = _timed_summary("converged", t0, state, packed,
                                type(protocol).__name__,
                                "frontier_occupancy" in keys)
    out["value"] = out.pop("coverage")  # pack_summary's f32 slot, reused
    return state, out


def _converged_loop(graph, protocol, state0, key, *, stat, threshold,
                    max_rounds, steps_per_round=1):
    if state0 is None:
        state0 = protocol.init(graph, key)
    return _stat_while(
        graph, protocol, state0, key, stat=stat,
        keep_going=lambda v, r: (v >= threshold) & (r < max_rounds),
        value0=jnp.float32(jnp.inf), steps_per_round=steps_per_round,
    )


_converged_loop_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "stat", "max_rounds",
                              "steps_per_round"),
    donate_argnames=("state0",))(_converged_loop)
_converged_loop_keeping = functools.partial(
    jax.jit, static_argnames=("protocol", "stat", "max_rounds",
                              "steps_per_round"))(_converged_loop)


# ------------------------------------------------------------- batch plane

#: Completion-rounds buckets: floods finish in O(diameter) rounds, so
#: geometric 1..2048 resolves both small-world (~10) and chain-like tails.
_COMPLETION_BUCKETS = telemetry.exponential_buckets(1.0, 2.0, 12)


def _add_words(acc, words: jax.Array):
    """Fold per-word uint32 subtotals into the two-limb accumulator —
    each subtotal is < 2^32 by the ``messages_words`` contract
    (models/messagebatch.py), so ``accum.add``'s single-carry invariant
    holds per fold. W is tens at most; a fori_loop keeps it carry-exact
    without widening anything."""
    return jax.lax.fori_loop(
        0, words.shape[0], lambda i, a: accum.add(a, words[i]), acc)


def _batch_body(graph, protocol, batch0, key, *, max_rounds, ring=None):
    """The batched run-to-coverage loop: advance every running lane per
    iteration until ALL admitted lanes complete (or ``max_rounds`` more
    global rounds pass). Per-lane completion/round accounting lives in
    the protocol's step (lane-masked popcounts vs per-lane targets);
    this loop only asks "is anything still running" — one i32 reduction
    per round, no host sync. Callers must hand in a REFRESHED batch
    (protocol.refresh — run_batch_until_coverage does): refreshing
    inside this jit would dead-code the stale seen_count input and
    silently drop its donation.

    One body for the recording and non-recording forms (trace-time
    ``ring`` branch, the ``_stat_while`` pattern) so the RNG chain and
    accumulation math CANNOT diverge between them. A ring row per
    global round: union-frontier occupancy, this round's aggregate
    sends, the running total, the masked seen-count sum over lanes (the
    batch plane's coverage numerator), and the active-lane count."""

    def cond(carry):
        batch, r = carry[0], carry[2]
        return jnp.any(batch.admitted & ~batch.done) & (r < max_rounds)

    def body(carry):
        batch, k, r, hi, lo, occ = carry[:6]
        k, sub = jax.random.split(k)
        batch, stats = protocol.step(graph, batch, sub)
        hi, lo = _add_words((hi, lo), stats["messages_words"])
        out = (batch, k, r + 1, hi, lo,
               occ + jnp.float32(stats["batch_occupancy"]))
        if ring is None:
            return out
        return out + (flightrec.write_row(
            carry[6], r,
            occupancy=stats["batch_occupancy"],
            new=jnp.sum(stats["messages_words"].astype(jnp.float32)),
            total=flightrec.total_f32(hi, lo),
            coverage=jnp.sum(batch.seen_count.astype(jnp.float32)),
            active_lanes=stats["active_lanes"],
            ici_bytes=0.0),)

    init = (batch0, key, jnp.int32(0), *accum.zero(), jnp.float32(0.0))
    if ring is not None:
        init = init + (ring,)
    final = jax.lax.while_loop(cond, body, init)
    batch, _, rounds, hi, lo, occ = final[:6]
    packed = accum.pack_batch_summary(
        rounds,
        jnp.sum((batch.admitted & ~batch.done).astype(jnp.int32)),
        jnp.sum(batch.done.astype(jnp.int32)),
        (hi, lo),
        occ / jnp.maximum(rounds, 1),
        bitset.pack_bits(batch.done),
        batch.rounds,
    )
    if ring is None:
        return batch, packed
    return batch, packed, final[6]


def _batch_loop(graph, protocol, batch0, key, *, max_rounds):
    return _batch_body(graph, protocol, batch0, key, max_rounds=max_rounds)


_batch_loop_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "max_rounds"),
    donate_argnames=("batch0",))(_batch_loop)
_batch_loop_keeping = functools.partial(  # graftlint: ignore[carry-no-donate] -- the deliberate donate=False escape hatch, same as the single-message twins
    jax.jit, static_argnames=("protocol", "max_rounds"))(_batch_loop)


def _batch_loop_rec(graph, protocol, batch0, key, ring, *, max_rounds):
    """The recording form of :func:`_batch_body` (this wrapper only
    exists so the jit variants can name ``ring`` in
    ``donate_argnames``) — same RNG chain and state math by
    construction, so per-lane results stay bit-identical."""
    return _batch_body(graph, protocol, batch0, key, max_rounds=max_rounds,
                       ring=ring)


_batch_loop_rec_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "max_rounds"),
    donate_argnames=("batch0", "ring"))(_batch_loop_rec)
_batch_loop_rec_keeping = functools.partial(  # graftlint: ignore[carry-no-donate] -- same donate=False escape hatch as the non-recording twin
    jax.jit, static_argnames=("protocol", "max_rounds"))(_batch_loop_rec)


def _record_batch_summary(wall_s: float, dispatch_s: float,
                          transfer_s: float, transfer_bytes: int, out: dict,
                          newly_done_rounds, protocol_name: str) -> None:
    """Bridge one batched run summary into the registry: the shared
    sim_* run counters under ``loop="batch"`` plus the batch plane's own
    gauges — ``sim_batch_active_lanes`` (lanes still running when the
    loop returned: >0 means max_rounds cut stragglers off) and one
    ``sim_batch_completion_rounds`` observation per lane that COMPLETED
    in this call (lanes finished in an earlier call must not re-observe
    on resume)."""
    # The shared sim_* run counters register through the one site that
    # owns their names/help/labels (loop="batch" has no "coverage" key,
    # so the coverage gauge and occupancy branches there stay idle).
    _record_run_summary("batch", wall_s, dispatch_s, transfer_s,
                        transfer_bytes, out, protocol_name)
    reg = telemetry.default_registry()
    reg.gauge("sim_batch_active_lanes",
              "Lanes still running (admitted, not at target) when the last "
              "batched loop returned — nonzero means max_rounds froze "
              "stragglers.").set(float(out["active_lanes"]))
    hist = reg.histogram(
        "sim_batch_completion_rounds",
        "Rounds each batched message took to reach its coverage target "
        "(one observation per lane completed in a "
        "run_batch_until_coverage call).", buckets=_COMPLETION_BUCKETS)
    for r in newly_done_rounds.tolist():  # host ints (numpy, post-unpack)
        hist.observe(r)
    _observe_occupancy("batch", protocol_name,
                       float(out["occupancy_mean"]))
    # One history-ring sample per batched run, taken AFTER the batch
    # gauges are set so /history's sim_batch_active_lanes series tracks
    # run boundaries (telemetry/history.py).
    history.default_history().sample()


def _emit_batch_entry_events(admitted0, done0, rounds0) -> None:
    """Per-lane lifecycle events at batch-run entry (trace plane,
    telemetry/spans.py): ``lane_admit`` for lanes this run advances for
    the first time, ``lane_resume`` for lanes resuming from an earlier
    call. No-ops unless a tracer is installed (the callers gate)."""
    running = admitted0 & ~done0
    for lane in np.flatnonzero(running & (rounds0 == 0)).tolist():
        spans.emit("lane_admit", lane=lane)
    for lane in np.flatnonzero(running & (rounds0 > 0)).tolist():
        spans.emit("lane_resume", lane=lane)


def _emit_batch_exit_events(admitted0, done0, out) -> None:
    """Per-lane lifecycle events at batch-run exit: ``lane_complete``
    for lanes that reached target in this call (with their cumulative
    round count), ``lane_freeze`` for running lanes the loop returned
    still unfinished (max_rounds cut the stragglers off)."""
    lane_done = out["lane_done"]
    newly = np.flatnonzero(lane_done & ~done0)
    rounds = out["lane_rounds"][newly]
    for lane, r in zip(newly.tolist(), rounds.tolist()):
        spans.emit("lane_complete", lane=lane, rounds=r)
    frozen = np.flatnonzero(admitted0 & ~done0 & ~lane_done)
    for lane in frozen.tolist():
        spans.emit("lane_freeze", lane=lane)


def run_batch_until_coverage(graph: Graph, protocol, batch, key: jax.Array,
                             *, max_rounds: int = 1024,
                             donate: bool = True, recorder=None):
    """Advance ALL in-flight messages of a lane-packed batch until every
    admitted lane reaches its coverage target (or ``max_rounds`` global
    rounds pass) — the B-message sibling of
    :func:`run_until_coverage_from`, one compiled program per call.

    ``protocol`` is a batched protocol (models/messagebatch.BatchFlood):
    ``step(graph, batch, key) -> (batch, stats)`` with per-lane
    completion folded into the state and ``stats`` carrying
    ``messages_words`` / ``batch_occupancy`` / ``active_lanes``.
    Completed lanes freeze (masked out of the batch frontier), so
    stragglers do not pay for finished messages; admission of NEW
    messages into open lanes happens between calls via
    ``protocol.admit`` — the serving front-end's seam.

    Returns ``(batch, out)`` where ``out`` carries the aggregates
    (``rounds`` global rounds this call, exact ``messages``,
    ``active_lanes``, ``completed``, ``occupancy_mean``) plus per-lane
    vectors (``lane_done`` bool[B], ``lane_rounds`` i32[B] — TOTAL steps
    applied per lane, resume-cumulative) and, when any lane completed in
    this call, ``completion_rounds_p50`` / ``completion_rounds_p99`` over
    those lanes — the serving-SLO numbers the bench publishes. The whole
    summary is ONE packed device->host transfer however large B is.

    ``donate=True`` (default) hands the batch's buffers to the loop and
    invalidates the caller's copy (see :func:`run_from`); pass
    ``donate=False`` to keep reading the pre-run batch (e.g. to resume
    it twice).

    ``recorder`` (a :class:`~p2pnetwork_tpu.sim.flightrec.FlightRecorder`,
    default off) rides the per-round flight ring in the donated carry
    and attaches ``out["flight_record"]`` — per-lane results stay
    bit-identical to recorder-off runs. When a trace plane is installed
    (telemetry/spans.py), the whole call runs under a ``batch_run`` span
    carrying per-lane ``lane_admit`` / ``lane_resume`` /
    ``lane_complete`` / ``lane_freeze`` events."""
    # graftquake chunk-dispatch gate (see run_until_coverage_from): an
    # armed fault raises before the batch is read, so a healing retry
    # re-dispatches an intact carry.
    chaos_device.dispatch_gate("engine-batch")
    t0 = time.perf_counter()
    _check_not_donated(batch)  # friendly error before refresh reads it
    # Pre-run done flags, snapshotted BEFORE the refresh: a lane the
    # refresh itself completes (failures between calls moved its target)
    # completed in THIS call and must observe into the completion
    # histogram/percentiles like any other (and the copy must precede
    # the loop consuming the donated buffers anyway).
    done0 = np.asarray(batch.done)
    tracer = spans.current_tracer()
    # Lane lifecycle snapshot for the trace plane, read pre-refresh
    # (refresh-completed lanes still count as completing in this run).
    admitted0 = np.asarray(batch.admitted) if tracer is not None else None
    rounds0 = np.asarray(batch.rounds) if tracer is not None else None
    with spans.span("batch_run", loop="engine", max_rounds=max_rounds):
        if tracer is not None:
            _emit_batch_entry_events(admitted0, done0, rounds0)
        # Entry-time mask refresh — the batched cov0 seeding: node
        # failures applied between calls change the masked
        # numerator/denominator, so lanes re-decide "already done"
        # against the CURRENT graph before any step runs. Eager on
        # purpose (see BatchFlood.refresh).
        batch = protocol.refresh(graph, batch)
        n_words = int(batch.seen.shape[0])
        if recorder is None:
            loop_fn = _pick_loop(_batch_loop_donating, _batch_loop_keeping,
                                 donate, batch, graph, key)
            state, packed = loop_fn(graph, protocol, batch, key,
                                    max_rounds=max_rounds)
            ring = None
        else:
            loop_fn = _pick_loop(_batch_loop_rec_donating,
                                 _batch_loop_rec_keeping, donate, batch,
                                 graph, key)
            state, packed, ring = loop_fn(graph, protocol, batch, key,
                                          recorder.init(),
                                          max_rounds=max_rounds)
        t1 = time.perf_counter()
        nbytes = sum(int(getattr(leaf, "nbytes", 0))
                     for leaf in jax.tree_util.tree_leaves((packed, ring)))
        if ring is not None:
            packed, ring = jax.device_get((packed, ring))
        out = accum.unpack_batch_summary(packed, n_words)
        if ring is not None:
            out["flight_record"] = flightrec.trim(ring, out["rounds"])
        t2 = time.perf_counter()
        newly = out["lane_done"] & ~done0
        # Which lanes completed in THIS call (pre-run done excluded) —
        # the serving front-end's harvest set: map these back to tickets
        # without re-deriving done-flag deltas caller-side.
        out["newly_completed_lanes"] = np.flatnonzero(newly).astype(np.int32)
        newly_rounds = out["lane_rounds"][newly]
        if newly_rounds.size:
            out["completion_rounds_p50"] = float(
                np.percentile(newly_rounds, 50))
            out["completion_rounds_p99"] = float(
                np.percentile(newly_rounds, 99))
        if tracer is not None:
            _emit_batch_exit_events(admitted0, done0, out)
            # graftsight: one summary point per chunk inside the
            # batch_run span — the engine-side join key for the serve
            # driver's per-ticket ticket_chunk replay (serve/service.py
            # correlates by tick; this carries the chunk's aggregates).
            spans.emit("batch_summary",
                       rounds=int(out["rounds"]),
                       completed=int(out["completed"]),
                       active_lanes=int(out["active_lanes"]),
                       newly_completed=int(
                           out["newly_completed_lanes"].size))
        _record_batch_summary(t2 - t0, t1 - t0, t2 - t1, nbytes, out,
                              newly_rounds, type(protocol).__name__)
    return state, out


# ------------------------------------------------------------- query plane


def _query_body(graph, protocol, qb0, key, *, max_rounds, ring=None):
    """The batched query loop: advance every running lane of a
    :class:`~p2pnetwork_tpu.models.querybatch.QueryBatch` per iteration
    until ALL admitted queries settle (or ``max_rounds`` more global
    rounds pass) — ``_batch_body``'s sibling for the non-boolean lane
    families (min-plus routing, DHT chases, push-sum). Per-lane
    completion/round accounting lives in the family's step; this loop
    only asks "is anything still running" and folds the per-round send
    subtotal into the exact two-limb counter. The packed summary adds
    the query plane's per-lane ANSWERS (``protocol.lane_values``) to the
    batch plane's per-lane tail — one transfer for the whole K-query
    result set. Callers hand in a REFRESHED batch (the entry point
    does); ``ring`` is the flight-recorder carry (one row per global
    round, same single-body discipline as the other loops)."""
    capacity = int(qb0.admitted.shape[0])

    def cond(carry):
        qb, r = carry[0], carry[2]
        return jnp.any(qb.admitted & ~qb.done) & (r < max_rounds)

    def body(carry):
        qb, k, r, hi, lo, occ = carry[:6]
        k, sub = jax.random.split(k)
        qb, stats = protocol.step(graph, qb, sub)
        hi, lo = accum.add((hi, lo), stats["messages"])
        active = jnp.sum((qb.admitted & ~qb.done).astype(jnp.int32))
        # Lane occupancy — the query plane's "how full is the batch"
        # analog of frontier occupancy: running lanes / capacity.
        occ_r = active.astype(jnp.float32) / capacity
        out = (qb, k, r + 1, hi, lo, occ + occ_r)
        if ring is None:
            return out
        return out + (flightrec.write_row(
            carry[6], r,
            occupancy=occ_r,
            new=stats["messages"],
            total=flightrec.total_f32(hi, lo),
            coverage=jnp.sum(qb.done.astype(jnp.int32)),
            active_lanes=active,
            ici_bytes=0.0),)

    init = (qb0, key, jnp.int32(0), *accum.zero(), jnp.float32(0.0))
    if ring is not None:
        init = init + (ring,)
    final = jax.lax.while_loop(cond, body, init)
    qb, _, rounds, hi, lo, occ = final[:6]
    packed = accum.pack_query_summary(
        rounds,
        jnp.sum((qb.admitted & ~qb.done).astype(jnp.int32)),
        jnp.sum(qb.done.astype(jnp.int32)),
        (hi, lo),
        occ / jnp.maximum(rounds, 1),
        bitset.pack_bits(qb.done),
        qb.rounds,
        protocol.lane_values(graph, qb),
        values_float=protocol.VALUES_FLOAT,
    )
    if ring is None:
        return qb, packed
    return qb, packed, final[6]


def _query_loop(graph, protocol, qb0, key, *, max_rounds):
    return _query_body(graph, protocol, qb0, key, max_rounds=max_rounds)


_query_loop_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "max_rounds"),
    donate_argnames=("qb0",))(_query_loop)
_query_loop_keeping = functools.partial(  # graftlint: ignore[carry-no-donate] -- the deliberate donate=False escape hatch, same as the batch twins
    jax.jit, static_argnames=("protocol", "max_rounds"))(_query_loop)


def _query_loop_rec(graph, protocol, qb0, key, ring, *, max_rounds):
    """The recording form of :func:`_query_body` (wrapper so the jit
    variants can name ``ring`` in ``donate_argnames``) — same RNG chain
    and state math by construction."""
    return _query_body(graph, protocol, qb0, key, max_rounds=max_rounds,
                       ring=ring)


_query_loop_rec_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "max_rounds"),
    donate_argnames=("qb0", "ring"))(_query_loop_rec)
_query_loop_rec_keeping = functools.partial(  # graftlint: ignore[carry-no-donate] -- same donate=False escape hatch as the non-recording twin
    jax.jit, static_argnames=("protocol", "max_rounds"))(_query_loop_rec)


def _record_query_summary(wall_s: float, dispatch_s: float,
                          transfer_s: float, transfer_bytes: int, out: dict,
                          newly_done_rounds, protocol_name: str) -> None:
    """Bridge one batched query-run summary into the registry: the
    shared sim_* run counters under ``loop="query"`` plus the query
    plane's own instruments — ``sim_query_active_lanes`` (queries still
    running at return: >0 means max_rounds froze stragglers) and one
    ``sim_query_completion_rounds`` observation per lane that settled
    in this call."""
    _record_run_summary("query", wall_s, dispatch_s, transfer_s,
                        transfer_bytes, out, protocol_name)
    reg = telemetry.default_registry()
    reg.gauge("sim_query_active_lanes",
              "Query lanes still running (admitted, not settled) when "
              "the last run_queries_until_done call returned — nonzero "
              "means max_rounds froze stragglers.").set(
                  float(out["active_lanes"]))
    hist = reg.histogram(
        "sim_query_completion_rounds",
        "Rounds each batched query took to settle (one observation per "
        "lane completed in a run_queries_until_done call).",
        buckets=_COMPLETION_BUCKETS)
    for r in newly_done_rounds.tolist():  # host ints (numpy, post-unpack)
        hist.observe(r)
    history.default_history().sample()


def run_queries_until_done(graph: Graph, protocol, batch, key: jax.Array,
                           *, max_rounds: int = 1024,
                           donate: bool = True, recorder=None):
    """Advance ALL in-flight queries of a lane-packed
    :class:`~p2pnetwork_tpu.models.querybatch.QueryBatch` until every
    admitted lane settles (or ``max_rounds`` global rounds pass) — the
    query-family sibling of :func:`run_batch_until_coverage`, one
    compiled program per call for K routing lookups / DHT chases /
    aggregations at once.

    ``protocol`` is a query family (models/querybatch.py
    ``MinPlusQueries`` / ``DhtLookups`` / ``PushSumQueries``):
    ``step(graph, batch, key) -> (batch, stats)`` with per-lane
    completion folded into the state, ``stats["messages"]`` the
    round's aggregate send subtotal (< 2^32 — budget ``K * E``), and
    ``lane_values(graph, batch)`` the per-lane answers. Completed lanes
    freeze; admission of new queries happens between calls via
    ``protocol.admit`` — the same serving seam as the flood plane.

    Returns ``(batch, out)``: aggregates (``rounds``, exact
    ``messages``, ``active_lanes``, ``completed``, ``occupancy_mean`` —
    mean running-lane fraction), per-lane vectors (``lane_done``,
    ``lane_rounds`` — resume-cumulative, ``lane_values`` — the ANSWERS,
    f32 or i32 per family, ``newly_completed_lanes``) and, when any lane
    settled this call, ``completion_rounds_p50``/``p99`` over those
    lanes. One packed device->host transfer however large K is.

    ``donate=True`` (default) hands the batch's buffers to the loop and
    invalidates the caller's copy (see :func:`run_from`). ``recorder``
    rides the per-round flight ring in the donated carry and attaches
    ``out["flight_record"]`` — per-lane results stay bit-identical to
    recorder-off runs. With a trace plane installed (telemetry/spans.py)
    the call runs under a ``query_run`` span with the same per-lane
    ``lane_admit`` / ``lane_resume`` / ``lane_complete`` /
    ``lane_freeze`` events as the batch plane."""
    t0 = time.perf_counter()
    _check_not_donated(batch)  # friendly error before refresh reads it
    done0 = np.asarray(batch.done)
    tracer = spans.current_tracer()
    admitted0 = np.asarray(batch.admitted) if tracer is not None else None
    rounds0 = np.asarray(batch.rounds) if tracer is not None else None
    with spans.span("query_run", loop="engine", max_rounds=max_rounds):
        if tracer is not None:
            _emit_batch_entry_events(admitted0, done0, rounds0)
        # Entry-time refresh — identity for today's families (their
        # completions latch; nothing is mask-derived), kept eager for
        # template parity with the batch plane: a future mask-derived
        # refresh inside the donated jit would dead-code its stale
        # input leaf and silently drop that donation (BatchFlood.refresh
        # documents the incident).
        batch = protocol.refresh(graph, batch)
        capacity = int(batch.admitted.shape[0])
        if recorder is None:
            loop_fn = _pick_loop(_query_loop_donating, _query_loop_keeping,
                                 donate, batch, graph, key)
            state, packed = loop_fn(graph, protocol, batch, key,
                                    max_rounds=max_rounds)
            ring = None
        else:
            loop_fn = _pick_loop(_query_loop_rec_donating,
                                 _query_loop_rec_keeping, donate, batch,
                                 graph, key)
            state, packed, ring = loop_fn(graph, protocol, batch, key,
                                          recorder.init(),
                                          max_rounds=max_rounds)
        t1 = time.perf_counter()
        nbytes = sum(int(getattr(leaf, "nbytes", 0))
                     for leaf in jax.tree_util.tree_leaves((packed, ring)))
        if ring is not None:
            packed, ring = jax.device_get((packed, ring))
        out = accum.unpack_query_summary(
            packed, capacity, values_float=protocol.VALUES_FLOAT)
        if ring is not None:
            out["flight_record"] = flightrec.trim(ring, out["rounds"])
        t2 = time.perf_counter()
        newly = out["lane_done"] & ~done0
        out["newly_completed_lanes"] = np.flatnonzero(newly).astype(np.int32)
        newly_rounds = out["lane_rounds"][newly]
        if newly_rounds.size:
            out["completion_rounds_p50"] = float(
                np.percentile(newly_rounds, 50))
            out["completion_rounds_p99"] = float(
                np.percentile(newly_rounds, 99))
        if tracer is not None:
            _emit_batch_exit_events(admitted0, done0, out)
        _record_query_summary(t2 - t0, t1 - t0, t2 - t1, nbytes, out,
                              newly_rounds, type(protocol).__name__)
    return state, out


def donating_carry_loops() -> dict:
    """The donating state-carry loops, by name — the exact jitted objects
    the resume entry points dispatch, exposed as a stable seam for
    graftaudit's donation audit (analysis/ir/donation.py: the compiled
    ``input_output_alias`` must cover every carry leaf). Keyed by name so
    a renamed or removed loop fails the audit loudly instead of leaving
    the aliasing gate silently pointed at nothing."""
    return {
        "run_from": _run_from_donating,
        "coverage_from": _coverage_loop_donating,
        "converged_from": _converged_loop_donating,
        "batch_from": _batch_loop_donating,
        "query_from": _query_loop_donating,
        # The flight-recorder twins: the ring is an extra donated carry
        # leaf, and the audit must prove it stays aliased (a recorder
        # that double-buffers its ring would silently tax every
        # recorded run).
        "run_from_rec": _run_from_rec_donating,
        "coverage_from_rec": _coverage_loop_rec_donating,
        "batch_from_rec": _batch_loop_rec_donating,
        "query_from_rec": _query_loop_rec_donating,
    }


#: Memoized stats-key sets per (protocol, graph structure) — the abstract
#: trace of init+step runs once, not per call (the run-to-* entry points
#: sit on paths budgeted in milliseconds). FIFO-bounded: a sweep over many
#: protocol configs must not grow it without limit or pin every protocol
#: instance alive (ADVICE r3).
_stats_keys_cache: dict = {}
_STATS_KEYS_CACHE_MAX = 128


def _require_stats(graph, protocol, state0, key, required):
    """Check the protocol's stats dict exposes ``required`` keys, by
    abstract tracing (no device work) — a typo'd or missing stat must be a
    clear ValueError up front, not a KeyError from inside the jitted
    loop. Returns the full stats-key frozenset so callers can sniff
    OPTIONAL stats (``frontier_occupancy``) off the same cached trace."""
    cache_key = (protocol, jax.tree_util.tree_structure(graph))
    keys = _stats_keys_cache.get(cache_key)
    if keys is None:
        shapes = jax.eval_shape(
            lambda g, k, s0: protocol.step(
                g, protocol.init(g, k) if s0 is None else s0, k
            )[1],
            graph, key, state0,
        )
        if len(_stats_keys_cache) >= _STATS_KEYS_CACHE_MAX:
            _stats_keys_cache.pop(next(iter(_stats_keys_cache)))
        keys = _stats_keys_cache[cache_key] = frozenset(shapes)
    missing = [r for r in required if r not in keys]
    if missing:
        raise ValueError(
            f"{type(protocol).__name__} exposes stats {sorted(keys)}; "
            f"this loop needs {sorted(missing)}"
        )
    return keys


def _stat_while(graph, protocol, state0, key, *, stat, keep_going, value0,
                steps_per_round=1, ring=None):
    """The shared device-side early-exit loop: run protocol rounds while
    ``keep_going(stats[stat], rounds)`` holds, accumulating messages in the
    two-limb counter and returning the packed one-transfer summary. Both
    run-to-coverage and run-to-convergence are this loop with a different
    predicate and seed value.

    ``steps_per_round=T`` batches T protocol steps into each while-loop
    iteration as a ``lax.scan`` — rounds-bound protocols (the walker
    cohort runs thousands of rounds at a per-iteration floor set by
    while_loop dispatch, not bandwidth) amortize that floor T-fold.
    BIT-EXACT vs T=1 by construction, not approximately: each sub-step
    re-evaluates ``keep_going`` and applies the protocol step only while
    it holds (a crossed target freezes state/rounds/messages for the
    remainder of the super-step), and the sub-step RNG chain is the same
    ``k, sub = split(k)`` sequence the T=1 body walks. The only cost is
    up to T-1 discarded trailing step computations in the final
    super-step.

    When the protocol's stats include ``frontier_occupancy`` (the flood
    family), its per-round values accumulate device-side and the packed
    summary carries their mean in the fifth slot — zero for protocols
    without the stat (the entry points know which is which and drop the
    meaningless zeros).

    ``ring`` (optional ``f32[capacity, K]``, sim/flightrec.py) appends
    the flight-recorder ring to the carry: one row write per APPLIED
    round — frozen sub-steps of a batched super-step write nothing —
    and the final ring comes back as a third return value. The ring
    never feeds the loop's math, so results are bit-identical either
    way."""
    T = int(steps_per_round)
    if T < 1:
        raise ValueError(f"steps_per_round must be >= 1, got {T}")

    def _occ(stats):
        return jnp.float32(stats.get("frontier_occupancy", 0.0))

    def _row(rg, rounds_before, stats, hi, lo):
        # Per-round flight record: the loop's tracked stat rides the
        # coverage column (a coverage fraction for the flood loops).
        return flightrec.write_row(
            rg, rounds_before, occupancy=_occ(stats),
            new=stats["messages"], total=flightrec.total_f32(hi, lo),
            coverage=stats[stat], active_lanes=1, ici_bytes=0.0)

    def cond(carry):
        return keep_going(carry[3], carry[2])

    def body(carry):
        state, k, rounds, _, hi, lo, occ = carry[:7]
        k, sub = jax.random.split(k)
        state, stats = protocol.step(graph, state, sub)
        hi, lo = accum.add((hi, lo), stats["messages"])
        out = (state, k, rounds + 1, jnp.float32(stats[stat]), hi, lo,
               occ + _occ(stats))
        if ring is None:
            return out
        return out + (_row(carry[7], rounds, stats, hi, lo),)

    def batched_body(carry):
        def substep(c, _):
            state, k, rounds, value, hi, lo, occ = c[:7]
            live = keep_going(value, rounds)
            # k advances unconditionally: the while carry never exposes
            # it, and frozen sub-steps discard everything drawn from it,
            # so the chain the APPLIED steps see matches T=1 exactly.
            k, sub = jax.random.split(k)
            new_state, stats = protocol.step(graph, state, sub)
            state = jax.tree_util.tree_map(
                lambda new, old: jnp.where(live, new, old), new_state, state)
            hi, lo = accum.add(
                (hi, lo),
                jnp.where(live, stats["messages"],
                          jnp.zeros_like(stats["messages"])))
            new_rounds = jnp.where(live, rounds + 1, rounds)
            value = jnp.where(live, jnp.float32(stats[stat]), value)
            occ = occ + jnp.where(live, _occ(stats), jnp.float32(0.0))
            out = (state, k, new_rounds, value, hi, lo, occ)
            if ring is None:
                return out, None
            # Frozen sub-steps keep the ring untouched (their discarded
            # step would otherwise overwrite the last applied row).
            return out + (jnp.where(live, _row(c[7], rounds, stats, hi, lo),
                                    c[7]),), None

        carry, _ = jax.lax.scan(substep, carry, None, length=T)
        return carry

    init = (state0, key, jnp.int32(0), value0, *accum.zero(),
            jnp.float32(0.0))
    if ring is not None:
        init = init + (ring,)
    final = jax.lax.while_loop(cond, body if T == 1 else batched_body, init)
    state, _, rounds, value, hi, lo, occ = final[:7]
    occ_mean = occ / jnp.maximum(rounds, 1)
    packed = _pack_summary(rounds, value, (hi, lo), extra=occ_mean)
    if ring is None:
        return state, packed
    return state, packed, final[7]


def _coverage_body(graph, protocol, state0, key, coverage_target, max_rounds,
                   steps_per_round=1, ring=None):
    cov0 = (
        jnp.float32(protocol.coverage(graph, state0))
        if hasattr(protocol, "coverage")
        else jnp.float32(0.0)
    )
    return _stat_while(
        graph, protocol, state0, key, stat="coverage",
        keep_going=lambda v, r: (v < coverage_target) & (r < max_rounds),
        value0=cov0, steps_per_round=steps_per_round, ring=ring,
    )


@functools.partial(jax.jit, static_argnames=("protocol", "max_rounds",
                                             "steps_per_round"))
def _coverage_with_init(graph, protocol, key, *, coverage_target, max_rounds,
                        steps_per_round=1):
    """init + loop in one XLA program (the fresh-run entry pays zero eager
    dispatches — protocol.init's scatter and the seed coverage all trace)."""
    return _coverage_body(graph, protocol, protocol.init(graph, key), key,
                          coverage_target, max_rounds, steps_per_round)


def _coverage_loop(graph, protocol, state0, key, *, coverage_target,
                   max_rounds, steps_per_round=1):
    return _coverage_body(graph, protocol, state0, key, coverage_target,
                          max_rounds, steps_per_round)


_coverage_loop_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "max_rounds", "steps_per_round"),
    donate_argnames=("state0",))(_coverage_loop)
_coverage_loop_keeping = functools.partial(
    jax.jit, static_argnames=("protocol", "max_rounds",
                              "steps_per_round"))(_coverage_loop)


def _coverage_loop_rec(graph, protocol, state0, key, ring, *,
                       coverage_target, max_rounds, steps_per_round=1):
    """The run-to-coverage resume loop with the flight-recorder ring in
    the carry (sim/flightrec.py) — returns ``(state, packed, ring)``;
    the ring is a donated carry leaf of the donating variant exactly
    like the state (graftaudit's donation audit covers this seam)."""
    return _coverage_body(graph, protocol, state0, key, coverage_target,
                          max_rounds, steps_per_round, ring=ring)


_coverage_loop_rec_donating = functools.partial(
    jax.jit, static_argnames=("protocol", "max_rounds", "steps_per_round"),
    donate_argnames=("state0", "ring"))(_coverage_loop_rec)
_coverage_loop_rec_keeping = functools.partial(  # graftlint: ignore[carry-no-donate] -- same donate=False escape hatch as the non-recording twin
    jax.jit, static_argnames=("protocol", "max_rounds",
                              "steps_per_round"))(_coverage_loop_rec)

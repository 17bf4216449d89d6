"""JaxSimNode — the bridge between the Node extension API and the sim engine.

This is the north-star integration point (BASELINE.json): a ``Node``
subclass slotting into the same extend-or-callback seam as every other node,
whose "peers" are a simulated population in HBM instead of socket threads.
It is still a real sockets node — it binds a port, accepts connections, and
can broadcast to live peers — but its population-scale traffic happens as
batched graph propagation.

The semantic bridge, stated honestly (SURVEY.md section 7 "hard parts" 1):
socket peers deliver asynchronous per-message callbacks; the simulated
population advances in synchronous rounds. Events about the population
arrive through the standard ``node_message`` hook [ref: p2pnetwork/
node.py:334-338] with a :class:`SimPeer` stand-in as the connected node and
one dict per completed round — so existing callback-based applications
observe the simulation with no new API.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import numpy as np

from p2pnetwork_tpu import telemetry
from p2pnetwork_tpu.node import Node
from p2pnetwork_tpu.sim import checkpoint as ckpt
from p2pnetwork_tpu.sim import engine
from p2pnetwork_tpu.sim.graph import Graph
from p2pnetwork_tpu.telemetry import spans


class SimPeer:
    """Stand-in for ``NodeConnection`` representing the simulated population.

    Carries the connection surface events expose (``id``, ``host``, ``port``,
    ``info``, ``set_info/get_info`` [ref: nodeconnection.py:231-235]) so
    callbacks written against socket peers work unchanged. ``send`` is a
    debug no-op: messages enter the simulation through protocol state, not a
    socket."""

    def __init__(self, main_node: Node, n_nodes: int):
        self.main_node = main_node
        self.id = f"sim:{n_nodes}-nodes"
        self.host = "hbm"
        self.port = 0
        self.info: dict = {}

    def send(self, data, encoding_type=None, compression="none") -> None:
        self.main_node.debug_print(
            "SimPeer.send: the simulated population is driven by protocol "
            "state, not socket sends"
        )

    def stop(self) -> None:  # parity surface; nothing to stop
        pass

    def set_info(self, key: str, value: Any) -> None:
        self.info[key] = value

    def get_info(self, key: str) -> Any:
        return self.info[key]

    def __str__(self) -> str:
        return f"SimPeer({self.id})"

    __repr__ = __str__


class JaxSimNode(Node):
    """A ``Node`` whose population-scale peers live in HBM.

    Usage::

        node = JaxSimNode("127.0.0.1", 0, graph=g, protocol=Flood(source=0))
        node.start()                  # normal sockets lifecycle
        stats = node.run_rounds(10)   # 10 batched propagation rounds
        node.stop(); node.join()

    Pass ``mesh=jax.make_mesh(...)`` (or ``parallel.mesh.ring_mesh()``) to
    run the population on the MULTI-CHIP backend: same events, same
    stepping/churn/checkpoint methods, with the graph partitioned over the
    device ring (parallel/sharded.py) — the reference's whole API surface
    at the scale one chip cannot hold.

    Each completed round fires ``node_message`` with
    ``{"sim_round": r, **round_stats}``. ``sim_message_count`` accumulates
    the simulated message volume — the population-scale analog of
    ``message_count_send`` [ref: node.py:64-67]; the socket counters stay
    reserved for real socket traffic.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 graph: Optional[Graph] = None, protocol=None, seed: int = 0,
                 mesh=None, dynamic_edges: int = 0, rng: Optional[str] = None,
                 layout: str = "hybrid", adaptive_k: int = 0,
                 comm: str = "ppermute", **node_kwargs):
        super().__init__(host, port, **node_kwargs)
        self.sim_graph: Optional[Graph] = None
        self.sim_protocol = None
        self.sim_state = None
        self.sim_round = 0
        self.sim_message_count = 0
        self.sim_peer: Optional[SimPeer] = None
        self.sim_mesh = None
        self.sim_sharded = None
        self._shard_keep = None
        self._sim_rng: Optional[str] = None
        self._sim_key: Optional[jax.Array] = None
        self._sim_adaptive_k = 0
        self._sim_comm = "ppermute"
        self._churn_count = 0
        if graph is not None and protocol is not None:
            self.attach_simulation(graph, protocol, seed=seed, mesh=mesh,
                                   dynamic_edges=dynamic_edges, rng=rng,
                                   layout=layout, adaptive_k=adaptive_k,
                                   comm=comm)

    # ------------------------------------------------------------- plumbing

    def attach_simulation(self, graph: Graph, protocol, seed: int = 0,
                          mesh=None, dynamic_edges: int = 0,
                          rng: Optional[str] = None,
                          layout: str = "hybrid",
                          adaptive_k: int = 0,
                          comm: str = "ppermute") -> None:
        """Attach (or replace) the simulated population.

        ``mesh`` switches the node onto the multi-chip backend
        (parallel/sharded.py): the population is partitioned over the
        device ring and every stepping, churn, and checkpoint operation
        below drives the sharded representation — same Node event surface,
        same semantics, proven bit-exact against the engine in
        tests/test_sharded.py. On that backend ``sim_graph`` remains the
        PRISTINE attach-time construction (the seed for re-shards and
        checkpoint templates); the live topology is ``sim_sharded``, and
        backend-agnostic introspection goes through ``sim_node_alive``.
        ``dynamic_edges`` reserves runtime link capacity on the sharded
        graph; ``rng`` picks the sharded RNG mode ('exact' | 'tile' |
        'fold', default tile when aligned); ``layout`` picks the sharded
        edge layout — 'hybrid' (ring-decomposed diagonals + MXU remainder,
        the fast default), 'mxu', or 'segment' (BENCH.md has the measured
        ladder). All layouts are bit-exact. ``adaptive_k > 0`` additionally
        builds the sender-CSR view and runs Flood's ``run_until_coverage``
        through the frontier-adaptive loop (small-frontier rounds skip the
        ring; bit-identical results). ``comm`` picks the mesh backend's
        halo exchange ('ppermute', 'pallas' or 'auto', parallel/auto.py);
        both backends are bit-identical.

        On a mesh the node keeps the pristine ``ShardedGraph`` of its
        last attach: attaching the same graph object again with the same
        mesh, ``layout`` and ``adaptive_k`` reuses it
        (``sharded.ShardKeep``; churn since then made new ones, so the run
        starts from the attach-time topology) and builds only the
        protocol's initial state and any ``dynamic_edges`` region. Any
        other graph or flag shards afresh.

        The key and the initial state are built under a ``sim_attach``
        span; their host seconds add to ``sim_attach_seconds_total``.
        """
        if layout not in ("hybrid", "mxu", "segment"):
            # Validate regardless of backend: a typo'd layout must not be
            # silently accepted just because no mesh is attached yet.
            raise ValueError(
                f"layout must be 'hybrid', 'mxu' or 'segment', got "
                f"{layout!r}"
            )
        if adaptive_k > 0:
            from p2pnetwork_tpu.models.flood import Flood as _Flood
            from p2pnetwork_tpu.models.hopdist import (
                HopDistance as _HopDistance,
            )

            # A silent no-op would be worse than an error: the flag only
            # drives the mesh backend's Flood/HopDistance loops.
            if mesh is None:
                raise ValueError(
                    "adaptive_k drives the mesh backend's coverage loop; "
                    "on the single-device backend use "
                    "protocol=AdaptiveFlood(...) on a source_csr=True graph"
                )
            if not isinstance(protocol, (_Flood, _HopDistance)):
                raise ValueError(
                    f"adaptive_k applies to Flood and HopDistance on the "
                    f"mesh backend; got {type(protocol).__name__}"
                )
        t0 = time.perf_counter()
        with spans.span("sim_attach"):
            self.sim_graph = graph
            self.sim_protocol = protocol
            self._sim_key = jax.random.key(seed)
            self.sim_mesh = mesh
            self._sim_rng = rng
            self._sim_adaptive_k = adaptive_k
            self._sim_comm = comm
            # The previous attach's topology and state leave the chips
            # before a new shard comes.
            self.sim_sharded = self.sim_state = None
            if mesh is not None:
                from p2pnetwork_tpu.parallel import sharded

                if self._shard_keep is None:
                    self._shard_keep = sharded.ShardKeep()
                sg = sharded.shard_graph(graph, mesh, mxu=layout == "mxu",
                                         hybrid=layout == "hybrid",
                                         source_csr=adaptive_k > 0,
                                         keep=self._shard_keep)
                if dynamic_edges:
                    sg = sharded.with_capacity(sg, dynamic_edges)
                self.sim_sharded = sg
                self.sim_state = sharded.init_state(sg, protocol,
                                                    self._sim_key)
            else:
                self._shard_keep = None
                self.sim_state = protocol.init(graph, self._sim_key)
        telemetry.default_registry().counter(
            "sim_attach_seconds_total",
            "Host seconds in JaxSimNode.attach_simulation: the run key, "
            "the protocol's initial state (and the shard on a mesh)."
        ).inc(time.perf_counter() - t0)
        self.sim_round = 0
        self.sim_message_count = 0
        self._churn_count = 0
        self.sim_peer = SimPeer(self, graph.n_nodes)
        self.debug_print(
            f"attach_simulation: {graph.n_nodes} nodes / {graph.n_edges} edges, "
            f"protocol {type(protocol).__name__}"
            + (f", {mesh.devices.size}-device mesh" if mesh is not None else "")
        )

    def _require_sim(self):
        if self.sim_graph is None:
            raise RuntimeError("JaxSimNode: no simulation attached; call attach_simulation()")

    @property
    def sim_node_alive(self):
        """Liveness of the simulated population (bool, one entry per padded
        node) from whichever backend is active. On the mesh backend the
        live topology is ``sim_sharded`` — ``sim_graph`` stays the pristine
        attach-time construction (it seeds re-shards and checkpoint
        templates), so topology introspection must go through this
        property, not ``sim_graph.node_mask``."""
        self._require_sim()
        if self.sim_mesh is not None:
            return np.asarray(self.sim_sharded.node_mask).reshape(-1)
        return np.asarray(self.sim_graph.node_mask)

    # ------------------------------------------------------------- stepping

    def _run_rounds_sharded(self, rounds: int, seg_key):
        """Dispatch a run_rounds segment onto the sharded backend."""
        from p2pnetwork_tpu.models.flood import Flood
        from p2pnetwork_tpu.models.gossip import Gossip
        from p2pnetwork_tpu.models.hopdist import HopDistance
        from p2pnetwork_tpu.models.pagerank import PageRank
        from p2pnetwork_tpu.models.pushsum import PushSum
        from p2pnetwork_tpu.models.sir import SIR
        from p2pnetwork_tpu.parallel import sharded

        sg, mesh, proto = self.sim_sharded, self.sim_mesh, self.sim_protocol
        comm = self._sim_comm
        if isinstance(proto, Flood):
            return sharded.flood(sg, mesh, proto.source, rounds,
                                 state0=self.sim_state, return_state=True,
                                 comm=comm)
        if isinstance(proto, SIR):
            return sharded.sir(sg, mesh, proto, seg_key, rounds,
                               rng=self._sim_rng, status0=self.sim_state,
                               comm=comm)
        if isinstance(proto, Gossip):
            return sharded.gossip(sg, mesh, proto, seg_key, rounds,
                                  rng=self._sim_rng, values0=self.sim_state,
                                  comm=comm)
        if isinstance(proto, HopDistance):
            return sharded.hopdist(sg, mesh, proto, rounds,
                                   state0=self.sim_state, comm=comm)
        if isinstance(proto, PageRank):
            return sharded.pagerank(sg, mesh, proto, rounds,
                                    ranks0=self.sim_state, comm=comm)
        if isinstance(proto, PushSum):
            return sharded.pushsum(sg, mesh, proto, seg_key, rounds,
                                   state0=self.sim_state, comm=comm)
        raise ValueError(
            f"the sharded backend implements Flood, SIR, Gossip, "
            f"HopDistance, PageRank and PushSum; got {type(proto).__name__}"
        )

    def run_rounds(self, rounds: int) -> dict:
        """Advance the population ``rounds`` synchronous rounds.

        One compiled ``lax.scan`` on device; afterwards fires ``node_message``
        once per round (aggregate stats dict) through the standard event
        path. Returns the stacked stats as numpy arrays."""
        self._require_sim()
        # Per-segment key: deterministic in (seed, segment start).
        seg_key = jax.random.fold_in(self._sim_key, self.sim_round)
        if self.sim_mesh is not None:
            self.sim_state, stats = self._run_rounds_sharded(rounds, seg_key)
        else:
            self.sim_state, stats = engine.run_from(
                self.sim_graph, self.sim_protocol, self.sim_state, seg_key,
                rounds,
            )
        host_stats = {k: np.asarray(v) for k, v in stats.items()}
        for r in range(rounds):
            round_stats = {k: host_stats[k][r].item() for k in host_stats}  # graftlint: ignore[host-sync-in-loop] -- host_stats is numpy (one transfer above the loop)
            if "messages" in round_stats:
                self.sim_message_count += int(round_stats["messages"])  # graftlint: ignore[host-sync-in-loop] -- already a Python scalar
            self.sim_round += 1
            self.node_message(self.sim_peer, {"sim_round": self.sim_round, **round_stats})
        return host_stats

    def _finish_run(self, out: dict) -> dict:
        """Shared tail of the run-to-* loops: host summary, round/message
        accounting, and the single summary ``node_message`` event."""
        summary = {k: np.asarray(v).item() for k, v in out.items()}
        self.sim_round += int(summary["rounds"])
        self.sim_message_count += int(summary["messages"])
        self.node_message(self.sim_peer, {"sim_run": True, **summary})
        return summary

    def run_until_coverage(self, coverage_target: float = 0.99,
                           max_rounds: int = 1024) -> dict:
        """Device-side run-to-coverage continuing from the current state
        (no per-round events; one summary ``node_message`` at the end).
        On the mesh backend this is the multi-chip while_loop
        (sharded.flood_until_coverage / sharded.sir_until_coverage). The
        call runs under a ``sim_run`` span holding the engine's
        ``run_dispatch`` / ``run_summary`` spans."""
        self._require_sim()
        with spans.span("sim_run"):
            seg_key = jax.random.fold_in(self._sim_key, self.sim_round)
            if self.sim_mesh is not None:
                from p2pnetwork_tpu.models.flood import Flood
                from p2pnetwork_tpu.models.hopdist import HopDistance
                from p2pnetwork_tpu.models.sir import SIR
                from p2pnetwork_tpu.parallel import sharded

                if isinstance(self.sim_protocol, Flood):
                    self.sim_state, out = sharded.flood_until_coverage(
                        self.sim_sharded, self.sim_mesh,
                        self.sim_protocol.source,
                        coverage_target=coverage_target,
                        max_rounds=max_rounds,
                        state0=self.sim_state, return_state=True,
                        adaptive_k=self._sim_adaptive_k, comm=self._sim_comm,
                    )
                elif isinstance(self.sim_protocol, HopDistance):
                    self.sim_state, out = sharded.hopdist_until_coverage(
                        self.sim_sharded, self.sim_mesh, self.sim_protocol,
                        coverage_target=coverage_target,
                        max_rounds=max_rounds,
                        state0=self.sim_state,
                        adaptive_k=self._sim_adaptive_k, comm=self._sim_comm,
                    )
                elif isinstance(self.sim_protocol, SIR):
                    self.sim_state, out = sharded.sir_until_coverage(
                        self.sim_sharded, self.sim_mesh, self.sim_protocol,
                        seg_key, coverage_target=coverage_target,
                        max_rounds=max_rounds, rng=self._sim_rng,
                        status0=self.sim_state, comm=self._sim_comm,
                    )
                else:
                    raise ValueError(
                        "run_until_coverage on the sharded backend "
                        "implements Flood, SIR and HopDistance; the protocol "
                        "must expose a coverage stat"
                    )
            else:
                self.sim_state, out = engine.run_until_coverage_from(
                    self.sim_graph, self.sim_protocol, self.sim_state,
                    seg_key, coverage_target=coverage_target,
                    max_rounds=max_rounds,
                )
            return self._finish_run(out)

    def run_until_converged(self, stat: str, threshold: float,
                            max_rounds: int = 1024) -> dict:
        """Device-side run-to-convergence continuing from the current state
        (engine.run_until_converged): advance until ``stats[stat]`` drops
        below ``threshold`` — PageRank to a residual, PushSum/Gossip to a
        variance. On the mesh backend, PageRank (stat='residual') and
        PushSum (stat='variance') ride the multi-chip loops
        (sharded.pagerank_until_residual / pushsum_until_variance)."""
        self._require_sim()
        seg_key = jax.random.fold_in(self._sim_key, self.sim_round)
        if self.sim_mesh is not None:
            from p2pnetwork_tpu.models.pagerank import PageRank
            from p2pnetwork_tpu.models.pushsum import PushSum
            from p2pnetwork_tpu.parallel import sharded

            if isinstance(self.sim_protocol, PageRank) and stat == "residual":
                self.sim_state, out = sharded.pagerank_until_residual(
                    self.sim_sharded, self.sim_mesh, self.sim_protocol,
                    tol=threshold, max_rounds=max_rounds,
                    ranks0=self.sim_state, comm=self._sim_comm,
                )
            elif isinstance(self.sim_protocol, PushSum) and stat == "variance":
                self.sim_state, out = sharded.pushsum_until_variance(
                    self.sim_sharded, self.sim_mesh, self.sim_protocol,
                    seg_key, tol=threshold, max_rounds=max_rounds,
                    state0=self.sim_state, comm=self._sim_comm,
                )
            else:
                raise ValueError(
                    "run_until_converged on the sharded backend implements "
                    "PageRank (stat='residual') and PushSum "
                    "(stat='variance'); run other protocols on the "
                    "single-device backend or step them with run_rounds"
                )
        else:
            self.sim_state, out = engine.run_until_converged(
                self.sim_graph, self.sim_protocol, seg_key, stat=stat,
                threshold=threshold, max_rounds=max_rounds,
                state0=self.sim_state,
            )
        return self._finish_run(out)

    # ------------------------------------------------------------- topology

    def _sim_topology_event(self, change: str) -> None:
        """Population topology changes surface through ``node_message``
        (like round stats) — SimPeer is not in the socket registries, so
        the inbound/outbound disconnect dispatcher correctly ignores it."""
        mask = (self.sim_sharded.node_mask if self.sim_mesh is not None
                else self.sim_graph.node_mask)
        alive = int(np.asarray(mask.sum()))
        self.node_message(
            self.sim_peer, {"sim_topology": change, "alive_nodes": alive}
        )

    def fail_sim_nodes(self, node_ids) -> None:
        """Fail-stop simulated peers (sim/failures.py, or the sharded
        mirror on the mesh backend) — the population analog of peers
        dropping [ref: node.py:307-319]."""
        self._require_sim()
        if self.sim_mesh is not None:
            from p2pnetwork_tpu.parallel import sharded

            self.sim_sharded = sharded.fail_nodes(self.sim_sharded, node_ids)
        else:
            from p2pnetwork_tpu.sim import failures

            self.sim_graph = failures.fail_nodes(self.sim_graph, node_ids)
        self._sim_topology_event("fail_nodes")

    def inject_sim_churn(self, frac: float, seed: Optional[int] = None) -> None:
        """Randomly fail ``frac`` of the live simulated population.

        Each call draws fresh randomness by default (an internal counter
        folds into the node's sim key) — a fixed seed would re-select the
        same, already-dead nodes on every call after the first. Pass
        ``seed`` only to reproduce one specific churn event.
        """
        self._require_sim()
        if seed is not None:
            key = jax.random.key(seed)
        else:
            self._churn_count += 1
            key = jax.random.fold_in(
                jax.random.fold_in(self._sim_key, 0x0C0C), self._churn_count
            )
        if self.sim_mesh is not None:
            from p2pnetwork_tpu.parallel import sharded

            self.sim_sharded = sharded.random_node_failures(
                self.sim_sharded, key, frac
            )
        else:
            from p2pnetwork_tpu.sim import failures

            self.sim_graph = failures.random_node_failures(
                self.sim_graph, key, frac
            )
        self._sim_topology_event("churn")

    def connect_sim_nodes(self, senders, receivers) -> None:
        """Add links between simulated peers at runtime (sim/topology.py,
        or the sharded mirror; the population analog of
        ``connect_with_node`` [ref: node.py:122]). Needs dynamic capacity
        (``topology.with_capacity`` / ``dynamic_edges=`` at attach)."""
        self._require_sim()
        if self.sim_mesh is not None:
            from p2pnetwork_tpu.parallel import sharded

            self.sim_sharded = sharded.connect(
                self.sim_sharded, senders, receivers
            )
        else:
            from p2pnetwork_tpu.sim import topology

            self.sim_graph = topology.connect(self.sim_graph, senders, receivers)
        self._sim_topology_event("connect")

    # ----------------------------------------------------------- checkpoint

    def save_checkpoint(self, path: str) -> None:
        """Persist protocol state, PRNG key, round/message counters, AND the
        topology mutation state (failed nodes, cut edges, runtime links,
        churn counter) — see sim/checkpoint.py. Topology is state here for
        the same reason the reference keeps its peer lists on the node
        object [ref: p2pnetwork/node.py:46-52]: a restored run must see the
        network as it was, not as it was built."""
        self._require_sim()
        payload = {
            "protocol": self.sim_state,
            "topology": self._topology_state(),
            "churn_count": np.int64(self._churn_count),
        }
        ckpt.save(path, payload, self._sim_key, self.sim_round,
                  self.sim_message_count)

    def _topology_state(self):
        if self.sim_mesh is not None:
            from p2pnetwork_tpu.parallel import sharded

            return sharded.topology_state(self.sim_sharded)
        return ckpt.topology_state(self.sim_graph)

    def load_checkpoint(self, path: str) -> None:
        """Restore a checkpoint taken from a node with the same (pristine)
        graph construction and protocol.

        The attached graph supplies the static arrays; the checkpoint's
        topology state is re-applied onto it, so a run that failed nodes or
        grew links resumes on exactly the damaged/grown network — and the
        churn counter is restored, so the next ``inject_sim_churn()`` draws
        fresh randomness instead of replaying pre-checkpoint draws."""
        self._require_sim()
        if self.sim_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from p2pnetwork_tpu.parallel import sharded

            template = {
                "protocol": sharded.init_state(
                    self.sim_sharded, self.sim_protocol, jax.random.key(0)
                ),
                "topology": sharded.topology_state(self.sim_sharded),
                "churn_count": np.int64(0),
            }
            payload, key, rnd, msgs = ckpt.load(path, template)
            new_sharded = sharded.apply_topology_state(
                self.sim_sharded, payload["topology"]
            )
            shard = NamedSharding(self.sim_mesh,
                                  P(self.sim_mesh.axis_names[0]))
            replicated = NamedSharding(self.sim_mesh, P())

            def put(x):
                # Scalar leaves (HopDistance's round counter) replicate —
                # a rank-1 spec on a 0-d array is invalid.
                arr = jax.numpy.asarray(x)
                return jax.device_put(arr,
                                      shard if arr.ndim >= 1 else replicated)

            self.sim_state = jax.tree.map(put, payload["protocol"])
            self.sim_sharded = new_sharded
        else:
            proto_template = self.sim_protocol.init(self.sim_graph,
                                                    jax.random.key(0))
            payload, key, rnd, msgs = ckpt.load_node_payload(
                path, self.sim_graph, proto_template
            )
            # Validate everything (including topology shapes) BEFORE
            # mutating the node — a rejected load must leave it untouched,
            # not holding a foreign protocol state against its own graph.
            new_graph = ckpt.apply_topology_state(self.sim_graph,
                                                  payload["topology"])
            # Device-put the protocol leaves (npz gives numpy): raw numpy
            # would re-pay host->device transfer on every jit dispatch.
            self.sim_state = jax.tree.map(jax.numpy.asarray,
                                          payload["protocol"])
            self.sim_graph = new_graph
        self._sim_key = key
        self.sim_round = rnd
        self.sim_message_count = msgs
        self._churn_count = int(payload["churn_count"])

"""Auto-sharded (GSPMD) protocol execution: annotate shardings, let XLA
insert the collectives.

The explicit ring path (parallel/sharded.py) hand-places every ``ppermute``;
this module is the complementary idiom from the JAX sharding playbook: put
the graph's arrays on the mesh with named shardings and run the *unchanged*
single-device engine — the compiler partitions the computation and inserts
all-gathers/reduce-scatters where edges cross shards. Any protocol written
against the engine (Flood, Gossip, SIR, user protocols) scales this way
with zero protocol changes; the explicit ring remains the
bandwidth-predictable path for the flood benchmark.

Layouts: every per-node array is sharded on its leading (node) axis, every
per-edge array on its edge axis, the neighbor table on rows. The blocked
and hybrid representations carry over too — buckets are destination-block
(node-order) slabs, so their leading axis shards in alignment with the
node axis. Use ``method="hybrid-blocked"`` here: the diagonal rolls and
the one-hot einsum remainder are all partitionable ops, which closes most
of the gap to the explicit ring path (the plain segment lowering pays the
full scatter floor); the Pallas remainder kernel (``method="hybrid"``)
stays single-chip — a pallas_call is an opaque custom call the
partitioner would have to replicate.

Communication evidence (tests/test_auto_comm.py inspects the compiled
HLO): for segment-method Flood/SIR on an 8-device mesh, every collective
GSPMD inserts is node-extent — the bool frontier (N bytes) for flood, the
f32 pressure signal (4N bytes) for SIR, plus scalar stats all-reduces —
and edge-extent arrays are never moved. That is the bandwidth-sane
partitioning (per-round cross-shard volume on the order of the node
state, like the explicit ring path, delivered as compiler-placed
collectives instead of S ppermute hops). The tests bound every
collective's payload to node extent — including variadic combined and
async forms — so a compiler or layout change that regresses to
edge-extent traffic fails loudly.
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2pnetwork_tpu.parallel.mesh import DEFAULT_AXIS
from p2pnetwork_tpu.sim.graph import Graph

#: The explicit ring's halo-exchange backends. resolve_comm validates
#: against parallel/sharded.COMM_BACKENDS itself (lazy import — sharded
#: pulls in jax); this literal only serves the docstring/error text and
#: is pinned equal to sharded's by tests/test_ring.py.
COMM_BACKENDS = ("ppermute", "pallas")


def resolve_comm(comm: str = "auto") -> str:
    """Route the ring path's halo-exchange backend (``comm=`` knob on every
    parallel/sharded.py entry point, ``MeshConfig.comm`` in config.py).

    - ``"ppermute"``: XLA collective-permute — the portable default; the
      compiler's latency-hiding scheduler may overlap it with the bucket
      compute the ring bodies issue after it.
    - ``"pallas"``: ``pltpu.make_async_remote_copy`` ring-DMA kernels
      (ops/pallas_ring.py). On the MXU bucket layout the hop is FUSED
      under the blocked segment sum (genuine in-kernel overlap); on the
      segment layouts today's hop kernel is start+wait in one call —
      measure before preferring it there (sharded._RingComm's overlap
      note). Native on TPU; on CPU it runs the Pallas interpreter
      (orders of magnitude slower — kept for the bit-identity parity
      CI, tests/test_ring.py).
    - ``"auto"``: pallas on a TPU backend, ppermute elsewhere — the same
      shape of routing ``ops/segment.py`` does for kernel methods.
    """
    if comm == "auto":
        import jax

        return "pallas" if jax.default_backend() == "tpu" else "ppermute"
    from p2pnetwork_tpu.parallel.sharded import COMM_BACKENDS as _BACKENDS

    if comm not in _BACKENDS:
        raise ValueError(
            f"comm must be one of {_BACKENDS + ('auto',)}, got {comm!r}")
    return comm


def shard_graph_auto(graph: Graph, mesh: Mesh,
                     axis_name: str = DEFAULT_AXIS) -> Graph:
    """Return ``graph`` with its arrays placed on ``mesh``, node/edge axes
    sharded. Shapes are already padded to multiples of 128, so any mesh of
    up to 128 devices divides them evenly."""
    # The compiler-inserted-collectives idiom needs Auto axes: under JAX's
    # explicit sharding-in-types (the make_mesh default), a node-sharded
    # gather by edge-sharded indices is a type error instead of an
    # auto-partitioned program.
    mesh = Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names),
    )
    spec = NamedSharding(mesh, P(axis_name))

    def put(x):
        return None if x is None else jax.device_put(x, spec)

    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]

    def put_blocked(blocked):
        # BlockedEdges buckets are destination blocks in node order, so
        # sharding their leading axis aligns each bucket with the shard
        # that owns its destination nodes; the einsum stays local and only
        # the (node-extent) signal gather crosses shards. A remainder with
        # fewer buckets than shards (tiny graphs) is replicated instead —
        # device_put needs even division, and at that size it is noise.
        if blocked is None:
            return None
        div = blocked.src.shape[0] % axis_size == 0
        bspec = NamedSharding(mesh, P(axis_name) if div else P())
        return dataclasses.replace(
            blocked,
            src=jax.device_put(blocked.src, bspec),
            local_dst=jax.device_put(blocked.local_dst, bspec),
            mask=jax.device_put(blocked.mask, bspec),
        )

    def put_hybrid(hybrid):
        # Diagonal masks are [D, n] with n the (unpadded) node axis:
        # shard axis 1 when it divides. The remainder rides the blocked
        # (einsum) form — under this path use method="hybrid-blocked";
        # the Pallas remainder kernel is an opaque custom call the
        # partitioner cannot shard.
        if hybrid is None:
            return None
        div = hybrid.masks.shape[1] % axis_size == 0
        mspec = NamedSharding(mesh, P(None, axis_name) if div else P())
        return dataclasses.replace(
            hybrid,
            masks=jax.device_put(hybrid.masks, mspec),
            remainder=put_blocked(hybrid.remainder),
        )

    def put_skew(skew):
        # Virtual rows are owner-sorted (node order), so sharding the row
        # axis keeps each shard's rows aligned with the shard owning
        # their receiver nodes; only the (node-extent) signal gather and
        # the owner-segment combine cross shards. Row padding is a
        # multiple of 8, not 128 — replicate when it does not divide
        # (tiny graphs, odd meshes), same contract as put_blocked.
        if skew is None:
            return None
        div = skew.src.shape[0] % axis_size == 0
        rspec = NamedSharding(mesh, P(axis_name) if div else P())
        return dataclasses.replace(
            skew,
            src=jax.device_put(skew.src, rspec),
            mask=jax.device_put(skew.mask, rspec),
            owner=jax.device_put(skew.owner, rspec),
            start=jax.device_put(skew.start, rspec),
            weight=(None if skew.weight is None
                    else jax.device_put(skew.weight, rspec)),
        )

    return dataclasses.replace(
        graph,
        senders=put(graph.senders),
        receivers=put(graph.receivers),
        edge_mask=put(graph.edge_mask),
        node_mask=put(graph.node_mask),
        in_degree=put(graph.in_degree),
        out_degree=put(graph.out_degree),
        neighbors=put(graph.neighbors),
        neighbor_mask=put(graph.neighbor_mask),
        edge_weight=put(graph.edge_weight),
        neighbor_weight=put(graph.neighbor_weight),
        blocked=put_blocked(graph.blocked),
        hybrid=put_hybrid(graph.hybrid),
        skew=put_skew(graph.skew),
    )


def run_auto(graph: Graph, protocol, key: jax.Array, rounds: int):
    """Run ``rounds`` protocol rounds on an auto-sharded graph.

    Identical semantics to ``engine.run`` (it IS engine.run — the shardings
    on ``graph``'s arrays make GSPMD partition the compiled program)."""
    from p2pnetwork_tpu.sim import engine

    return engine.run(graph, protocol, key, rounds)

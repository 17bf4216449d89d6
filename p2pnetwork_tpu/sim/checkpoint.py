"""Checkpoint / resume for simulation runs.

The reference has no persistence of any kind — node ids are regenerated per
run [ref: p2pnetwork/node.py:85-90] (SURVEY.md section 5 "Checkpoint").
For multi-million-node simulations, resumability is table stakes: a
checkpoint is the protocol state pytree plus the PRNG key and round counter
— everything needed to make a resumed run bit-identical to an uninterrupted
one (tests/test_checkpoint.py proves that). Topology mutations (failures,
runtime links) are state too — the reference's peer lists live on the node
object [ref: p2pnetwork/node.py:46-52] — captured/re-applied via
:func:`topology_state` / :func:`apply_topology_state`.

Format: a single ``.npz`` (atomic rename on save). The state's tree
structure is recorded so loads verify against the template; arrays come
back as numpy and are device-put lazily by the first jitted use.

For sharded / multi-host runs use :func:`save_orbax` / :func:`load_orbax`:
the npz path funnels every shard through one host, while orbax writes each
process's shards in parallel and restores arrays WITH their shardings (the
template's shardings are applied on load, so a resumed multi-chip run does
not round-trip through host memory).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import tempfile
import zipfile
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


class CheckpointCorrupt(RuntimeError):
    """A checkpoint file failed integrity verification.

    Raised instead of the bare ``zipfile.BadZipFile`` / ``zlib.error`` /
    ``ValueError`` soup a truncated or bit-flipped ``.npz`` produces — a
    crash-tolerant resume loop (supervise/store.py) needs to tell "this
    entry is damaged, skip to the previous one" apart from "the caller
    passed the wrong template" (which stays a ``ValueError``). Carries the
    file path plus, for hash mismatches, the expected and actual digests.
    """

    def __init__(self, path: str, detail: str = "",
                 expected: Optional[str] = None, actual: Optional[str] = None):
        self.path = path
        self.expected = expected
        self.actual = actual
        msg = f"corrupt checkpoint {path!r}"
        if expected is not None:
            msg += f": content hash mismatch (expected {expected}, got {actual})"
        elif detail:
            msg += f": {detail}"
        super().__init__(msg)


#: npz entry carrying the content digest; excluded from its own hash.
_DIGEST_KEY = "__sha256__"


def _payload_digest(payload: Dict[str, np.ndarray]) -> str:
    """sha256 over every payload entry (name, dtype, shape, raw bytes), in
    sorted-name order — the integrity hash ``save`` embeds and ``load``
    verifies. Deterministic across processes: no pickled objects, no dict
    order dependence."""
    h = hashlib.sha256()
    for name in sorted(payload):
        if name == _DIGEST_KEY:
            continue
        arr = np.ascontiguousarray(payload[name])
        h.update(name.encode())
        h.update(str(arr.dtype).encode())
        h.update(repr(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def topology_state(graph) -> Dict[str, Any]:
    """The graph's runtime-mutable leaves, as a checkpointable pytree.

    The reference's topology IS its state — the peer lists live on the node
    object [ref: p2pnetwork/node.py:46-52] — so a faithful checkpoint must
    capture what failures (sim/failures.py) and dynamic links
    (sim/topology.py) did to the graph: liveness masks, degrees, the
    dynamic edge region, and the derived masks of every attached
    aggregation representation. The static arrays (edge lists, neighbor
    ids, kernel layouts) are NOT stored — they are reconstructed by
    attaching the same pristine graph and re-applying this state via
    :func:`apply_topology_state`.
    """
    ts: Dict[str, Any] = {
        "node_mask": graph.node_mask,
        "edge_mask": graph.edge_mask,
        "in_degree": graph.in_degree,
        "out_degree": graph.out_degree,
    }
    if graph.neighbor_mask is not None:
        ts["neighbor_mask"] = graph.neighbor_mask
    if graph.dyn_senders is not None:
        ts["dyn_senders"] = graph.dyn_senders
        ts["dyn_receivers"] = graph.dyn_receivers
        ts["dyn_mask"] = graph.dyn_mask
    if graph.blocked is not None:
        ts["blocked_mask"] = graph.blocked.mask
    if graph.hybrid is not None:
        ts["hybrid_masks"] = graph.hybrid.masks
        if graph.hybrid.remainder is not None:
            ts["hybrid_remainder_mask"] = graph.hybrid.remainder.mask
    return ts


def apply_topology_state(graph, ts: Dict[str, Any]):
    """Re-apply a :func:`topology_state` onto a structurally-equal graph.

    ``graph`` must carry the same representations (dynamic capacity,
    neighbor table, blocked/hybrid layouts) and shapes as the graph the
    state was saved from — typically the same pristine construction the
    original run attached. Returns a new Graph whose mutation state
    (failed nodes, cut edges, runtime links, degrees) is exactly the
    saved one.
    """
    def _shape(name, current):
        saved_shape = tuple(np.shape(ts[name]))
        if current is None or saved_shape != tuple(current.shape):
            raise ValueError(
                f"topology state mismatch for {name!r}: saved shape "
                f"{saved_shape}, graph has "
                f"{None if current is None else tuple(current.shape)} — "
                f"attach the same graph construction the checkpoint came from"
            )

    ts = {k: jax.numpy.asarray(v) for k, v in ts.items()}  # npz gives numpy;
    # raw numpy leaves would break .at[] updates (connect after restore) and
    # re-pay host->device transfer on every subsequent jit call.

    expected = set(topology_state(graph).keys())
    got = set(ts.keys())
    drop_neighbor_table = False
    if expected - got == {"neighbor_mask"} and not graph.neighbors_complete:
        # The checkpointed run dropped its width-capped neighbor table
        # (fail_edges on an incomplete table loses the slot->edge map);
        # mirror that on the attached graph instead of rejecting a valid
        # checkpoint the docs say to restore onto the pristine build.
        drop_neighbor_table = True
        expected.discard("neighbor_mask")
    if expected != got:
        raise ValueError(
            f"topology state keys mismatch: checkpoint has {sorted(got)}, "
            f"attached graph expects {sorted(expected)} — attach a graph "
            f"with the same representations (capacity, neighbor table, "
            f"blocked/hybrid) as the one checkpointed"
        )

    for name, cur in (
        ("node_mask", graph.node_mask),
        ("edge_mask", graph.edge_mask),
        ("in_degree", graph.in_degree),
        ("out_degree", graph.out_degree),
    ):
        _shape(name, cur)
    kw: Dict[str, Any] = {
        "node_mask": ts["node_mask"],
        "edge_mask": ts["edge_mask"],
        "in_degree": ts["in_degree"],
        "out_degree": ts["out_degree"],
    }
    if "neighbor_mask" in ts:
        _shape("neighbor_mask", graph.neighbor_mask)
        kw["neighbor_mask"] = ts["neighbor_mask"]
    elif drop_neighbor_table:
        kw["neighbors"] = None
        kw["neighbor_mask"] = None
    if "dyn_senders" in ts:
        _shape("dyn_senders", graph.dyn_senders)
        kw["dyn_senders"] = ts["dyn_senders"]
        kw["dyn_receivers"] = ts["dyn_receivers"]
        kw["dyn_mask"] = ts["dyn_mask"]
    if "blocked_mask" in ts:
        _shape("blocked_mask", graph.blocked.mask)
        kw["blocked"] = dataclasses.replace(graph.blocked, mask=ts["blocked_mask"])
    if "hybrid_masks" in ts:
        _shape("hybrid_masks", graph.hybrid.masks)
        remainder = graph.hybrid.remainder
        if "hybrid_remainder_mask" in ts:
            _shape("hybrid_remainder_mask", remainder.mask)
            remainder = dataclasses.replace(
                remainder, mask=ts["hybrid_remainder_mask"]
            )
        kw["hybrid"] = dataclasses.replace(
            graph.hybrid, masks=ts["hybrid_masks"], remainder=remainder
        )
    return dataclasses.replace(graph, **kw)


def grow_state(state: Any, template: Any) -> Any:
    """Zero-extend every leaf of ``state`` into ``template``'s shapes — the
    repad-compatibility half of ``Graph.grow``.

    A checkpoint written at node capacity ``N_pad`` holds per-node leaves of
    width ``N_pad``; after a geometric repad the resumed run's template is
    wider. Growth padding is all-dead (``node_mask`` False), and zero IS the
    canonical state value for dead padding in every shipped protocol (init
    masks by liveness), so zero-extension makes resume-across-repad
    bit-identical to an uninterrupted grown run — tests/test_graftchurn.py
    pins that.

    Each leaf must match its template leaf's dtype and rank, and be no
    larger along any axis (growth only — shrinking would drop state);
    otherwise ``ValueError``, which :meth:`CheckpointStore.load_latest`
    counts as a template mismatch and skips past. Leaves whose shapes
    already match pass through untouched (the no-repad case is identity).
    """
    s_leaves, s_def = jax.tree_util.tree_flatten(state)
    t_leaves, t_def = jax.tree_util.tree_flatten(template)
    if str(s_def) != str(t_def):
        raise ValueError(
            f"state structure mismatch:\n  state: {s_def}\n  template: {t_def}")
    out = []
    for i, (s, t) in enumerate(zip(s_leaves, t_leaves)):
        t_shape = tuple(np.shape(t))
        t_dtype = np.dtype(getattr(t, "dtype", None) or np.asarray(t).dtype)  # graftlint: ignore[host-sync-in-loop] -- dtype probe of a dtype-less leaf (a Python scalar); no device transfer
        s_shape = tuple(np.shape(s))
        s_dtype = np.dtype(getattr(s, "dtype", None) or np.asarray(s).dtype)  # graftlint: ignore[host-sync-in-loop] -- dtype probe of a dtype-less leaf (a Python scalar); no device transfer
        if s_shape == t_shape and s_dtype == t_dtype:
            out.append(s)
            continue
        if (s_dtype != t_dtype or len(s_shape) != len(t_shape)
                or any(a > b for a, b in zip(s_shape, t_shape))):
            raise ValueError(
                f"state leaf {i} is not repad-growable: saved "
                f"{s_dtype}{s_shape}, template {t_dtype}{t_shape} — a "
                f"repad-compatible leaf matches dtype and rank and only "
                f"grows along axes")
        grown = np.zeros(t_shape, s_dtype)
        grown[tuple(slice(0, d) for d in s_shape)] = np.asarray(  # graftlint: ignore[host-sync-in-loop] -- zero-extension IS a host splice of every grown leaf; once per resume, not per round
            jax.device_get(s))  # graftlint: ignore[host-sync-in-loop] -- one audited pull per grown leaf, once per resume
        out.append(grown)
    return jax.tree_util.tree_unflatten(s_def, out)


def load_node_payload(path: str, graph, protocol_state_template) -> Tuple[
        Dict[str, Any], jax.Array, int, int]:
    """Load a JaxSimNode checkpoint (payload dict with ``protocol``,
    ``topology``, ``churn_count`` keys) written by
    ``JaxSimNode.save_checkpoint``.

    Owns the format-level tolerances:

    - A run that hit ``fail_edges`` on a width-capped neighbor table
      dropped the table, so its checkpoint legitimately lacks
      ``neighbor_mask`` — when the straight load rejects the structure and
      the attached graph's table is droppable (incomplete), retry with a
      table-less template and let :func:`apply_topology_state` mirror the
      drop.
    - Legacy checkpoints (pre-topology format: the protocol state was the
      root pytree) still load — they carry no topology, so the graph
      resumes exactly as attached.
    """
    ts_template = topology_state(graph)

    def _template(ts):
        return {
            "protocol": protocol_state_template,
            "topology": ts,
            "churn_count": np.int64(0),
        }

    try:
        return load(path, _template(ts_template))
    except ValueError as err:
        if "neighbor_mask" in ts_template and not graph.neighbors_complete:
            ts2 = dict(ts_template)
            ts2.pop("neighbor_mask")
            try:
                return load(path, _template(ts2))
            except ValueError:
                pass
        try:
            state, key, rnd, msgs = load(path, protocol_state_template)
        except ValueError:
            raise err  # genuinely mismatched, not just old-format
        payload = {
            "protocol": state,
            "topology": topology_state(graph),  # as-attached (no-op apply)
            "churn_count": np.int64(0),
        }
        return payload, key, rnd, msgs


def save(path: str, state: Any, key: jax.Array, round_index: int,
         message_count: int = 0) -> None:
    """Atomically write (state pytree, PRNG key, round counter, message
    counter) to ``path``, with an embedded content hash ``load`` verifies
    (a bit-flipped or truncated file raises :class:`CheckpointCorrupt`
    instead of resuming from garbage)."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    payload = {f"leaf_{i}": np.asarray(jax.device_get(x)) for i, x in enumerate(leaves)}
    payload["__key__"] = np.asarray(jax.random.key_data(key))
    payload["__round__"] = np.asarray(round_index, dtype=np.int64)
    payload["__messages__"] = np.asarray(message_count, dtype=np.int64)
    payload["__treedef__"] = np.frombuffer(str(treedef).encode(), dtype=np.uint8)
    payload[_DIGEST_KEY] = np.frombuffer(
        _payload_digest(payload).encode(), dtype=np.uint8)
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **payload)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def load(path: str, template: Any, *,
         grow: bool = False) -> Tuple[Any, jax.Array, int, int]:
    """Load a checkpoint written by :func:`save`.

    ``template`` is a state pytree with the same structure (e.g. a freshly
    built ``protocol.init(...)``); its treedef validates the file.
    Returns ``(state, key, round_index, message_count)``.

    ``grow=True`` makes the template repad-compatible: a file whose leaves
    are *smaller* than the template's (written before a ``Graph.grow``
    capacity repad) is zero-extended into the grown shapes via
    :func:`grow_state`; leaves that cannot grow into the template (dtype or
    rank change, shrink) stay a ``ValueError``.

    Integrity: a file carrying the embedded content hash (every checkpoint
    written since the hash landed in the format) is verified against it; a
    truncated, bit-flipped, or otherwise unreadable file raises
    :class:`CheckpointCorrupt` (file + expected/actual hash), never a bare
    ``zipfile``/``zlib`` error. Old hashless files load unverified for
    back-compat. A structure mismatch against ``template`` stays a
    ``ValueError`` — that is a caller error, not file damage.
    """
    try:
        # Read every member eagerly inside the guard: npz members load
        # lazily, so a file truncated mid-member only fails at access time.
        with np.load(path) as data:
            payload = {k: np.asarray(data[k]) for k in data.files}
    except (zipfile.BadZipFile, OSError, EOFError, KeyError, ValueError) as e:
        raise CheckpointCorrupt(
            path, detail=f"{type(e).__name__}: {e}") from e
    if _DIGEST_KEY in payload:
        stored_digest = bytes(payload[_DIGEST_KEY]).decode()
        actual = _payload_digest(payload)
        if stored_digest != actual:
            raise CheckpointCorrupt(path, expected=stored_digest,
                                    actual=actual)
    if "__treedef__" not in payload or "__round__" not in payload \
            or "__key__" not in payload:
        raise CheckpointCorrupt(
            path, detail="missing checkpoint bookkeeping entries "
            "(not a sim/checkpoint.py file, or truncated before the "
            "hash format)")
    _, treedef = jax.tree_util.tree_flatten(template)
    stored = bytes(payload["__treedef__"]).decode()
    if stored != str(treedef):
        raise ValueError(
            f"checkpoint structure mismatch:\n  file: {stored}\n  template: {treedef}"
        )
    n = len([k for k in payload if k.startswith("leaf_")])
    leaves = [payload[f"leaf_{i}"] for i in range(n)]
    state = jax.tree_util.tree_unflatten(treedef, leaves)
    if grow:
        state = grow_state(state, template)
    key = jax.random.wrap_key_data(payload["__key__"])
    messages = int(payload["__messages__"]) if "__messages__" in payload else 0
    return state, key, int(payload["__round__"]), messages


def save_orbax(path: str, state: Any, key: jax.Array, round_index: int,
               message_count: int = 0) -> None:
    """Checkpoint via orbax (sharding-preserving, multi-host-parallel).

    ``path`` is a directory (created/overwritten). All hosts of a
    multi-process job must call this collectively.
    """
    import orbax.checkpoint as ocp

    payload = {
        "state": state,
        # Host numpy, not a device array: a single-device jax.Array is
        # "host-local" to orbax in a multi-process job and refuses to
        # serialize; the key is tiny and identical on every process.
        "key_data": np.asarray(jax.random.key_data(key)),
        # 0-d ndarrays, not np.int64 scalars: this image's orbax rejects
        # numpy GENERIC scalars as unsupported leaf types.
        "round_index": np.asarray(round_index, dtype=np.int64),
        "message_count": np.asarray(message_count, dtype=np.int64),
    }
    # Context-manage: each StandardCheckpointer owns async worker threads;
    # a checkpoint-every-N-rounds loop must not leak one pool per save.
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), payload, force=True)
        ckptr.wait_until_finished()


def load_orbax(path: str, template: Any) -> Tuple[Any, jax.Array, int, int]:
    """Restore a :func:`save_orbax` checkpoint.

    ``template`` supplies structure, dtypes, AND shardings: pass a state
    built the way the resumed run will use it (e.g. on the same mesh) and
    the restored arrays land sharded the same way, no host round-trip.
    Returns ``(state, key, round_index, message_count)``.
    """
    import orbax.checkpoint as ocp

    # Every leaf gets an explicit sharding: omitting one makes orbax fall
    # back to the sharding recorded at save time, which it documents as
    # unsafe across device topologies — exactly the resume-on-a-different-
    # slice case this API exists for. Leaves without a sharding (and the
    # bookkeeping scalars) are replicated over the template's mesh when it
    # has one, else placed on the default device.
    meshes = [
        leaf.sharding.mesh
        for leaf in jax.tree.leaves(template)
        if isinstance(getattr(leaf, "sharding", None), jax.sharding.NamedSharding)
    ]
    if meshes:
        default_sharding = jax.sharding.NamedSharding(
            meshes[0], jax.sharding.PartitionSpec()
        )
    else:
        default_sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def abstract(x):
        x = jax.numpy.asarray(x)
        sharding = getattr(x, "sharding", None) or default_sharding
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    key_data = jax.random.key_data(jax.random.key(0))
    target = {
        "state": jax.tree.map(abstract, template),
        "key_data": jax.ShapeDtypeStruct(
            key_data.shape, key_data.dtype, sharding=default_sharding
        ),
        "round_index": jax.ShapeDtypeStruct((), np.int64, sharding=default_sharding),
        "message_count": jax.ShapeDtypeStruct((), np.int64, sharding=default_sharding),
    }
    with ocp.StandardCheckpointer() as ckptr:
        restored = ckptr.restore(os.path.abspath(path), target)
    key = jax.random.wrap_key_data(restored["key_data"])
    return (
        restored["state"],
        key,
        int(restored["round_index"]),
        int(restored["message_count"]),
    )


# ------------------------------------------------------ graph persistence

#: Array-valued Graph fields serialized by save_graph (optionals skipped
#: when None); static ints/bools/tuples travel in the JSON meta record.
_GRAPH_ARRAYS = (
    "senders", "receivers", "edge_mask", "node_mask", "in_degree",
    "out_degree", "neighbors", "neighbor_mask", "dyn_senders",
    "dyn_receivers", "dyn_mask", "src_eid", "src_offsets", "edge_weight",
    "neighbor_weight", "layout_perm", "layout_inv",
)


#: The ring layout's arrays (parallel/ringlayout.RingLayout), saved as
#: ``ring_<name>`` and loaded back as host arrays.
_RING_ARRAYS = ("src", "dst", "offsets", "diag_masks", "mxu_src", "mxu_dst",
                "mxu_mask")


def save_graph(path: str, graph) -> None:
    """Atomically persist a built :class:`~p2pnetwork_tpu.sim.graph.Graph`
    — including kernel layouts (blocked/hybrid/source-CSR, the host-side
    ring layout), weights, the dynamic region, and any liveness re-masking
    — as one ``.npz``.

    The complement of the state checkpoints above: graph CONSTRUCTION is
    the host-side cost at scale (tens of seconds for the 100M-edge build,
    BENCH.md), so a pipeline that reuses a topology should pay it once.
    No pickle: arrays plus a JSON record of the static fields.
    """
    import json

    payload: Dict[str, Any] = {}
    meta: Dict[str, Any] = {
        "version": 1,
        "n_nodes": graph.n_nodes,
        "n_edges": graph.n_edges,
        "neighbors_complete": graph.neighbors_complete,
        "max_degree_cap": graph.max_degree_cap,
        "edge_pad_multiple": graph.edge_pad_multiple,
        "max_in_span": graph.max_in_span,
        "max_out_span": graph.max_out_span,
    }
    # One pytree transfer for every present array rather than a
    # device_get per field: device_get batches the whole dict into a
    # single device->host round trip (graftlint host-sync-in-loop).
    present = {name: getattr(graph, name) for name in _GRAPH_ARRAYS
               if getattr(graph, name) is not None}
    payload.update({name: np.asarray(v)
                    for name, v in jax.device_get(present).items()})
    if graph.blocked is not None:
        meta["blocked_block"] = graph.blocked.block
        payload["blocked_src"] = np.asarray(jax.device_get(graph.blocked.src))
        payload["blocked_local_dst"] = np.asarray(
            jax.device_get(graph.blocked.local_dst))
        payload["blocked_mask"] = np.asarray(
            jax.device_get(graph.blocked.mask))
    if graph.skew is not None:
        payload["skew_src"] = np.asarray(jax.device_get(graph.skew.src))
        payload["skew_mask"] = np.asarray(jax.device_get(graph.skew.mask))
        payload["skew_owner"] = np.asarray(jax.device_get(graph.skew.owner))
        payload["skew_start"] = np.asarray(jax.device_get(graph.skew.start))
        if graph.skew.weight is not None:
            payload["skew_weight"] = np.asarray(
                jax.device_get(graph.skew.weight))
    if graph.hybrid is not None:
        meta["hybrid_offsets"] = list(graph.hybrid.offsets)
        meta["hybrid_n"] = graph.hybrid.n
        payload["hybrid_masks"] = np.asarray(
            jax.device_get(graph.hybrid.masks))
        rem = graph.hybrid.remainder
        if rem is not None:
            meta["hybrid_rem_block"] = rem.block
            payload["hybrid_rem_src"] = np.asarray(jax.device_get(rem.src))
            payload["hybrid_rem_local_dst"] = np.asarray(
                jax.device_get(rem.local_dst))
            payload["hybrid_rem_mask"] = np.asarray(jax.device_get(rem.mask))
    ring = graph.ring
    if ring is not None:
        meta["ring"] = {"n_shards": ring.n_shards, "block": ring.block,
                        "n_edges": ring.n_edges, "hybrid": ring.hybrid,
                        "max_diags": ring.max_diags,
                        "min_count": ring.min_count,
                        "diag_pieces": [list(p) for p in ring.diag_pieces]}
        payload.update({f"ring_{name}": getattr(ring, name)
                        for name in _RING_ARRAYS
                        if getattr(ring, name) is not None})
    payload["__meta__"] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8)

    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **payload)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def load_graph(path: str):
    """Load a graph written by :func:`save_graph` (arrays land on the
    default device lazily, via the first jitted use)."""
    import json

    import jax.numpy as jnp

    from p2pnetwork_tpu.ops.blocked import BlockedEdges
    from p2pnetwork_tpu.ops.diag import HybridEdges
    from p2pnetwork_tpu.sim.graph import Graph

    with np.load(path) as data:
        meta = json.loads(bytes(data["__meta__"]).decode())
        if meta.get("version") != 1:
            raise ValueError(f"unknown graph file version: {meta.get('version')}")
        # Absent arrays were None at save time and must load back as None
        # explicitly: neighbors/neighbor_mask have no dataclass default, so
        # omitting them raises for any graph saved with
        # build_neighbor_table=False (the 10M bench config).
        fields: Dict[str, Any] = {
            name: jnp.asarray(data[name]) if name in data.files else None
            for name in _GRAPH_ARRAYS
        }
        blocked = None
        if "blocked_src" in data.files:
            blocked = BlockedEdges(
                src=jnp.asarray(data["blocked_src"]),
                local_dst=jnp.asarray(data["blocked_local_dst"]),
                mask=jnp.asarray(data["blocked_mask"]),
                block=int(meta["blocked_block"]),
            )
        skew = None
        if "skew_src" in data.files:
            from p2pnetwork_tpu.ops.skew import SkewTable

            skew = SkewTable(
                src=jnp.asarray(data["skew_src"]),
                mask=jnp.asarray(data["skew_mask"]),
                owner=jnp.asarray(data["skew_owner"]),
                start=jnp.asarray(data["skew_start"]),
                weight=(jnp.asarray(data["skew_weight"])
                        if "skew_weight" in data.files else None),
            )
        hybrid = None
        if "hybrid_masks" in data.files:
            rem = None
            if "hybrid_rem_src" in data.files:
                rem = BlockedEdges(
                    src=jnp.asarray(data["hybrid_rem_src"]),
                    local_dst=jnp.asarray(data["hybrid_rem_local_dst"]),
                    mask=jnp.asarray(data["hybrid_rem_mask"]),
                    block=int(meta["hybrid_rem_block"]),
                )
            masks = data["hybrid_masks"]
            hybrid = HybridEdges(
                masks=jnp.asarray(masks),
                remainder=rem,
                offsets=tuple(meta["hybrid_offsets"]),
                n=int(meta["hybrid_n"]),
                n_diag_edges=int(masks.sum()),
            )
        ring = None
        if "ring" in meta:
            from p2pnetwork_tpu.parallel.ringlayout import RingLayout

            rm = meta["ring"]
            ring = RingLayout(
                n_shards=int(rm["n_shards"]), block=int(rm["block"]),
                n_edges=int(rm["n_edges"]), hybrid=bool(rm["hybrid"]),
                max_diags=int(rm["max_diags"]), min_count=rm["min_count"],
                diag_pieces=tuple(tuple(p) for p in rm["diag_pieces"]),
                **{name: (data[f"ring_{name}"]
                          if f"ring_{name}" in data.files else None)
                   for name in _RING_ARRAYS})
        cap = meta.get("max_degree_cap")  # absent in pre-cap files
        return Graph(
            n_nodes=int(meta["n_nodes"]),
            n_edges=int(meta["n_edges"]),
            neighbors_complete=bool(meta["neighbors_complete"]),
            max_degree_cap=None if cap is None else int(cap),
            edge_pad_multiple=int(meta.get("edge_pad_multiple", 128)),
            max_in_span=int(meta["max_in_span"]),
            max_out_span=int(meta["max_out_span"]),
            blocked=blocked,
            hybrid=hybrid,
            skew=skew,
            ring=ring,
            **fields,
        )

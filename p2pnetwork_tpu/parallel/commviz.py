"""Collective-placement diagnostics: parse compiled HLO, classify traffic.

The multi-chip claims this framework makes — node-extent-only payloads on
the GSPMD auto path, ICI-confined bulk traffic with DCN as a bounded
remainder, the ring's 1/per_host boundary-hop structure — are properties
of COMPILED programs, so the evidence lives in HLO text. This module is
the one parser both the test suite (tests/test_auto_comm.py,
tests/test_mesh2d_comm.py) and the shipped diagnostics/examples
(examples/hierarchical_mesh_demo.py) use, so the pinned assertions and
the printed numbers cannot drift apart.

Handles XLA's iota replica-group form (``[G,S]<=[dims]T(perm)``), the
literal form (``{{0,1},{2,3}}``), variadic/async collectives, and
collective-permutes (which carry ``source_target_pairs`` instead of
replica groups — skipping them would blind any DCN budget to cross-host
permute traffic).

The reference has nothing comparable to diagnose — its transport is one
blocking socket per peer [ref: p2pnetwork/nodeconnection.py:38-44].
"""

from __future__ import annotations

import re
from typing import Callable, List, Tuple

import numpy as np

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}

# Matches the full (possibly tuple/variadic) result type of a collective —
# XLA's collective combiner fuses ops into variadic forms like
#   (s32[], s32[], f32[4096]) all-reduce(...)
# and async pairs use the -start suffix; both must stay visible here or an
# edge-extent payload could hide inside a fused/async op.
COLLECTIVE_LINE = re.compile(
    r"=\s+(.+?)\s+"
    r"(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter)"
    r"(?:-start)?\("
)
_SHAPE = re.compile(r"(\w+)\[([0-9,]*)\]")
_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_LITERAL = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_PAIRS = re.compile(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}")


def collectives(hlo_text: str) -> List[Tuple[str, str, tuple, int]]:
    """``[(op, dtype, shape, bytes)]`` — one entry per tensor component of
    every collective in the module, tuple results flattened."""
    out = []
    for type_str, op in COLLECTIVE_LINE.findall(hlo_text):
        for dtype, shape in _SHAPE.findall(type_str):
            if dtype not in _DTYPE_BYTES:
                continue  # e.g. token types
            dims = [int(d) for d in shape.split(",") if d] or [1]  # graftlint: ignore[host-sync-in-loop] -- regex capture strings, not jax arrays
            out.append((op, dtype, tuple(dims),
                        int(np.prod(dims)) * _DTYPE_BYTES[dtype]))  # graftlint: ignore[host-sync-in-loop] -- host ints from parsed HLO text
    return out


def decode_groups(line: str) -> List[tuple]:
    """Replica groups of one HLO collective line as a list of tuples."""
    m = _IOTA.search(line)
    if m:
        ng, gs = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        perm = ([int(d) for d in m.group(4).split(",")]
                if m.group(4) else list(range(len(dims))))
        devs = np.arange(int(np.prod(dims))).reshape(dims).transpose(perm)
        return [tuple(int(x) for x in g) for g in devs.reshape(ng, gs)]
    m = _LITERAL.search(line)
    if m:
        return [tuple(int(x) for x in grp.split(",") if x.strip())
                for grp in m.group(1).strip("{}").split("},{")]
    return []


def permute_pairs(line: str) -> List[Tuple[int, int]]:
    """source->target pairs of one collective-permute HLO line."""
    m = _PAIRS.search(line)
    if not m:
        return []
    return [tuple(int(x) for x in p.split(","))
            for p in m.group(1).strip("{}").split("},{")]


def classify_collective_bytes(hlo: str,
                              host_of: Callable[[int], int]) -> Tuple[int, int]:
    """``(within_host_bytes, cross_host_bytes)`` over every collective in
    the module — replica-group collectives classified by decoded groups,
    collective-permutes by their source->target pairs. ``host_of`` maps a
    linearized device id to its host/slice index."""
    within = cross = 0
    for ln in hlo.splitlines():
        if not COLLECTIVE_LINE.search(ln):
            continue
        groups = decode_groups(ln)
        pairs = permute_pairs(ln)
        if not groups and not pairs:
            continue
        nbytes = sum(c[3] for c in collectives(ln))
        crossing = (any(len({host_of(d) for d in g}) > 1 for g in groups)
                    or any(host_of(a) != host_of(b) for a, b in pairs))
        if crossing:
            cross += nbytes
        else:
            within += nbytes
    return within, cross


def record_traffic(hlo: str, host_of: Callable[[int], int], *,
                   program: str = "default",
                   registry=None) -> Tuple[int, int]:
    """Classify ``hlo``'s collective traffic and publish it as gauges in
    the telemetry registry: ``comm_collective_bytes{program, placement}``
    with ``placement="within_host"`` (ICI-confined on a TPU slice) and
    ``"cross_host"`` (the DCN remainder). Returns the same
    ``(within, cross)`` tuple as :func:`classify_collective_bytes`, so
    diagnostics can keep their printed numbers and the registry's budget
    gauges from drifting apart — one classification, two consumers."""
    from p2pnetwork_tpu import telemetry

    within, cross = classify_collective_bytes(hlo, host_of)
    reg = registry or telemetry.default_registry()
    g = reg.gauge(
        "comm_collective_bytes",
        "Collective payload bytes of a compiled program by interconnect "
        "placement (within_host ~ ICI budget, cross_host ~ DCN budget).",
        ("program", "placement"))
    g.labels(program, "within_host").set(within)
    g.labels(program, "cross_host").set(cross)
    return within, cross


# --------------------------------------------------- Pallas ring-DMA census
#
# The pallas comm backend (ops/pallas_ring.py) moves the halo as
# ``make_async_remote_copy`` DMAs issued from inside kernels. Those are
# INVISIBLE to both censuses above: the jaxpr shows one opaque
# ``pallas_call`` eqn (no ppermute), and the interpret-mode CPU lowering
# compiles to callbacks (no collective-permute in HLO) — so without this
# section a Pallas-comm program would read as zero ICI bytes and silently
# pass every comm budget. The handle is the kernel NAME: every ring-DMA
# pallas_call is named ``ring_halo_*`` (pallas_ring.RING_DMA_MARKER), the
# name lands in the eqn's ``name`` param, and by convention
# the kernel's FIRST output is the DMA payload (the received block), so
# ``outvars[0]`` prices the hop — one payload copy per hop, the same
# model a ppermute is priced at.

#: Substring marking a ring-DMA kernel's pallas_call (kept in lockstep
#: with ops/pallas_ring.RING_DMA_MARKER — pinned by tests/test_ring.py;
#: duplicated here so this module stays importable without jax/pallas).
RING_DMA_MARKER = "ring_halo"

#: The jaxpr-level pseudo-collective key ring DMAs are censused under
#: (beside ppermute/psum/... in graftaudit's collective census).
RING_DMA_KEY = "ring_dma"


def ring_model_bytes(prim: str, nbytes: int, axis_size: int) -> int:
    """The documented static ICI byte model of one collective occurrence
    on an ``axis_size``-way ring: ppermute — and a ring-DMA hop — moves
    each operand once; psum (ring all-reduce) moves ``2·(S-1)/S ≈ 2``
    copies; all_gather moves ``S-1`` shard-sized pieces. One model, two
    consumers: graftaudit's jaxpr census ratchet
    (analysis/ir/registry.py) and the comm estimates below."""
    s = max(axis_size, 2)
    if prim in ("ppermute", RING_DMA_KEY):
        return nbytes
    if prim in ("psum", "pmax", "pmin"):
        return int(nbytes * 2 * (s - 1) / s)
    if prim in ("all_gather", "all_to_all", "reduce_scatter"):
        return nbytes * (s - 1)
    return nbytes


def ring_dma_payload_bytes(eqn) -> int:
    """DMA payload bytes of one jaxpr eqn: the first output's extent when
    the eqn is a ring-DMA ``pallas_call`` (see RING_DMA_MARKER), else 0.
    Takes a ``jax.core.JaxprEqn`` — jax is imported by the caller."""
    if eqn.primitive.name != "pallas_call":
        return 0
    if RING_DMA_MARKER not in str(eqn.params.get("name", "")):
        return 0
    aval = eqn.outvars[0].aval
    import numpy as _np

    return int(_np.prod(aval.shape, dtype=_np.int64) or 1) * aval.dtype.itemsize


def jaxpr_comm_census(fn, args, axis_size: int) -> dict:
    """Trace ``fn(*args)`` abstractly and census its cross-device traffic
    under the ring byte model: ``{prim: {"count", "bytes"}}`` over every
    collective primitive PLUS ``"ring_dma"`` for Pallas ring-DMA kernels
    — the estimate the bench ``multichip`` column and the comm-budget
    tests read for BOTH comm backends of the sharded path.

    Counts are weighted by statically-known trip counts: a collective
    inside a ``lax.scan`` / ``fori`` body is multiplied by the scan
    length — the ring pass is a length-``S-1`` scan of one hop, so a
    ring program's totals price all ``S-1`` hops per pass, not one.
    ``while_loop`` bodies (trip count dynamic) count once, so on the
    run-to-* loops the totals are PER-ROUND bytes."""
    import jax

    from p2pnetwork_tpu.analysis.ir.registry import COLLECTIVE_PRIMS

    closed = jax.make_jaxpr(fn)(*args)
    out: dict = {}

    def bump(key, nbytes, times):
        rec = out.setdefault(key, {"count": 0, "bytes": 0})
        rec["count"] += times
        rec["bytes"] += nbytes * times

    def visit(jaxpr, times):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in COLLECTIVE_PRIMS:
                nbytes = sum(  # graftlint: ignore[host-sync-in-loop] -- aval shapes are host ints (abstract trace), no device values
                    int(np.prod(v.aval.shape, dtype=np.int64) or 1)
                    * v.aval.dtype.itemsize
                    for v in eqn.invars if hasattr(v, "aval"))
                bump(prim, ring_model_bytes(prim, nbytes, axis_size), times)
            else:
                payload = ring_dma_payload_bytes(eqn)
                if payload:
                    bump(RING_DMA_KEY,
                         ring_model_bytes(RING_DMA_KEY, payload, axis_size),
                         times)
            inner_times = times
            if prim == "scan":
                inner_times = times * int(eqn.params.get("length", 1))  # graftlint: ignore[host-sync-in-loop] -- scan length is a static Python int in jaxpr params
            for v in eqn.params.values():
                for x in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(x, "eqns"):
                        visit(x, inner_times)
                    elif hasattr(getattr(x, "jaxpr", None), "eqns"):
                        visit(x.jaxpr, inner_times)

    visit(closed.jaxpr, 1)
    return out


def ici_bytes_estimate(fn, args, axis_size: int) -> int:
    """Total modeled ICI bytes of one traced program (collectives + ring
    DMAs) — the single number comm-budget assertions compare across the
    ppermute and pallas backends of the same ring program."""
    return sum(rec["bytes"]
               for rec in jaxpr_comm_census(fn, args, axis_size).values())


def ring_hop_classes(hlo: str, host_of: Callable[[int], int]):
    """``(within_hops, cross_hops, permute_pair_lists)`` over every
    collective-permute of a compiled ring program."""
    within = cross = 0
    per_permute = []
    for ln in hlo.splitlines():
        if "collective-permute" not in ln:
            continue
        pairs = permute_pairs(ln)
        if not pairs:
            continue
        per_permute.append(pairs)
        for a, b in pairs:
            if host_of(a) == host_of(b):
                within += 1
            else:
                cross += 1
    return within, cross, per_permute


def lower_ring_flood_hlo(n: int = 1024, n_devices: int = 8,
                         rounds: int = 3, comm: str = "ppermute") -> str:
    """Compile the real sharded ring flood over an ``n_devices`` ring mesh
    and return its HLO text — the program whose hop placement
    :func:`ring_hop_classes` reads. ``comm`` selects the halo backend;
    note the pallas backend's DMA hops do NOT appear as HLO collectives
    (use :func:`jaxpr_comm_census` for backend-comparable byte
    estimates)."""
    from p2pnetwork_tpu.parallel import mesh as M, sharded
    from p2pnetwork_tpu.sim import graph as G

    g = G.watts_strogatz(n, 6, 0.2, seed=0)
    mesh = M.ring_mesh(n_devices)
    sg = sharded.shard_graph(g, mesh)
    fn = sharded._flood_fn(mesh, mesh.axis_names[0], sg.n_shards,
                           sg.block, rounds, sg.diag_pieces, sg.mxu_block,
                           comm)
    seen0 = sharded._flood_seed(sg, 0)
    return fn.lower(
        sg.bkt_src, sg.bkt_dst, sg.bkt_mask, *sharded._dyn_or_empty(sg),
        *sharded._mxu_or_empty(sg), sharded._diag_masks_or_empty(sg),
        sg.node_mask, sg.out_degree, seen0, seen0,
    ).compile().as_text()

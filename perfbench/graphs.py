"""A configuration's overlay: the benchmark's edges, the program's graph.

The benchmark generates the edge list from the configuration
(``overlays.py``), hands it to the program's public builder
(``sim.graph.from_edges`` with the configuration's layout flags) and keeps
its sender CSR for the reference. The built layout is kept in the
program's own layout cache (``sim/layoutcache.py``) at a fixed path inside
the checkout, keyed on the program's builder sources, this file,
``overlays.py`` and the configuration's overlay (``overlays.spec``).

Placement. A configuration without a ``placement`` key runs on one chip:
the program's graph is built (or loaded) onto the default device. One with
``"placement": {"mesh": "ring"}`` runs on a ring of the cell's ``chips``
(more than one): the graph is built (or loaded) on the host CPU and handed
on as NumPy arrays, so no chip ever holds the whole overlay; the program
shards it when the driver attaches it to the ring
(``JaxSimNode.attach_simulation(..., mesh=...)``).
"""

from __future__ import annotations

import os
import time

import numpy as np

import harness
import overlays
from reference import bfs


class Overlay:
    """The benchmark's copy of the overlay, for the reference: the
    undirected pairs (4 bytes a directed edge) until the first use of
    ``edges`` turns them into the sender CSR of the directed edges
    (``bfs.sender_csr``). That use comes after the window, so the CSR's
    seconds are the check's, not set-up's."""

    def __init__(self, lo: np.ndarray, hi: np.ndarray, n: int):
        self.n = n
        self.directed_edges = 2 * int(lo.size)
        self.node_mask = np.ones(n, bool)
        self._pairs, self._csr = (lo, hi), None

    @property
    def edges(self):
        if self._csr is None:
            self._csr = bfs.sender_csr(*overlays.directed(*self._pairs),
                                       self.n)
            self._pairs = None
        return (*self._csr, self.node_mask)

    @property
    def out_degree(self) -> np.ndarray:
        return np.diff(self.edges[0])


def mesh(config: dict, chips: int):
    """The program's ring over the cell's ``chips``, or ``None`` for a
    configuration without a placement. Refuses a placement that is not a
    ring, or a ring on one chip."""
    place = config.get("placement")
    if place is None:
        return None
    if place != {"mesh": "ring"}:
        raise ValueError(f"{config['name']}: placement must be "
                         f'{{"mesh": "ring"}}, not {place}')
    if int(chips) < 2:
        raise ValueError(f"{config['name']}: a ring placement needs more "
                         f"than one chip; the cell asks for {chips}")
    from p2pnetwork_tpu.parallel.mesh import ring_mesh

    return ring_mesh(int(chips))


def host_device():
    """The device a ring configuration's graph is built on: the host CPU
    (the last, where the CPU backend is given several devices)."""
    import jax

    return jax.devices("cpu")[-1]


def _host_graph(get):
    """``get()``'s graph built on the host CPU and returned as NumPy
    arrays: held by no device."""
    import jax

    with jax.default_device(host_device()):
        graph, *rest = get()
    return (jax.tree_util.tree_map(np.asarray, graph), *rest)


def build(config: dict, *, on_host: bool = False, cache: bool = True):
    """``(program_graph, overlay, info)`` for a configuration; ``on_host``
    builds or loads the program's graph on the host (a ring placement)."""
    from p2pnetwork_tpu.sim import graph as G
    from p2pnetwork_tpu.sim import layoutcache

    t0 = time.perf_counter()
    lo, hi, n = overlays.undirected_edges(config)
    overlay = Overlay(lo, hi, n)
    gen_s = time.perf_counter() - t0

    def make():
        src, dst = overlays.directed(lo, hi)
        return G.from_edges(src, dst, n, **config["layout"])

    here = os.path.dirname(os.path.abspath(__file__))

    def cached():
        return layoutcache.cached_graph(
            f"perfbench_{config['name']}", make,
            cache_dir=harness.LAYOUT_CACHE_DIR,
            params=overlays.spec(config), enabled=cache,
            extra_sources=(os.path.join(here, "graphs.py"),
                           os.path.join(here, "overlays.py")))

    graph, build_s, hit = _host_graph(cached) if on_host else cached()
    info = {"nodes": n, "directed_edges": overlay.directed_edges,
            "edges_s": gen_s, "graph_s": build_s,
            "layout_cache_hit": hit, "on_host": on_host}
    return graph, overlay, info

"""Content-addressed persistence of built graph layouts.

Graph construction is the host-side scale bottleneck (BENCH_r02: the 1M
run finishes in 0.133 s while ``graph_build_s`` is 5.31), so a built
layout — COO + neighbor tables + kernel layouts + CSR, everything
``sim/checkpoint.py`` ``save_graph`` serializes — should be paid for once
per (builder code, topology params, layout flags) and reloaded
thereafter. bench.py grew exactly this machinery privately
(``_layout_fingerprint`` / ``_cached_graph``); this module is the
library-level generalization bench, the supervise plane, and tests all
share.

The cache is content-addressed: an entry's filename carries a
:func:`fingerprint` of (a) every source file whose code determines the
built arrays — the graph builder, the reorder pass, the topology
generators, the kernel-layout builders, the native radix/merge kernels
and their bindings, the serializer — and (b) the caller-supplied
``params`` (topology arguments and layout flags, the reorder strategy
included). Editing any of those sources, or changing a param, changes
the name, so a stale layout can never be loaded as fresh — it is simply
never found (delete old files at leisure; ``clear()`` does it for you).

Fingerprints are pure stdlib (file bytes + canonical JSON); jax enters
only inside :func:`cached_graph`, where graphs are actually
(de)serialized through ``sim/checkpoint.py`` — bench's stdlib-only
parent process never calls either (its stage children do).
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Callable, Iterable, Optional, Tuple

from p2pnetwork_tpu import telemetry

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Package-relative sources whose code determines a built graph's arrays
#: and kernel layouts. This set is the fix for the bench stale-cache bug:
#: the old bench-private fingerprint omitted the native radix sort
#: (graphcore.cpp + its bindings) and the topology generators, so edits
#: there silently reused stale cached graphs.
DEFAULT_SOURCES = (
    "sim/graph.py",
    "sim/layout.py",
    "sim/topology.py",
    "sim/checkpoint.py",
    "ops/blocked.py",
    "ops/diag.py",
    "ops/skew.py",
    "ops/bitset.py",
    "ops/frontier.py",
    "parallel/ringlayout.py",
    "native/graphcore.cpp",
    "native/__init__.py",
)


def fingerprint(*, params: Optional[dict] = None,
                extra_sources: Iterable[str] = (),
                digest_size: int = 6) -> str:
    """Hex digest naming one layout configuration.

    Folds the bytes of every :data:`DEFAULT_SOURCES` file (package-
    relative) plus any ``extra_sources`` (absolute paths — e.g. the
    caller script whose build invocation holds the kwargs), then the
    canonical JSON of ``params``. Pass every topology argument and
    layout flag that shapes the build — the reorder strategy included —
    as ``params``; two configurations differing only there must not
    share an entry.
    """
    h = hashlib.blake2b(digest_size=digest_size)
    for rel in DEFAULT_SOURCES:
        try:
            with open(os.path.join(_PKG_DIR, rel), "rb") as f:
                h.update(f.read())
        except OSError:
            # Not every install ships every source (a .py-only wheel has
            # no graphcore.cpp — the same case the native loader falls
            # back on). Absence is itself fingerprinted, so a source
            # (dis)appearing still invalidates; the cache must degrade,
            # never crash the caller's build.
            h.update(f"<absent:{rel}>".encode())
    for path in extra_sources:
        # Caller-supplied sources stay strict: a typo'd path here would
        # silently fingerprint nothing and UNDER-invalidate.
        with open(path, "rb") as f:
            h.update(f.read())
    if params:
        h.update(json.dumps(params, sort_keys=True, default=str).encode())
    return h.hexdigest()


def default_cache_dir() -> str:
    """``$P2P_LAYOUT_CACHE_DIR``, else a per-user cache directory."""
    env = os.environ.get("P2P_LAYOUT_CACHE_DIR")
    if env:
        return env
    cache = os.environ.get("XDG_CACHE_HOME",
                           os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(cache, "p2pnetwork_tpu", "layouts")


def entry_path(name: str, *, cache_dir: Optional[str] = None,
               params: Optional[dict] = None,
               extra_sources: Iterable[str] = ()) -> str:
    """The content-addressed file a configuration persists to."""
    fp = fingerprint(params=params, extra_sources=extra_sources)
    return os.path.join(cache_dir or default_cache_dir(),
                        f"{name}_{fp}.npz")


def _miss_counter():
    return telemetry.default_registry().counter(
        "layout_cache_miss_total",
        "Layout-cache misses by cause; every miss costs a full graph "
        "build.", ("reason",))


def cached_graph(name: str, build: Callable, *,
                 cache_dir: Optional[str] = None,
                 params: Optional[dict] = None,
                 extra_sources: Iterable[str] = (),
                 enabled: Optional[bool] = None,
                 on_miss: Optional[Callable] = None,
                 log: Optional[Callable[[str], None]] = None) -> Tuple:
    """Load the persisted layout for ``(name, fingerprint)`` or build and
    persist it. Returns ``(graph, seconds, from_cache)``.

    Any cache failure (missing file, version skew, truncated write) falls
    back to a fresh ``build()`` — the cache can only make callers faster,
    never wrong: the fingerprint pins the builder code and params, and
    builds are seed-deterministic, so cached and rebuilt graphs are
    identical arrays. Every fallback is REPORTED, never swallowed: the
    ``layout_cache_miss_total{reason=missing|corrupt|disabled}`` counter
    plus the optional ``on_miss(reason, path, error)`` callback (bench
    mirrors it into its structured warning events). ``enabled`` defaults
    to ``$P2P_LAYOUT_CACHE != "0"``; ``log`` (if given) receives one
    info line per load/store.
    """
    from p2pnetwork_tpu.sim import checkpoint as ckpt

    if enabled is None:
        enabled = os.environ.get("P2P_LAYOUT_CACHE", "1") != "0"
    cache_dir = cache_dir or default_cache_dir()
    path = None
    if enabled:  # a disabled cache computes no fingerprint at all
        path = entry_path(name, cache_dir=cache_dir, params=params,
                          extra_sources=extra_sources)

    def _miss(reason: str, error: Optional[str] = None) -> None:
        _miss_counter().labels(reason=reason).inc()
        if on_miss is not None:
            on_miss(reason, path, error)

    if enabled and os.path.exists(path):
        try:
            t0 = time.perf_counter()
            g = ckpt.load_graph(path)
            dt = time.perf_counter() - t0
            if log is not None:
                log(f"{name}: loaded cached graph in {dt:.1f}s ({path})")
            return g, dt, True
        except Exception as e:
            _miss("corrupt", f"{type(e).__name__}: {e}")
    elif enabled:
        _miss("missing")
    else:
        _miss("disabled")
    t0 = time.perf_counter()
    g = build()
    dt = time.perf_counter() - t0
    if enabled:
        try:
            os.makedirs(cache_dir, exist_ok=True)
            ckpt.save_graph(path, g)
            if log is not None:
                log(f"{name}: built in {dt:.1f}s, cached to {path}")
        except Exception as e:  # a full disk must not sink the caller
            if log is not None:
                log(f"{name}: cache save failed ({type(e).__name__}: {e})")
    return g, dt, False


def clear(cache_dir: Optional[str] = None) -> int:
    """Delete every ``.npz`` entry under the cache dir (current AND stale
    fingerprints — the invalidation workflow after intentional layout
    changes). Returns the number of files removed."""
    cache_dir = cache_dir or default_cache_dir()
    removed = 0
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return 0
    for fname in names:
        if fname.endswith(".npz"):
            try:
                os.unlink(os.path.join(cache_dir, fname))
                removed += 1
            except OSError:
                pass
    return removed

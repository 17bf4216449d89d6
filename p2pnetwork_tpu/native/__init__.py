"""Native (C++) host kernels with transparent numpy fallback.

The device hot path of this framework is XLA/Pallas; the *host* hot path is
graph construction — sorting multi-million-edge lists and deduplicating
undirected pairs, which dominates wall clock at BASELINE scale when done
with numpy's comparison sorts. ``graphcore.cpp`` implements them as LSD
radix passes; this module compiles it on first use (``g++ -O3 -shared``,
cached next to the source under a name keyed on the source's sha256)
and binds it with ctypes — no build system, no
binding generator, and every entry point silently falls back to numpy when
a compiler is unavailable (``force_fallback()`` pins that for tests).

The reference has no native code at all (SURVEY.md section 2.1); this layer
exists because the new framework builds graphs five orders of magnitude
larger than a reference process would hold sockets.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

from p2pnetwork_tpu import concurrency

_SRC = Path(__file__).with_name("graphcore.cpp")

_lock = concurrency.lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_forced_fallback = False


def force_fallback(enabled: bool = True) -> None:
    """Disable (or re-enable) the native library — numpy paths only."""
    global _forced_fallback
    _forced_fallback = enabled


def _so_candidates(digest: str):
    """Where the library built from the source with this ``digest`` may
    live: next to the source (dev checkout), else a per-user cache dir
    (read-only installs). The name carries the digest, so a library built
    from other source is never loaded, whatever its mtime."""
    name = f"libgraphcore-{digest}.so"
    yield _SRC.with_name(name)
    cache = Path(os.environ.get("XDG_CACHE_HOME", Path.home() / ".cache"))
    yield cache / "p2pnetwork_tpu" / name


def _compile() -> Optional[Path]:
    """Compile (or find cached) libgraphcore-<digest>.so; None means use
    numpy.

    Every filesystem/toolchain failure is swallowed — the contract of this
    module is a silent numpy fallback, never an import-time crash.
    """
    try:
        digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    except OSError:
        return None  # source not shipped (e.g. a .py-only wheel)
    for so in _so_candidates(digest):
        if so.exists():
            return so
    for so in _so_candidates(digest):
        try:
            so.parent.mkdir(parents=True, exist_ok=True)
            # Build into a temp file then rename: concurrent importers must
            # never dlopen a half-written .so.
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=str(so.parent))
            os.close(fd)
        except OSError:
            continue
        cmd = ["g++", "-O3", "-shared", "-fPIC", str(_SRC), "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
            return so
        except (OSError, subprocess.SubprocessError):
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None  # compiler failure will not differ by directory
    return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _forced_fallback:
        return None
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _compile()  # graftlint: ignore[blocking-under-lock] -- the lock EXISTS to serialize the build-once; concurrent callers must block until the .so exists
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(str(so))  # graftlint: ignore[lock-open-call] -- same build-once critical section
            i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")  # graftlint: ignore[lock-open-call] -- pure ctypes type ctor
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")  # graftlint: ignore[lock-open-call] -- pure ctypes type ctor
            u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")  # graftlint: ignore[lock-open-call] -- pure ctypes type ctor
            lib.gc_sort_pairs_i32.argtypes = [i32p, i32p, ctypes.c_int64, i32p, i32p]
            lib.gc_sort_pairs_i32.restype = None
            lib.gc_sort_unique_i64.argtypes = [i64p, ctypes.c_int64]
            lib.gc_sort_unique_i64.restype = ctypes.c_int64
            lib.gc_delta_antimerge_i32.argtypes = [
                i32p, i32p, u8p, ctypes.c_int64, i32p, i32p, ctypes.c_int64,
                u8p, i32p]
            lib.gc_delta_antimerge_i32.restype = ctypes.c_int64
            lib.gc_delta_merge_i32.argtypes = [
                i32p, i32p, u8p, ctypes.c_int64, i32p, i32p, ctypes.c_int64,
                i32p, i32p, i32p, i32p]
            lib.gc_delta_merge_i32.restype = ctypes.c_int64
            lib.gc_map_filter_i32.argtypes = [i32p, ctypes.c_int64, i32p, i32p]
            lib.gc_map_filter_i32.restype = ctypes.c_int64
            lib.gc_merge_eids_by_sender_i32.argtypes = [
                i32p, i32p, ctypes.c_int64, i32p, ctypes.c_int64, i32p]
            lib.gc_merge_eids_by_sender_i32.restype = None
            _lib = lib
        except OSError:
            _lib = None
        return _lib


def available() -> bool:
    """True when the native library is loaded (compiles on first call)."""
    return _load() is not None


def sort_pairs(keys: np.ndarray, vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Stable sort of (keys, vals) by non-negative int32 ``keys``.

    Equivalent to ``order = np.argsort(keys, kind="stable");
    (keys[order], vals[order])`` — radix passes instead of comparison sort.
    """
    keys = np.ascontiguousarray(keys, dtype=np.int32)
    vals = np.ascontiguousarray(vals, dtype=np.int32)
    if keys.shape != vals.shape or keys.ndim != 1:
        raise ValueError("sort_pairs expects two equal-length 1-D arrays")
    lib = _load()
    if lib is None or keys.size == 0:
        order = np.argsort(keys, kind="stable")
        return keys[order], vals[order]
    out_k = np.empty_like(keys)
    out_v = np.empty_like(vals)
    lib.gc_sort_pairs_i32(keys, vals, keys.size, out_k, out_v)
    return out_k, out_v


def sort_unique(keys: np.ndarray) -> np.ndarray:
    """Sorted unique non-negative int64 ``keys`` (``np.unique`` equivalent)."""
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    if keys.ndim != 1:
        raise ValueError("sort_unique expects a 1-D array")
    lib = _load()
    if lib is None or keys.size == 0:
        return np.unique(keys)
    buf = keys.copy()
    m = lib.gc_sort_unique_i64(buf, buf.size)
    return buf[:m]


# ------------------------------------------------------------ delta builds
#
# Host kernels behind sim/graph.py's apply_delta: the base COO arrays are
# already receiver-sorted, so an add/remove batch only needs the DELTA
# radix-sorted (sort_pairs above) plus these linear merge/anti-merge
# passes — never the full E-element sort a from-scratch build pays. Each
# has a vectorized numpy fallback honoring force_fallback().

def _pair_keys(r: np.ndarray, s: np.ndarray) -> np.ndarray:
    """int64 (receiver, sender) keys ordering like the lexicographic pair —
    both ids are non-negative int32, so 32-bit shifting cannot collide."""
    return (r.astype(np.int64) << 32) | s.astype(np.int64)


def delta_antimerge(base_r: np.ndarray, base_s: np.ndarray,
                    alive: np.ndarray, rem_r: np.ndarray,
                    rem_s: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Survivor mask of the base COO under a removal batch.

    ``base_r``/``base_s`` are the full padded edge arrays (receiver-sorted
    among live slots), ``alive`` the liveness mask; ``rem_r``/``rem_s``
    must be sorted by (receiver, sender). Returns ``(keep, matched)``:
    ``keep`` marks live edges NOT removed; ``matched[j]`` says removal
    ``j`` hit at least one live copy (every copy of a matched pair is
    removed). Callers decide whether unmatched removals are an error.
    """
    base_r = np.ascontiguousarray(base_r, dtype=np.int32)
    base_s = np.ascontiguousarray(base_s, dtype=np.int32)
    alive_u8 = np.ascontiguousarray(alive, dtype=np.uint8)
    rem_r = np.ascontiguousarray(rem_r, dtype=np.int32)
    rem_s = np.ascontiguousarray(rem_s, dtype=np.int32)
    lib = _load()
    if lib is not None and base_r.size and rem_r.size:
        keep = np.empty(base_r.size, dtype=np.uint8)
        hits = np.empty(rem_r.size, dtype=np.int32)
        lib.gc_delta_antimerge_i32(base_r, base_s, alive_u8, base_r.size,
                                   rem_r, rem_s, rem_r.size, keep, hits)
        return keep.view(bool), hits > 0
    keep = alive_u8.astype(bool)
    if rem_r.size == 0 or base_r.size == 0:
        return keep, np.zeros(rem_r.size, dtype=bool)
    bk = _pair_keys(base_r, base_s)
    rk = _pair_keys(rem_r, rem_s)
    uk = np.unique(rk)
    pos = np.searchsorted(uk, bk)
    hit = keep & (uk[np.minimum(pos, uk.size - 1)] == bk)
    matched_unique = np.zeros(uk.size, dtype=bool)
    matched_unique[pos[hit]] = True
    return keep & ~hit, matched_unique[np.searchsorted(uk, rk)]


def delta_merge(base_r: np.ndarray, base_s: np.ndarray, keep: np.ndarray,
                d_r: np.ndarray, d_s: np.ndarray,
                out_r: Optional[np.ndarray] = None,
                out_s: Optional[np.ndarray] = None):
    """Stable merge of the kept base edges with a receiver-sorted delta
    (base first on ties — the order a stable from-scratch sort of
    ``[kept base, delta]`` yields). Returns ``(out_r, out_s, posa, posb)``
    where ``posa[i]`` is base slot i's merged index (-1 when dropped) and
    ``posb[j]`` delta entry j's. ``out_r``/``out_s`` may be preallocated
    int32 buffers (at least merged-count long, e.g. the already-padded
    target arrays) — the merge then writes in place, skipping a copy."""
    base_r = np.ascontiguousarray(base_r, dtype=np.int32)
    base_s = np.ascontiguousarray(base_s, dtype=np.int32)
    keep_u8 = np.ascontiguousarray(keep, dtype=np.uint8)
    d_r = np.ascontiguousarray(d_r, dtype=np.int32)
    d_s = np.ascontiguousarray(d_s, dtype=np.int32)
    cap = base_r.size + d_r.size
    if out_r is None:
        out_r = np.empty(cap, dtype=np.int32)
        out_s = np.empty(cap, dtype=np.int32)
    lib = _load()
    if lib is not None and base_r.size:
        posa = np.empty(base_r.size, dtype=np.int32)
        posb = np.empty(d_r.size, dtype=np.int32)
        n = lib.gc_delta_merge_i32(base_r, base_s, keep_u8, base_r.size,
                                   d_r, d_s, d_r.size, out_r, out_s,
                                   posa, posb)
        return out_r[:n], out_s[:n], posa, posb
    kept_idx = np.flatnonzero(keep_u8)
    kr, ks = base_r[kept_idx], base_s[kept_idx]
    nk, nd = kr.size, d_r.size
    # Stable-merge positions via searchsorted: a kept base edge lands after
    # every strictly-smaller delta receiver; a delta edge lands after every
    # kept receiver <= its own (base wins ties).
    posk = np.arange(nk, dtype=np.int32) + np.searchsorted(
        d_r, kr, side="left").astype(np.int32)
    posd = np.arange(nd, dtype=np.int32) + np.searchsorted(
        kr, d_r, side="right").astype(np.int32)
    out_r[posk], out_s[posk] = kr, ks
    out_r[posd], out_s[posd] = d_r, d_s
    posa = np.full(base_r.size, -1, dtype=np.int32)
    posa[kept_idx] = posk
    return out_r[:nk + nd], out_s[:nk + nd], posa, posd


def map_filter(eids: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """``pos[eids]`` with the ``-1`` (dropped) entries filtered out,
    order-preserving — the surviving half of the incremental CSR update."""
    eids = np.ascontiguousarray(eids, dtype=np.int32)
    pos = np.ascontiguousarray(pos, dtype=np.int32)
    lib = _load()
    if lib is not None and eids.size:
        out = np.empty(eids.size, dtype=np.int32)
        m = lib.gc_map_filter_i32(eids, eids.size, pos, out)
        return out[:m]
    mapped = pos[eids]
    return mapped[mapped >= 0]


def merge_eids_by_sender(senders: np.ndarray, ea: np.ndarray,
                         eb: np.ndarray,
                         out: Optional[np.ndarray] = None) -> np.ndarray:
    """Merge two edge-id lists, each sorted by ``(senders[eid], eid)``,
    preserving that order — the incremental source-CSR merge. ``out`` may
    be a preallocated int32 buffer (exactly ``ea.size + eb.size`` long,
    e.g. a view of the padded target array) to write in place."""
    senders = np.ascontiguousarray(senders, dtype=np.int32)
    ea = np.ascontiguousarray(ea, dtype=np.int32)
    eb = np.ascontiguousarray(eb, dtype=np.int32)
    if out is None:
        out = np.empty(ea.size + eb.size, dtype=np.int32)
    lib = _load()
    if lib is not None and (ea.size or eb.size):
        lib.gc_merge_eids_by_sender_i32(senders, ea, ea.size, eb, eb.size,
                                        out)
        return out
    ka = (senders[ea].astype(np.int64) << 32) | ea
    kb = (senders[eb].astype(np.int64) << 32) | eb
    out[np.arange(ea.size) + np.searchsorted(kb, ka)] = ea
    out[np.arange(eb.size) + np.searchsorted(ka, kb, side="right")] = eb
    return out

"""Native graphcore kernels vs their numpy fallbacks — identical results,
and graph construction must be identical whichever path built it."""

import numpy as np
import pytest

from p2pnetwork_tpu import native


@pytest.fixture(autouse=True)
def restore_fallback():
    yield
    native.force_fallback(False)


def test_native_library_compiles_and_loads():
    assert native.available(), "g++ is in this image; the library must build"


def test_library_is_keyed_on_the_source_hash(tmp_path, monkeypatch):
    # A library built from other source is never loaded, however new its
    # mtime: the name carries the source's digest, so an edited
    # graphcore.cpp looks for (and builds) a library of its own.
    src = tmp_path / "graphcore.cpp"
    src.write_bytes(native._SRC.read_bytes())
    monkeypatch.setattr(native, "_SRC", src)
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "cache"))
    stale = tmp_path / "libgraphcore.so"
    stale.write_bytes(b"not a library")
    built = native._compile()
    assert built is not None and built != stale
    assert built.parent == tmp_path and built.name.startswith(
        "libgraphcore-")
    assert native._compile() == built  # found again, not rebuilt
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    rebuilt = native._compile()
    assert rebuilt is not None and rebuilt != built


class TestSortPairs:
    @pytest.mark.parametrize("n", [0, 1, 7, 1000, 100_000])
    def test_matches_numpy_stable_argsort(self, n):
        rng = np.random.default_rng(n)
        keys = rng.integers(0, max(n, 1), size=n, dtype=np.int32)
        vals = np.arange(n, dtype=np.int32)
        out_k, out_v = native.sort_pairs(keys, vals)
        order = np.argsort(keys, kind="stable")
        np.testing.assert_array_equal(out_k, keys[order])
        np.testing.assert_array_equal(out_v, vals[order])

    def test_stability_on_duplicate_keys(self):
        keys = np.zeros(1000, dtype=np.int32)
        vals = np.arange(1000, dtype=np.int32)
        _, out_v = native.sort_pairs(keys, vals)
        np.testing.assert_array_equal(out_v, vals)  # stable = order preserved

    def test_large_key_range_multi_pass(self):
        # Keys above 2^16 force the second radix pass; above 2^31-ish the
        # sign bit would break it, so int32 max range is the contract edge.
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 2**31 - 1, size=50_000, dtype=np.int32)
        vals = np.arange(50_000, dtype=np.int32)
        out_k, out_v = native.sort_pairs(keys, vals)
        order = np.argsort(keys, kind="stable")
        np.testing.assert_array_equal(out_k, keys[order])
        np.testing.assert_array_equal(out_v, vals[order])


class TestSortUnique:
    @pytest.mark.parametrize("n", [0, 1, 1000, 200_000])
    def test_matches_numpy_unique(self, n):
        rng = np.random.default_rng(n)
        keys = rng.integers(0, max(n // 2, 1), size=n, dtype=np.int64)
        np.testing.assert_array_equal(native.sort_unique(keys), np.unique(keys))

    def test_large_values_multi_pass(self):
        rng = np.random.default_rng(1)
        keys = rng.integers(0, 10**12, size=100_000, dtype=np.int64)
        np.testing.assert_array_equal(native.sort_unique(keys), np.unique(keys))

    def test_input_not_mutated(self):
        keys = np.array([5, 3, 3, 1], dtype=np.int64)
        native.sort_unique(keys)
        np.testing.assert_array_equal(keys, [5, 3, 3, 1])

    def test_keys_above_2_48_terminate(self):
        # Regression: pair keys reach ~n^2; for n >= 2^24 that exceeds 2^48,
        # where the pass-count loop used to shift by >= 64 bits — undefined
        # behavior that spins forever on x86.
        rng = np.random.default_rng(2)
        keys = rng.integers(2**48, 2**62, size=50_000, dtype=np.int64)
        np.testing.assert_array_equal(native.sort_unique(keys), np.unique(keys))


def test_graph_identical_native_vs_fallback():
    from p2pnetwork_tpu.sim import graph as G

    def build():
        g = G.watts_strogatz(500, 6, 0.2, seed=3, blocked=True, hybrid=True)
        return g

    native.force_fallback(False)
    g_native = build()
    native.force_fallback(True)
    g_numpy = build()

    for field in ("senders", "receivers", "edge_mask", "node_mask",
                  "in_degree", "out_degree", "neighbors", "neighbor_mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(g_native, field)),
            np.asarray(getattr(g_numpy, field)),
            err_msg=field,
        )
    np.testing.assert_array_equal(
        np.asarray(g_native.blocked.src), np.asarray(g_numpy.blocked.src)
    )
    assert g_native.hybrid.offsets == g_numpy.hybrid.offsets


def test_from_edges_inline_reps_match_posthoc():
    from p2pnetwork_tpu.sim import graph as G

    g_inline = G.watts_strogatz(400, 4, 0.3, seed=1, blocked=True, hybrid=True)
    g_posthoc = G.watts_strogatz(400, 4, 0.3, seed=1).with_blocked().with_hybrid()
    np.testing.assert_array_equal(
        np.asarray(g_inline.blocked.src), np.asarray(g_posthoc.blocked.src)
    )
    np.testing.assert_array_equal(
        np.asarray(g_inline.blocked.local_dst),
        np.asarray(g_posthoc.blocked.local_dst),
    )
    assert g_inline.hybrid.offsets == g_posthoc.hybrid.offsets
    np.testing.assert_array_equal(
        np.asarray(g_inline.hybrid.masks), np.asarray(g_posthoc.hybrid.masks)
    )

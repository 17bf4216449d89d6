"""Beneš-routing feasibility study for the unstructured edge remainder.

BENCH.md's analysis says the hybrid method's floor is the gather for the
unstructured remainder (~8 cycles/element on the TPU, index-independent).
A Beneš network replaces the gather with ``2*log2(m) - 1`` columns of
2x2 switches; an XOR-butterfly Beneš column at distance ``d`` is

    y[i] = ctrl[i] ? x[i ^ d] : x[i]

— a reshape + reversed-slice + select, pure streaming VPU traffic with no
data-dependent addressing. Whether that beats the gather is a bandwidth
question, and the stage cost does NOT depend on the switch settings, so
phase 1 measures the stage structure with random controls (routing
correctness not required for timing):

    stage cost x (2 log2 m - 1)   vs   one m-element random gather

Phase 2 (only worth building if phase 1 wins): the looping algorithm to
compute real switch settings host-side, plus a copy-network phase for
multicast sources. Run: ``python benchmarks/benes.py [m_log2]``.
Prints one JSON line per measurement and a verdict line.
"""

import json
import sys
import time

sys.path.insert(0, ".")

from p2pnetwork_tpu.utils.jax_env import apply_platform_env  # noqa: E402

apply_platform_env()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def benes_stages(k: int):
    """XOR distances of the 2k-1 Beneš columns (butterfly + inverse)."""
    return [2 ** j for j in range(k - 1, 0, -1)] + [2 ** j for j in range(k)]


def apply_stage(x, ctrl, d):
    """One switch column: y[i] = ctrl[i] ? x[i ^ d] : x[i]."""
    m = x.shape[0]
    xs = x.reshape(m // (2 * d), 2, d)
    swapped = xs[:, ::-1, :].reshape(m)
    return jnp.where(ctrl, swapped, x)


def make_network(k: int, key):
    """Random switch settings for every column (timing only)."""
    m = 2 ** k
    ds = benes_stages(k)
    ctrls = jax.random.bernoulli(key, 0.5, (len(ds), m))
    return ds, ctrls


def make_route(ds):
    @jax.jit
    def route(x, ctrls):
        for i, d in enumerate(ds):
            x = apply_stage(x, ctrls[i], d)
        return x

    return route


def timeit(fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    _ = np.asarray(out.ravel()[0])  # host transfer ends the region
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        _ = np.asarray(out.ravel()[0])
        times.append(time.perf_counter() - t0)
    return min(times)


def main():
    k = int(sys.argv[1]) if len(sys.argv) > 1 else 21  # 2M wires
    m = 2 ** k
    key = jax.random.key(0)
    x = jax.random.normal(key, (m,), dtype=jnp.float32)
    perm = jax.random.permutation(jax.random.fold_in(key, 1), m)

    # Baseline: the gather the hybrid remainder currently pays.
    gather = jax.jit(lambda v, p: v[p])
    t_gather = timeit(gather, x, perm)
    emit = lambda r: print(json.dumps(r), flush=True)  # noqa: E731
    emit({"measure": "gather", "m": m, "ms": round(t_gather * 1e3, 3),
          "ns_per_elem": round(t_gather / m * 1e9, 3)})

    # Beneš stage structure with random controls.
    ds, ctrls = make_network(k, jax.random.fold_in(key, 2))
    routed = make_route(tuple(ds))
    t_benes = timeit(routed, x, ctrls)
    emit({"measure": "benes_stages", "m": m, "stages": len(ds),
          "ms": round(t_benes * 1e3, 3),
          "ns_per_elem_total": round(t_benes / m * 1e9, 3)})

    verdict = "benes_wins" if t_benes < t_gather else "gather_wins"
    emit({"measure": "verdict", "result": verdict,
          "speedup": round(t_gather / t_benes, 2),
          "note": ("switch-setting computation (phase 2) is only worth "
                   "building if benes_wins with margin; controls do not "
                   "affect stage cost")})
    return 0


if __name__ == "__main__":
    sys.exit(main())

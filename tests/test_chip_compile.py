"""The main path's and the ring's Pallas kernels, compiled for a described
TPU v5e (2x2) at the 1M-node graph's real shapes — no chip needed.

The TPU compiler refuses here what interpret mode never sees: bool DMAs,
unaligned tiles, too much VMEM. Each case asserts that the compiled text
holds a ``tpu_custom_call``, i.e. that the kernel is compiled and not
interpreted. ``_is_cpu`` (the kernels' interpret switch) still sees this
CPU process, so the tests steer it off themselves.

The topology is described only inside the module fixture: describing it
loads libtpu, which one process at a time may hold.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke
from p2pnetwork_tpu.models.flood import Flood
from p2pnetwork_tpu.ops import pallas_edge, pallas_ring
from p2pnetwork_tpu.parallel import sharded

SHARDS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(pallas_edge, "_is_cpu", lambda: False)
            mp.setattr(pallas_ring, "_is_cpu", lambda: False)
            yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        if prev_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring(topo):
    mesh = Mesh(np.array(topo.devices[:SHARDS]), ("shards",),
                axis_types=(jax.sharding.AxisType.Auto,))
    return mesh, NamedSharding(mesh, P("shards"))


@pytest.fixture(scope="module")
def g1m():
    g, _, _ = chip_smoke.build_main_graph(chip_smoke.N_MAIN)
    return g


@pytest.fixture(scope="module")
def sg1m(g1m):
    """The 1M graph sharded for a 4-ring on this process's CPU devices —
    the source of the ring programs' real shapes."""
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} host devices to shard the graph")
    mesh = Mesh(np.array(jax.devices()[:SHARDS]), ("shards",),
                axis_types=(jax.sharding.AxisType.Auto,))
    return sharded.shard_graph(g1m, mesh, hybrid=True)


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("exact", [True, False])
def test_edge_kernel_at_1m_blocked_shape(g1m, one_chip, exact):
    b = g1m.blocked
    contrib = jax.ShapeDtypeStruct(b.src.shape, jnp.float32,
                                   sharding=one_chip)
    dst = jax.ShapeDtypeStruct(b.local_dst.shape, jnp.int32,
                               sharding=one_chip)
    _assert_kernel(pallas_edge.segment_sum_pallas.lower(
        contrib, dst, b.block, exact=exact))


def test_hybrid_flood_step_at_1m(g1m, one_chip):
    proto = Flood(source=0, method="hybrid")
    key = jax.random.key(0)
    state = jax.eval_shape(proto.init, g1m, key)
    _assert_kernel(jax.jit(proto.step).lower(
        _sds(g1m, one_chip), _sds(state, one_chip),
        jax.ShapeDtypeStruct((), key.dtype, sharding=one_chip)))


def test_ring_shift_bool_frontier(ring, sg1m):
    mesh, shard = ring
    fn = jax.jit(jax.shard_map(
        lambda x: pallas_ring.ring_shift(x, "shards", SHARDS),
        mesh=mesh, in_specs=P("shards"), out_specs=P("shards"),
        check_vma=False))
    frontier = jax.ShapeDtypeStruct((SHARDS, sg1m.block), jnp.bool_,
                                    sharding=shard)
    _assert_kernel(fn.lower(frontier))


def test_fused_ring_segment_sum(ring, sg1m):
    mesh, shard = ring
    nb, w = sg1m.mxu_dst.shape[-2:]

    def body(rot, contrib, dst):
        return pallas_ring.ring_segment_sum(
            rot, contrib, dst, "shards", SHARDS, sg1m.mxu_block,
            exact=False)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("shards"),) * 3,
        out_specs=(P("shards"), P("shards")), check_vma=False))
    _assert_kernel(fn.lower(
        jax.ShapeDtypeStruct((SHARDS, sg1m.block), jnp.bool_,
                             sharding=shard),
        jax.ShapeDtypeStruct((SHARDS * nb, w), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((SHARDS * nb, w), jnp.int32, sharding=shard)))


@pytest.mark.parametrize("comm", sharded.COMM_BACKENDS)
def test_sharded_flood_loop_at_1m(ring, sg1m, comm):
    """The whole 4-ring run-to-coverage program the four-chip smoke runs."""
    mesh, shard = ring
    seen0, frontier0 = sharded.init_state(sg1m, Flood(source=0), None)
    args = (sg1m.bkt_src, sg1m.bkt_dst, sg1m.bkt_mask,
            *sharded._dyn_or_empty(sg1m), *sharded._mxu_or_empty(sg1m),
            sharded._diag_masks_or_empty(sg1m), sg1m.node_mask,
            sg1m.out_degree, seen0, frontier0)
    fn = sharded._flood_cov_fn(mesh, "shards", sg1m.n_shards, sg1m.block,
                               64, sg1m.diag_pieces, sg1m.mxu_block, comm)
    _assert_kernel(fn.lower(
        jax.ShapeDtypeStruct((), jnp.float32,
                             sharding=NamedSharding(mesh, P())),
        *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shard)
          for a in args)))

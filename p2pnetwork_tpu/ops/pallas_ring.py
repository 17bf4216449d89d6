"""Pallas TPU ring-DMA halo exchange: async remote copies over ICI.

The sharded ring (parallel/sharded.py) moves each shard's resident
frontier block to its ring neighbor once per ring step. As XLA
``lax.ppermute`` that transfer is a collective the scheduler serializes
against the bucket compute consuming the block; here the same hop is a
``pltpu.make_async_remote_copy`` issued from inside a Pallas kernel — the
DMA engine moves the halo while the shard's local propagation work runs,
the classic communication/computation overlap of the ring-attention /
multi-node-GCN literature (PAPERS.md).

Two kernels:

- :func:`ring_shift` — the bare halo hop: copy the whole payload to the
  next (or previous) ring neighbor. Payload-shape agnostic (bool
  frontier blocks, f32 value blocks, ``u32[W, block]`` lane words — one
  DMA round then moves 32 in-flight messages' boundary state per word).
- :func:`ring_segment_sum` — the FUSED ring step: start the halo DMA of
  the resident block at grid step 0, run the blocked one-hot-matmul
  segment sum (the ops/pallas_edge.py scheme) across the whole grid
  while the transfer is in flight, wait on the receive semaphore at the
  last grid step. The shard-local edge aggregation IS the overlap window.

Both run under ``shard_map`` on a ring mesh and are bit-identical to the
``ppermute`` formulation (the parity contract tests/test_ring.py pins).
On CPU they run in the Pallas interpreter — the interpreter honors
cross-device ``make_async_remote_copy``, so CI proves bit-identity on
the 8-device virtual mesh without chips; real overlap is a chip-only
property (the interpreter executes sequentially).

Both ``pallas_call``s are named ``ring_halo_*`` on purpose: the name
lands in the eqn's ``name`` param, which is how the ICI accounting
recognizes DMA traffic a collective census would otherwise read as zero
bytes (parallel/commviz.py ``RING_DMA_MARKER``,
analysis/ir/registry.py collective census).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2pnetwork_tpu.ops.pallas_edge import ROW_TILE, TILE_W, _is_cpu

#: Marker every ring-DMA kernel's function name carries — the handle the
#: ICI byte accounting greps for in ``pallas_call`` eqns (commviz /
#: graftaudit). The kernels' FIRST output is, by convention, the DMA
#: payload (the received block), so ``outvars[0]`` prices the hop.
RING_DMA_MARKER = "ring_halo"


def _neighbor(axis_name: str, axis_size: int, reverse: bool):
    """Logical device id of the ring neighbor this kernel copies TO.

    Forward (``reverse=False``) sends to ``my + 1``: after the copy,
    shard ``d`` holds the block previously on ``d - 1`` — exactly
    ``lax.ppermute(x, axis, [(i, (i+1) % S)])`` (sharded._ring_perm).
    Reverse sends to ``my - 1`` (the remask Horner accumulation's
    back-rotation).
    """
    my = lax.axis_index(axis_name)
    if reverse:
        return lax.rem(my + axis_size - 1, axis_size)
    return lax.rem(my + 1, axis_size)


def _neighbor_barrier(axis_name: str, axis_size: int):
    """Handshake with both ring neighbours before any remote DMA: signal
    each one's barrier semaphore and wait for both signals back. A device
    past the barrier knows the neighbour it writes to has entered the
    same kernel, so the DMA never lands in a buffer that neighbour has
    not handed to this call yet (the handshake of JAX's distributed
    Pallas guide). Compiled kernels only: the CPU interpreter runs the
    devices one after another, has no barrier semaphore, and cannot
    race."""
    barrier = pltpu.get_barrier_semaphore()
    for reverse in (False, True):
        pltpu.semaphore_signal(
            barrier, 1, device_id=_neighbor(axis_name, axis_size, reverse),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 2)


def _dma_dtype(dtype):
    """Mosaic refuses DMAs of bool: such payloads cross as uint8 (same
    bytes, 0/1) and are cast back after the hop."""
    return jnp.dtype(jnp.uint8) if dtype == jnp.bool_ else jnp.dtype(dtype)


def _ring_halo_copy_kernel(src_ref, dst_ref, send_sem, recv_sem, *,
                           axis_name: str, axis_size: int, reverse: bool,
                           barrier: bool):
    neighbor = _neighbor(axis_name, axis_size, reverse)
    if barrier:
        _neighbor_barrier(axis_name, axis_size)
    copy = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=neighbor,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    copy.start()
    copy.wait()  # graftlint: ignore[wait-untimed] -- Pallas DMA-semaphore wait inside a kernel, not a thread wait; Mosaic has no timeout form


@functools.lru_cache(maxsize=256)
def _shift_call(shape, dtype, axis_name: str, axis_size: int, reverse: bool,
                interpret: bool):
    kernel = functools.partial(
        _ring_halo_copy_kernel, axis_name=axis_name, axis_size=axis_size,
        reverse=reverse, barrier=not interpret,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
        compiler_params=pltpu.CompilerParams(collective_id=0),
        interpret=interpret,
        name=f"{RING_DMA_MARKER}_shift",
    )


def ring_shift(x: jax.Array, axis_name: str, axis_size: int, *,
               reverse: bool = False,
               interpret: bool | None = None) -> jax.Array:
    """One ring halo hop as an async remote copy: the Pallas twin of
    ``lax.ppermute(x, axis_name, [(i, (i+1) % S)])`` (``reverse=True``
    for the ``[((i+1) % S, i)]`` back-rotation).

    Must run inside a ``shard_map`` body over a ring mesh of
    ``axis_size`` devices; ``x`` is the per-shard block (any shape or
    dtype — frontier bools, value floats, lane words). Under
    ``axis_size == 1`` the hop is the identity, matching what the
    ppermute formulation's callers skip at trace time.
    """
    if axis_size == 1:
        return x
    if interpret is None:
        interpret = _is_cpu()
    wire = _dma_dtype(x.dtype)
    fn = _shift_call(tuple(x.shape), wire.name, axis_name, axis_size,
                     reverse, interpret)
    return fn(x.astype(wire)).astype(x.dtype)


def _ring_halo_segsum_kernel(rot_ref, contrib_ref, dst_ref,
                             rot_out_ref, out_ref, send_sem, recv_sem, *,
                             axis_name: str, axis_size: int,
                             n_i: int, n_j: int, tile_w: int, precision,
                             barrier: bool):
    """Fused ring step: the halo DMA of the resident block rides UNDER the
    blocked one-hot segment sum. Grid step (0, 0) starts the copy; every
    step accumulates its ``[ROW_TILE, TILE_W]`` strip's partial product
    (ops/pallas_edge.py scheme — the one-hot never touches HBM); the last
    step waits on the receive semaphore. The whole edge aggregation is
    the transfer's overlap window."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    neighbor = _neighbor(axis_name, axis_size, reverse=False)
    copy = pltpu.make_async_remote_copy(
        src_ref=rot_ref,
        dst_ref=rot_out_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=neighbor,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )

    @pl.when((i == 0) & (j == 0))
    def _():
        if barrier:
            _neighbor_barrier(axis_name, axis_size)
        copy.start()

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    contrib = contrib_ref[:]  # [ROW_TILE, TILE_W] f32
    dst = dst_ref[:]  # [ROW_TILE, TILE_W] i32
    rows, block = contrib.shape[0], out_ref.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, tile_w, block), 2)
    onehot = (dst[:, :, None] == iota).astype(jnp.float32)
    partial = jax.lax.dot_general(
        contrib[:, None, :],  # [R, 1, W]
        onehot,  # [R, W, B]
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=precision,
    )  # [R, 1, B]
    out_ref[:] += partial[:, 0, :]

    @pl.when((i == n_i - 1) & (j == n_j - 1))
    def _():
        copy.wait()  # graftlint: ignore[wait-untimed] -- Pallas DMA-semaphore wait (recv fence of the fused ring step), not a thread wait


@functools.lru_cache(maxsize=256)
def _segsum_call(rot_shape, rot_dtype, nb_pad: int, w: int, block: int,
                 tile_w: int, axis_name: str, axis_size: int, exact: bool,
                 interpret: bool):
    n_i, n_j = nb_pad // ROW_TILE, w // tile_w
    precision = (jax.lax.Precision.HIGHEST if exact
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(
        _ring_halo_segsum_kernel, axis_name=axis_name, axis_size=axis_size,
        n_i=n_i, n_j=n_j, tile_w=tile_w, precision=precision,
        barrier=not interpret,
    )
    return pl.pallas_call(
        kernel,
        grid=(n_i, n_j),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec((ROW_TILE, tile_w), lambda i, j: (i, j)),
            pl.BlockSpec((ROW_TILE, tile_w), lambda i, j: (i, j)),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec((ROW_TILE, block), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(rot_shape, rot_dtype),
            jax.ShapeDtypeStruct((nb_pad, block), jnp.float32),
        ),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
        compiler_params=pltpu.CompilerParams(collective_id=0),
        interpret=interpret,
        name=f"{RING_DMA_MARKER}_segsum",
    )


def ring_segment_sum(rot: jax.Array, contrib: jax.Array,
                     local_dst: jax.Array, axis_name: str, axis_size: int,
                     block: int = 128, tile_w: int = TILE_W, *,
                     exact: bool = True,
                     interpret: bool | None = None):
    """The fused ring step: ``(rot_next, out)`` where ``rot_next`` is
    ``rot`` received from the ring's previous shard (the forward halo
    hop) and ``out[n, b] = sum_w contrib[n, w] * (local_dst[n, w] == b)``
    — the blocked segment sum of ops/pallas_edge.py with the halo DMA
    overlapped under its grid.

    ``contrib`` f32[NB, W] (masked slots 0), ``local_dst`` i32[NB, W] in
    [0, block). Padding contracts, ``exact`` semantics and the returned
    sum are ops/pallas_edge.segment_sum_pallas_impl's exactly; ``rot``
    is any per-shard block. Must run inside a ``shard_map`` body over a
    ring of ``axis_size >= 2`` devices (at 1 there is no halo — callers
    use the plain kernel).
    """
    if axis_size < 2:
        raise ValueError("ring_segment_sum needs a ring of >= 2 shards")
    nb, w = contrib.shape
    if block % 128 != 0:
        raise ValueError(
            f"block must be a multiple of 128 (lane width), got {block}")
    if w % tile_w != 0:
        pad = tile_w - w % tile_w
        contrib = jnp.pad(contrib, ((0, 0), (0, pad)))
        local_dst = jnp.pad(local_dst, ((0, 0), (0, pad)))
        w += pad
    nb_pad = nb
    if nb % ROW_TILE != 0:
        row_pad = ROW_TILE - nb % ROW_TILE
        contrib = jnp.pad(contrib, ((0, row_pad), (0, 0)))
        local_dst = jnp.pad(local_dst, ((0, row_pad), (0, 0)))
        nb_pad += row_pad
    if interpret is None:
        interpret = _is_cpu()
    wire = _dma_dtype(rot.dtype)
    fn = _segsum_call(tuple(rot.shape), wire.name, nb_pad, w, block, tile_w,
                      axis_name, axis_size, exact, interpret)
    rot_next, out = fn(rot.astype(wire), contrib, local_dst)
    return rot_next.astype(rot.dtype), out[:nb]

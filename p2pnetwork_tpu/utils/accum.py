"""Wide message accounting without enabling global x64.

The reference's counters are unbounded Python ints [ref: p2pnetwork/
node.py:64-67]; the sim engine's device-side counters are not. With JAX's
default 32-bit mode a 10M-node / 100M-edge run reaches ~1e8 messages per
round, so a few dozen full-frontier rounds silently wrap an int32
accumulator. Enabling ``jax_enable_x64`` globally is the wrong fix — it
flips every default dtype (``jax.random.uniform`` becomes f64, breaking RNG
bit-parity contracts and TPU-unfriendly f64 math everywhere).

Instead: a two-limb accumulator. ``lo`` is uint32 (addition wraps mod 2^32
by definition, and a wrap is detected as ``lo + x < lo``); ``hi`` counts
2^32 carries in int32. Range: 2^63 messages — per-round counts stay int32,
which is structurally safe because a round's message count is bounded by
the directed edge count, and edge indices are int32 already.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Acc = Tuple[jax.Array, jax.Array]  # (hi: i32, lo: u32)


def zero() -> Acc:
    """A fresh accumulator (loop-carry friendly: two scalars)."""
    return (jnp.int32(0), jnp.uint32(0))


def add(acc: Acc, x: jax.Array) -> Acc:
    """Add a non-negative int32/uint32 scalar; jittable.

    Unsigned overflow is well-defined wraparound, and since ``x < 2^32``
    each add carries at most one: carry happened iff the wrapped sum is
    smaller than either operand.
    """
    hi, lo = acc
    lo2 = lo + x.astype(jnp.uint32)
    return (hi + (lo2 < lo).astype(jnp.int32), lo2)


def value(acc: Acc) -> int:
    """Combine to an exact Python int (host-side; forces a transfer)."""
    hi, lo = acc
    return (int(np.asarray(hi)) << 32) + int(np.uint32(np.asarray(lo)))


def pack_summary(rounds: jax.Array, coverage: jax.Array, acc: Acc,
                 extra=None) -> jax.Array:
    """[rounds, coverage-bits, hi, lo-bits] as one i32[4] — a single
    device->host transfer carries a whole run summary. Shared by the
    engine's and the sharded path's run-to-coverage loops.

    ``extra`` (optional f32 scalar) appends a fifth slot — the engine
    packs the mean per-round frontier occupancy there; callers that
    don't pass it keep the original i32[4] layout byte for byte."""
    hi, lo = acc
    parts = [
        rounds,
        jax.lax.bitcast_convert_type(coverage, jnp.int32),
        hi,
        jax.lax.bitcast_convert_type(lo, jnp.int32),
    ]
    if extra is not None:
        parts.append(
            jax.lax.bitcast_convert_type(jnp.float32(extra), jnp.int32))
    return jnp.stack(parts)


def unpack_summary(packed) -> dict:
    """Host-side inverse of :func:`pack_summary` (forces the transfer).
    A fifth slot, when present, comes back under ``"extra"``."""
    arr = np.asarray(packed)
    coverage = float(arr[1:2].view(np.float32)[0])
    messages = (int(arr[2]) << 32) + int(arr[3:4].view(np.uint32)[0])
    out = {"rounds": int(arr[0]), "coverage": coverage, "messages": messages}
    if arr.size >= 5:
        out["extra"] = float(arr[4:5].view(np.float32)[0])
    return out


#: Fixed slots of the batch summary ahead of the per-lane vectors.
_BATCH_HEAD = 6


def pack_batch_summary(rounds: jax.Array, active_lanes: jax.Array,
                       completed: jax.Array, acc: Acc, occ_mean: jax.Array,
                       done_words: jax.Array,
                       lane_rounds: jax.Array) -> jax.Array:
    """The batch engine's one-transfer run summary: ``i32[6 + W + B]``.

    Head: ``[global_rounds, active_lanes, completed, hi, lo-bits,
    occupancy-bits]`` — the scalar aggregates in :func:`pack_summary`'s
    spirit. Tail: the PER-LANE vectors the batched plane adds — the
    ``done`` lane flags packed as ``u32[W]`` words (ops/bitset.py lane
    order) and each lane's applied-round count ``i32[B]``. One packed
    vector = one device->host transfer for the whole B-message summary,
    however many messages rode the batch."""
    hi, lo = acc
    head = jnp.stack([
        rounds.astype(jnp.int32),
        active_lanes.astype(jnp.int32),
        completed.astype(jnp.int32),
        hi,
        jax.lax.bitcast_convert_type(lo, jnp.int32),
        jax.lax.bitcast_convert_type(jnp.float32(occ_mean), jnp.int32),
    ])
    return jnp.concatenate([
        head,
        jax.lax.bitcast_convert_type(done_words, jnp.int32).reshape(-1),
        lane_rounds.astype(jnp.int32),
    ])


def pack_query_summary(rounds: jax.Array, active_lanes: jax.Array,
                       completed: jax.Array, acc: Acc, occ_mean: jax.Array,
                       done_words: jax.Array, lane_rounds: jax.Array,
                       lane_values: jax.Array, *,
                       values_float: bool) -> jax.Array:
    """The query engine's one-transfer run summary:
    ``i32[6 + W + K + K]`` — :func:`pack_batch_summary`'s head and
    per-lane tail plus the query plane's addition: each lane's ANSWER
    (``lane_values``) rides the same packed vector, so a whole
    K-query result set costs one device->host transfer. Answers are
    f32 (bitcast; routing distances, aggregation means) or raw i32
    (DHT cursors — f32 would corrupt node ids past 2^24) per
    ``values_float``, which is static protocol knowledge the unpacker
    must be told again."""
    if values_float:
        vals = jax.lax.bitcast_convert_type(
            lane_values.astype(jnp.float32), jnp.int32)
    else:
        vals = lane_values.astype(jnp.int32)
    return jnp.concatenate([
        pack_batch_summary(rounds, active_lanes, completed, acc, occ_mean,
                           done_words, lane_rounds),
        vals.reshape(-1),
    ])


def unpack_query_summary(packed, capacity: int, *,
                         values_float: bool) -> dict:
    """Host-side inverse of :func:`pack_query_summary` (forces the
    transfer). ``lane_done``/``lane_rounds`` trim to ``capacity`` (the
    done words pad to whole 32-lane blocks); ``lane_values`` comes back
    f32 or i32 per ``values_float``. The head + per-lane core decodes
    through :func:`unpack_batch_summary` — one copy of that layout."""
    arr = np.asarray(packed)
    capacity = int(capacity)
    n_words = -(-capacity // 32)
    core_len = _BATCH_HEAD + n_words + capacity
    out = unpack_batch_summary(arr[:core_len], n_words)
    out["lane_done"] = out["lane_done"][:capacity]
    vals = arr[core_len:]
    out["lane_values"] = (vals.view(np.float32) if values_float
                          else vals.astype(np.int32))
    return out


def unpack_batch_summary(packed, n_words: int) -> dict:
    """Host-side inverse of :func:`pack_batch_summary` (forces the
    transfer). Returns ``rounds`` / ``active_lanes`` / ``completed`` /
    ``messages`` (exact int) / ``occupancy_mean`` plus the per-lane
    ``lane_done`` (bool[B]) and ``lane_rounds`` (i32[B]) vectors."""
    arr = np.asarray(packed)
    messages = (int(arr[3]) << 32) + int(arr[4:5].view(np.uint32)[0])
    done_words = arr[_BATCH_HEAD:_BATCH_HEAD + n_words].view(np.uint32)
    bits = (done_words[:, None] >> np.arange(32, dtype=np.uint32)) & 1
    return {
        "rounds": int(arr[0]),
        "active_lanes": int(arr[1]),
        "completed": int(arr[2]),
        "messages": messages,
        "occupancy_mean": float(arr[5:6].view(np.float32)[0]),
        "lane_done": bits.reshape(-1).astype(bool),
        "lane_rounds": arr[_BATCH_HEAD + n_words:].astype(np.int32),
    }

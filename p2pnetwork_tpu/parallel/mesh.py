"""Device-mesh construction for the sharded simulation path.

The reference's "distributed backend" is hand-rolled TCP between OS processes
(SURVEY.md section 2.4); the sim backend's is a JAX device mesh with XLA
collectives over ICI/DCN. Topology scale-out is one mesh axis — a ring of
graph shards — because per-round cross-shard traffic is neighbor exchange,
which rides ICI when the axis is laid out along the physical torus.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_AXIS = "shards"


def ring_mesh(n_shards: Optional[int] = None, axis_name: str = DEFAULT_AXIS,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D mesh of ``n_shards`` devices (default: all local devices).

    Its axis is ``Auto``: the sharded engine places and gathers its
    arrays by sharding annotations, which ``jax.make_mesh``'s default
    ``Explicit`` axes would turn into sharding type errors."""
    devs = list(devices) if devices is not None else jax.devices()
    n = n_shards or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} shards but only {len(devs)} devices")
    return jax.make_mesh((n,), (axis_name,), devices=devs[:n],
                         axis_types=(jax.sharding.AxisType.Auto,))


def shard_spec(mesh: Mesh, axis_name: str = DEFAULT_AXIS) -> NamedSharding:
    """Sharding that splits an array's leading axis across the ring."""
    return NamedSharding(mesh, P(axis_name))

"""Explicit device-mesh construction for the sharded serving tier.

ROADMAP item 1 (docs/SHARDING.md): the fleet scales by *replication*
— every process holds the whole graph — so a graph that cannot fit one
host has no serving story.  This module is the topology layer under
``quiver_tpu.mesh``: it builds the explicit ``jax.sharding.Mesh`` a
shard group serves over, names the two axes the tier partitions along
(``data`` for batch parallelism, ``shard`` for row-range sharding —
the TPU shape of torch-quiver's ``p2pCliqueTopo`` GPU cliques), and
exposes the ``NamedSharding`` helpers + regex partition rules every
sharded structure in ``mesh/feature.py`` / ``mesh/sampler.py`` places
arrays with.

CPU rehearsal: the whole tier runs anywhere via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the suite-wide
virtual mesh ``tests/conftest.py`` already forces).  Device count is a
process-boot decision in XLA — it cannot be raised after ``jax``
initializes — so :func:`require_devices` fails with the exact flag to
set instead of letting ``Mesh`` construction die on an opaque reshape
error.
"""

from __future__ import annotations

import re
from typing import List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["DATA_AXIS", "SHARD_AXIS", "require_devices", "build_mesh",
           "row_shard", "replicated", "shard_devices", "stack_shards",
           "shard_ranges", "match_partition_rules"]

DATA_AXIS = "data"
SHARD_AXIS = "shard"

_FLAG_HINT = ("--xla_force_host_platform_device_count=<n> (in XLA_FLAGS, "
              "before jax initializes)")


def require_devices(n: int) -> None:
    """Fail fast — with the rehearsal flag spelled out — when the
    process has fewer devices than the mesh needs.  XLA fixes the
    device count at backend init, so this is not recoverable in
    process; the error must say how to boot correctly."""
    import jax

    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"mesh needs {n} devices but this process has {have}; on "
            f"CPU, rehearse a virtual slice with {_FLAG_HINT}")


def build_mesh(n_shards: int, data: int = 1,
               devices: Optional[Sequence] = None):
    """An explicit ``(data, shard)`` mesh over ``data * n_shards``
    devices (first devices win when more are available).  ``data=1``
    (the serving default) still carries the axis, so partition specs
    written against the two-axis shape need no rewrite when batch
    parallelism turns on."""
    from ..utils.mesh import make_mesh

    n_shards = int(n_shards)
    data = int(data)
    if n_shards < 1 or data < 1:
        raise ValueError(
            f"mesh axes must be >= 1, got data={data} shard={n_shards}")
    need = data * n_shards
    require_devices(need)
    if devices is None:
        import jax

        devices = jax.devices()[:need]
    return make_mesh((DATA_AXIS, SHARD_AXIS), shape=(data, n_shards),
                     devices=devices)


def row_shard(mesh, axis: str = SHARD_AXIS):
    """Rows partitioned along ``axis``, every other dim replicated —
    the placement of each sharded structure's leading shard dim."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(axis))


def replicated(mesh):
    """Fully replicated placement (frontier ids, combine outputs)."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def shard_devices(mesh, axis: str = SHARD_AXIS) -> np.ndarray:
    """``[replicas, n_shards]`` devices: column ``s`` holds shard ``s``
    (one row per index of the mesh's other axes, which replicate it)."""
    i = mesh.axis_names.index(axis)
    devs = np.moveaxis(mesh.devices, i, -1)
    return devs.reshape(-1, devs.shape[-1])


def stack_shards(mesh, parts, axis: str = SHARD_AXIS):
    """The mesh-wide ``[S, ...]`` array whose slice ``s`` is ``parts[s]``,
    row-sharded along ``axis``.  ``parts[s]`` already lives on shard
    ``s``'s device, so nothing crosses devices (a ``jnp.stack`` would
    first gather every shard onto one of them)."""
    import jax

    devs = shard_devices(mesh, axis)
    arrays = []
    for replica in devs:
        for part, dev in zip(parts, replica):
            block = part[None]
            arrays.append(block if dev in block.devices()
                          else jax.device_put(block, dev))
    shape = (len(parts),) + tuple(parts[0].shape)
    return jax.make_array_from_single_device_arrays(
        shape, row_shard(mesh, axis), arrays)


def shard_ranges(n_rows: int, n_shards: int
                 ) -> Tuple[int, List[Tuple[int, int]]]:
    """Balanced contiguous row ranges: ``rows_per_shard`` (the padded
    per-shard extent — ownership is ``id // rows_per_shard``, a shift
    not a table lookup) and the half-open ``[lo, hi)`` range each shard
    actually owns (the last may be short; its pad rows are zeros and
    unreachable, since every real id maps below ``hi``)."""
    n_rows, n_shards = int(n_rows), int(n_shards)
    if n_rows < 1 or n_shards < 1:
        raise ValueError(f"need n_rows>=1, n_shards>=1; got "
                         f"{n_rows}, {n_shards}")
    rows_per_shard = -(-n_rows // n_shards)
    ranges = [(s * rows_per_shard, min((s + 1) * rows_per_shard, n_rows))
              for s in range(n_shards)]
    return rows_per_shard, ranges


def match_partition_rules(rules: Sequence[Tuple[str, object]], tree):
    """Regex -> ``PartitionSpec`` mapping over a param pytree: the
    first rule whose pattern searches
    the ``/``-joined path of a leaf supplies its spec.  An unmatched
    leaf raises — silent replication of a tensor someone meant to
    shard is how HBM budgets get blown."""
    import jax

    def _assign(path, _leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        for pattern, spec in rules:
            if re.search(pattern, name):
                return spec
        raise ValueError(f"no partition rule matches param {name!r}")

    return jax.tree_util.tree_map_with_path(_assign, tree)

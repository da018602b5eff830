"""Mesh construction + the shared data-parallel axis spec.

Mesh builders are functions (not module-level constants) so importing this
module never touches jax device state.

:class:`AxisSpec` / :func:`shard_slices` are the mesh-tier language the DB
shard tier reuses: ``db/shard.py`` mirrors the ``data`` axis across N
database connections with exactly the partitioning a jax mesh would apply
along its data axis, so a model trained in-DB with ``shards=N`` sees the
same per-shard batches as its dense data-parallel twin.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


@dataclasses.dataclass(frozen=True)
class AxisSpec:
    """One named parallel axis — the piece of a mesh both tiers agree on."""

    name: str
    size: int

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"axis {self.name!r} needs size >= 1, "
                             f"got {self.size}")


def data_axis_spec(mesh) -> AxisSpec:
    """The mesh's data-parallel axis as a spec (pod × data collapsed)."""
    return AxisSpec("data", axis_size(mesh, data_axes(mesh)))


def shard_slices(n_rows: int, n_shards: int) -> list[slice]:
    """Deterministic contiguous partition of ``n_rows`` batch rows across
    ``n_shards``: shard k takes the k-th contiguous block, blocks differ
    by at most one row (the first ``n_rows % n_shards`` shards carry the
    extra).  Fixed order is load-bearing — the shard trainer's AllReduce
    and its determinism guarantee (shards=1 ≡ shards=N) both assume shard
    k always sees the same rows."""
    if n_shards < 1:
        raise ValueError(f"need n_shards >= 1, got {n_shards}")
    if n_rows < n_shards:
        raise ValueError(
            f"cannot partition {n_rows} rows across {n_shards} shards "
            f"(every shard needs at least one row)")
    base, extra = divmod(n_rows, n_shards)
    out, start = [], 0
    for k in range(n_shards):
        stop = start + base + (1 if k < extra else 0)
        out.append(slice(start, stop))
        start = stop
    return out


def make_mesh(shape, axes=("data", "model")):
    """A mesh of the first devices in ``shape`` over ``axes``, every axis
    ``Auto``: GSPMD places what the sharding rules leave open (the
    default ``Explicit`` axes would require a sharding on every op)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes: ('pod', 'data') multi-pod, ('data',) single."""
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size

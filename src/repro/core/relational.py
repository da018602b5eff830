"""Relational matrix representation and relational-algebra execution.

The paper stores a matrix as the relation ``{[i, j, v]}`` (Fig. 1) and maps
matrix algebra onto relational algebra (Listing 4):

  matmul      γ_{m.i, n.j, sum(m.v·n.v)}(m ⋈_{m.j = n.i} n)
  hadamard    m ⋈_{m.i = n.i ∧ m.j = n.j} n,  select m.v·n.v
  transpose   select i as j, j as i, v
  f(X)        select i, j, f(v)

TPU adaptation (DESIGN.md §2): the database's hash join + hash aggregation has
no analogue in VMEM, so we execute the join as a *sort-merge join over the
canonically sorted relation*. For a canonical left operand (one tuple per
cell, row-major) the sort order is fixed by the shape: the tuples come in
runs of ``k`` per outer index ``i`` with ``j`` = 0…k−1, so the join is the
identity alignment against the inner relation's clusters and the group-by
is an *ordered reduction over each fixed run* — the sort-based aggregation
with continuous output that the paper's §8 proposes as future work. Join and
group-by together are then one contraction over ``k``, which runs on the
chip's matrix unit like the dense engine's product. A left operand with
fewer tuples than cells (sparse or padded) has no fixed runs: its join
gathers the matching inner tuples and its group-by is a *segment sum* (a
scatter-add) over ``i``. The join intermediate (``nnz(A) × n``
tuples before aggregation — the thousandfold blow-up of Fig. 5) is explicit
in this formulation and is what `benchmarks/fig5_matmul_memory.py` measures.

Matrices are stored *densely* in the relation (no CSR — §6.2.2 of the paper),
in canonical row-major order. A ``RelTensor`` may also carry fewer valid
tuples than its capacity (``nnz``) for genuinely sparse relations such as the
one-hot matrix or the MoE token→expert assignment; padding rows carry an
out-of-range ``i`` so the group-by drops them (scatter-drop semantics).

The device ops of each step carry a named scope: ``rel.join`` (the
multiply, with its gather on the sparse path), ``rel.group_by`` (the
segment sum; a canonical product's contraction sits inside both) and
``rel.pivot`` (dense ↔ relation), so a profiler trace charges the chip's
time to them. Each product traced counts ``rel.group_by.ordered`` or
``rel.group_by.scatter`` on the active tracer.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..obs.tracer import current


def _canonical_indices(m: int, n: int) -> tuple[jax.Array, jax.Array]:
    """``i`` and ``j`` of the canonical (row-major) ``(m, n)`` relation."""
    return (jnp.repeat(jnp.arange(m, dtype=jnp.int32), n),
            jnp.tile(jnp.arange(n, dtype=jnp.int32), m))


@partial(jax.tree_util.register_dataclass,
         data_fields=("i", "j", "v"), meta_fields=("shape",))
@dataclasses.dataclass
class RelTensor:
    """The relation {[i, j, v]} with logical matrix shape ``shape``."""

    i: jax.Array          # int32[cap] row index; == shape[0] marks padding
    j: jax.Array          # int32[cap] col index
    v: jax.Array          # float[cap] value
    shape: tuple[int, int]

    @property
    def capacity(self) -> int:
        return self.i.shape[0]

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_dense(x: jax.Array) -> "RelTensor":
        """Pivot a dense matrix into the canonical sorted relation."""
        m, n = x.shape
        with jax.named_scope("rel.pivot"):
            i, j = _canonical_indices(m, n)
            return RelTensor(i=i, j=j, v=x.reshape(-1), shape=(m, n))

    def to_dense(self) -> jax.Array:
        """Materialise the relation as a dense matrix (outer-join + coalesce:
        missing cells become 0, as in Listing 5's one-hot construction).
        A canonical relation holds every cell in row-major order, so its
        values are the matrix already; a sparse or padded one scatters."""
        m, n = self.shape
        with jax.named_scope("rel.pivot"):
            if self.is_canonical():
                return self.v.reshape(m, n)
            out = jnp.zeros((m, n), dtype=self.v.dtype)
            return out.at[self.i, self.j].add(self.v, mode="drop")

    def is_canonical(self) -> bool:
        """One tuple per cell, in row-major order (``from_dense``'s layout)."""
        m, n = self.shape
        return self.capacity == m * n

    # -- relational building blocks (Listing 4) ------------------------------
    def transpose(self) -> "RelTensor":
        """``select i as j, j as i, v`` + canonical re-sort.

        The index rename is free. For a canonical relation the re-sort is a
        permutation known from the shape alone: the values are the dense
        ``(m, n) → (n, m)`` transpose and the indices those of an ``(n, m)``
        canonical relation, so no sort runs. A relation with fewer tuples
        than cells (sparse, or padded) is re-sorted by an ``argsort`` of the
        transposed key.
        """
        m, n = self.shape
        if self.is_canonical():
            i, j = _canonical_indices(n, m)
            return RelTensor(i=i, j=j, v=self.v.reshape(m, n).T.reshape(-1),
                             shape=(n, m))
        key = self.j * m + self.i  # int32: capacities here stay < 2^31
        order = jnp.argsort(key)
        return RelTensor(i=self.j[order], j=self.i[order], v=self.v[order],
                         shape=(n, m))

    def map(self, fn) -> "RelTensor":
        """``select i, j, f(v)`` — elementwise function application."""
        return RelTensor(i=self.i, j=self.j, v=fn(self.v), shape=self.shape)

    def _aligned(self, other: "RelTensor") -> None:
        if self.shape != other.shape or self.capacity != other.capacity:
            raise ValueError(
                f"elementwise join needs aligned relations: "
                f"{self.shape}/{self.capacity} vs {other.shape}/{other.capacity}")

    def hadamard(self, other: "RelTensor") -> "RelTensor":
        """Join on both indices; with both relations in canonical sorted
        order the equi-join is the identity alignment (sort-merge join)."""
        self._aligned(other)
        return RelTensor(i=self.i, j=self.j, v=self.v * other.v, shape=self.shape)

    def add(self, other: "RelTensor") -> "RelTensor":
        self._aligned(other)
        return RelTensor(i=self.i, j=self.j, v=self.v + other.v, shape=self.shape)

    def sub(self, other: "RelTensor") -> "RelTensor":
        self._aligned(other)
        return RelTensor(i=self.i, j=self.j, v=self.v - other.v, shape=self.shape)

    def scale(self, c: float) -> "RelTensor":
        return RelTensor(i=self.i, j=self.j, v=self.v * c, shape=self.shape)

    def matmul(self, other: "RelTensor") -> "RelTensor":
        """γ_{m.i, n.j, sum(m.v·n.v)}(m ⋈_{m.j = n.i} n).

        1. JOIN  — each tuple ``(i, k, v)`` of ``self`` meets the ``n``
           tuples of ``other`` with inner index ``k`` (sort-merge join against
           the canonical order). The joined intermediate has
           ``capacity(self) × n`` tuples — the paper's Fig. 5 blow-up.
        2. GROUP BY (m.i, n.j) with sum.

        A canonical ``self`` (``is_canonical()``) is sorted by ``i`` in runs
        of exactly ``k`` tuples with ``j`` = 0…k−1, so tuple ``(i, kk)``
        meets inner cluster ``kk`` without a gather, and the group-by sums
        each run: join and group-by together are one contraction over ``k``,
        handed to the compiler as a matrix product so that the matrix unit
        forms and sums every joined product without materialising the join.
        Its operands are pinned at float32 (``Precision.HIGHEST``), whatever
        precision the caller's context sets. A ``self`` with fewer tuples
        than cells (the sparse one-hot, the MoE assignment, padded
        relations) gathers the inner clusters by ``j`` and groups by a
        segment sum over ``i`` (a scatter-add); its padding tuples
        (``i == m``) are dropped (scatter-drop), mirroring the inner join
        discarding non-matching tuples.
        """
        if self.shape[1] != other.shape[0]:
            raise ValueError(f"matmul: {self.shape} @ {other.shape}")
        if not other.is_canonical():
            raise ValueError("rhs of the join must be the canonical relation")
        m, k = self.shape
        n = other.shape[1]
        rhs_rows = other.v.reshape(k, n)     # clustered by inner index
        if self.is_canonical():
            current().inc("rel.group_by.ordered")
            with jax.named_scope("rel.join"), jax.named_scope("rel.group_by"):
                out = jnp.matmul(self.v.reshape(m, k), rhs_rows,
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
        else:
            current().inc("rel.group_by.scatter")
            with jax.named_scope("rel.join"):
                joined = self.v[:, None] * rhs_rows[self.j]    # (cap, n)
            with jax.named_scope("rel.group_by"):
                out = jax.ops.segment_sum(joined, self.i, num_segments=m)
        return RelTensor.from_dense(out)

    def matmul_intermediate_tuples(self, other: "RelTensor") -> int:
        """Size (in tuples) of the join result before aggregation — the
        quantity Fig. 5 measures ("1000 tuples per entry")."""
        return self.capacity * other.shape[1]


# ---------------------------------------------------------------------------
# data transformation (paper §4.1)
# ---------------------------------------------------------------------------

def one_hot(labels: jax.Array, num_classes: int) -> RelTensor:
    """Listing 5: the sparse relation of ones. ``to_dense`` performs the
    outer join against the full index frame + coalesce(·, 0)."""
    rows = labels.shape[0]
    return RelTensor(
        i=jnp.arange(rows, dtype=jnp.int32),
        j=labels.astype(jnp.int32),
        v=jnp.ones((rows,), dtype=jnp.float32),
        shape=(rows, num_classes),
    )


def one_hot_dense(labels: jax.Array, num_classes: int) -> RelTensor:
    """The materialised (canonical) one-hot relation, as Listing 5 stores it."""
    return RelTensor.from_dense(one_hot(labels, num_classes).to_dense())


def features_to_relation(table: jax.Array) -> RelTensor:
    """Pivot an input table's attributes into the relation (Fig. 3):
    column index j = attribute position, row index i = row number."""
    return RelTensor.from_dense(table)


# ---------------------------------------------------------------------------
# memory model (paper §6.1 / Table 1)
# ---------------------------------------------------------------------------

BYTES_PER_INDEX = 8   # the paper assumes 8 B per index attribute
BYTES_PER_VALUE = 8   # double precision


def relation_bytes(shape: tuple[int, int]) -> int:
    """Storage of the canonical relation: 3 attributes × 8 B per tuple —
    the threefold overhead of §6.2.2."""
    return shape[0] * shape[1] * (2 * BYTES_PER_INDEX + BYTES_PER_VALUE)


def array_bytes(shape: tuple[int, int]) -> int:
    """Storage of the array data type: 8 B per entry."""
    return shape[0] * shape[1] * BYTES_PER_VALUE


def join_intermediate_bytes(m: int, k: int, n: int) -> int:
    """Join result of the matmul before aggregation: m·k tuples each joined
    with n partners, 3 attributes each (i, j, product)."""
    return m * k * n * (2 * BYTES_PER_INDEX + BYTES_PER_VALUE)

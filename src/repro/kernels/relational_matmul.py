"""Pallas TPU kernel: the paper's join + group-by matmul (relational SpMM).

The paper executes ``γ_{m.i,n.j,sum(m.v·n.v)}(m ⋈_{m.j=n.i} n)`` with a hash
join and hash aggregation — a full pipeline breaker that materialises the
joined intermediate (Fig. 4/5). The TPU-native adaptation streams the sorted
relation through VMEM and keeps only an O(block) accumulator — the
"sort-based aggregation with continuous output" of the paper's §8:

  grid = (n/blk_n, nnz/blk_t); for each tuple block
    1. JOIN      one-hot(col_ids) · rhs — the equi-join on ``m.j = n.i``
                 expressed as an MXU matmul against the VMEM-resident rhs
                 column block                           (HBM→VMEM once per j)
    2. SELECT    scale by the tuple values
    3. GROUP BY  one-hot(row_ids)ᵀ · scaled — the segment sum expressed as an
                 MXU matmul, so the aggregation runs on the systolic array
                 instead of a hash table.

Padding tuples carry ``row_ids == m`` → their one-hot row is all-zero, which
drops them exactly like a non-matching inner-join tuple.

VMEM working set per grid cell:
  rhs block (k × blk_n) + tuple block (3 × blk_t) + one-hots
  (blk_t × k, blk_t × m) + accumulator (m × blk_n); about 13 MiB at the
  MNIST shapes (m = 2000, k = 784) with the defaults.

On the TPU ``blk_t`` must be a multiple of 1024: XLA tiles a 1-D int32
array there in 1024-element tiles, and a kernel block must match that
tiling. Callers pad the relation with ``row_ids == m`` tuples to whole
blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(rows_ref, cols_ref, vals_ref, b_ref, o_ref, acc_ref, *,
            m: int, n_tuple_blocks: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = rows_ref[...]                       # (blk_t,) sorted row ids
    cols = cols_ref[...]                       # (blk_t,) inner index
    vals = vals_ref[...]                       # (blk_t,)
    rhs = b_ref[...].astype(jnp.float32)       # (k, blk_n) clustered rhs
    k = rhs.shape[0]

    # JOIN on m.j = n.i via one-hot · MXU
    col_hot = (cols[:, None] ==
               jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
               ).astype(jnp.float32)
    joined = jnp.dot(col_hot, rhs, preferred_element_type=jnp.float32)
    scaled = joined * vals[:, None].astype(jnp.float32)   # SELECT m.v·n.v
    # GROUP BY m.i via one-hot · MXU: padding rows (== m) vanish.
    row_hot = (rows[:, None] ==
               jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
               ).astype(jnp.float32)
    acc_ref[...] += jnp.dot(row_hot.T, scaled,
                            preferred_element_type=jnp.float32)

    @pl.when(t == n_tuple_blocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "blk_t", "blk_n", "interpret"))
def relational_matmul(row_ids: jax.Array, col_ids: jax.Array, vals: jax.Array,
                      b: jax.Array, m: int, *, blk_t: int = 1024,
                      blk_n: int = 128, interpret: bool = False
                      ) -> jax.Array:
    """out (m, n) = group-by-sum of the joined relation; b is (k, n)."""
    nnz = row_ids.shape[0]
    k, n = b.shape
    blk_t = min(blk_t, nnz)
    blk_n = min(blk_n, n)
    if nnz % blk_t or n % blk_n:
        raise ValueError(f"nnz {nnz} % blk_t {blk_t} or n {n} % blk_n {blk_n}")
    n_tuple_blocks = nnz // blk_t
    grid = (n // blk_n, n_tuple_blocks)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, n_tuple_blocks=n_tuple_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_t,), lambda jn, t: (t,)),
            pl.BlockSpec((blk_t,), lambda jn, t: (t,)),
            pl.BlockSpec((blk_t,), lambda jn, t: (t,)),
            pl.BlockSpec((k, blk_n), lambda jn, t: (0, jn)),
        ],
        out_specs=pl.BlockSpec((m, blk_n), lambda jn, t: (0, jn)),
        out_shape=jax.ShapeDtypeStruct((m, n), vals.dtype),
        scratch_shapes=[pltpu.VMEM((m, blk_n), jnp.float32)],
        interpret=interpret,
    )(row_ids, col_ids, vals, b)

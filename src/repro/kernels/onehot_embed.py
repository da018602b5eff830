"""Pallas TPU kernel: one-hot matmul as a row gather (embedding lookup).

Paper §4.1 builds the one-hot relation and multiplies it against a weight
matrix; ``onehot(ids) · E`` touches exactly one row of E per id, so the
TPU-native execution is a scalar-prefetched DMA gather: the id vector is
prefetched (scalar memory), and the BlockSpec index_map steers each grid
step's DMA to the addressed embedding row — HBM traffic is |ids| · d instead
of the |ids| · V one-hot join.

One id per grid step. The table is viewed as ``(V, 1, d)`` so that each
step's ``(1, 1, d)`` block has the array's own last two dimensions, which
the TPU's (8, 128) tiling rule requires of a block this thin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, table_ref, o_ref):
    # The index_map already steered this block's DMA to row ids[i].
    o_ref[...] = table_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def onehot_embed(ids: jax.Array, table: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """out[t, :] = table[ids[t], :]  (onehot(ids) @ table)."""
    (t,) = ids.shape
    v, d = table.shape
    grid = (t,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, d),
                               lambda i, ids_ref: (ids_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, ids_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, d), table.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), table.reshape(v, 1, d))
    return out.reshape(t, d)

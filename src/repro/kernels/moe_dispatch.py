"""Pallas TPU kernel: MoE dispatch — the token→expert relation's join side.

The router emits the relation ``assign(token_i, expert_e, gate_v)`` — the
paper's ``{[i, j, v]}`` matrix at datacenter scale (DESIGN.md §4). Dispatch
gathers each assignment's token row (join on ``i``) and applies the gate
value (select clause), producing the expert-sorted activation buffer that the
per-expert GEMMs consume. The combine side (group-by token, sum) reuses the
``relational_matmul`` aggregation.

Scalar-prefetched gather, one assignment row per grid step. Tokens are
viewed as ``(T, 1, d)`` and gates as ``(S, 1, 1)`` so that every block's
last two dimensions equal the array's, as the TPU's tiling rule requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, gates_ref, o_ref):
    o_ref[...] = x_ref[...] * gates_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_dispatch(x: jax.Array, sort_idx: jax.Array, gates: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """out[s, :] = gates[s] · x[sort_idx[s], :] for expert-sorted slots s."""
    (slots,) = sort_idx.shape
    t, d = x.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda s, idx_ref: (idx_ref[s], 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda s, idx_ref: (s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda s, idx_ref: (s, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, 1, d), x.dtype),
        interpret=interpret,
    )(sort_idx.astype(jnp.int32), x.reshape(t, 1, d),
      gates.reshape(slots, 1, 1))
    return out.reshape(slots, d)

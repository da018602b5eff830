"""Pallas TPU kernel: fused ``sig(X · W)`` — one layer of the paper's model.

Paper §6.3.2: "No optimisation leads to one separate call to the BLAS library
for each operation, which decreases performance. In the future, we plan the
query optimiser to detect and combine subsequent matrix operations … to be
executed as a single library call."  This kernel is that combined call on
TPU: a blocked MXU matmul whose epilogue applies the sigmoid while the output
tile is still in VMEM, so the activation never round-trips to HBM.

grid = (m/blk_m, n/blk_n, k/blk_k); f32 accumulation in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k_blocks: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k_blocks - 1)
    def _epilogue():
        z = acc_ref[...]
        o_ref[...] = (1.0 / (1.0 + jnp.exp(-z))).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("blk_m", "blk_n", "blk_k", "interpret"))
def fused_sigmoid_matmul(x: jax.Array, w: jax.Array, *, blk_m: int = 128,
                         blk_n: int = 128, blk_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    blk_m, blk_n, blk_k = min(blk_m, m), min(blk_n, n), min(blk_k, k)
    if m % blk_m or n % blk_n or k % blk_k:
        raise ValueError(f"dims ({m},{k},{n}) not divisible by blocks "
                         f"({blk_m},{blk_k},{blk_n})")
    n_k_blocks = k // blk_k
    grid = (m // blk_m, n // blk_n, n_k_blocks)
    return pl.pallas_call(
        functools.partial(_kernel, n_k_blocks=n_k_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_m, blk_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((blk_k, blk_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((blk_m, blk_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((blk_m, blk_n), jnp.float32)],
        interpret=interpret,
    )(x, w)

"""Pallas TPU kernel: RWKV-6 time-mix recurrence with VMEM-resident state.

The jnp ``lax.scan`` implementation reads and writes the (H, N, N) matrix
state from HBM every token — 2·S·H·N²·4 B of traffic that dominates the
rwkv6 memory roofline term (EXPERIMENTS.md §Perf). On TPU the state is
small (N² f32 = 16 KiB per head): this kernel pins it in VMEM scratch
across a *sequential* time-block grid, so HBM traffic drops to the
r/k/v/w input stream + the output — the same accumulator pattern as
``relational_matmul``'s group-by.

    o_t = r_t · (S + diag(u) k_t v_tᵀ);   S ← diag(w_t) S + k_t v_tᵀ

grid = (B·H, S/blk_t); the t dimension is sequential (scratch carries S).
``u`` is viewed as ``(BH, 1, N)`` so that its ``(1, 1, N)`` block has the
array's own last two dimensions, as the TPU's tiling rule requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sf_ref,
            s_scr, *, blk_t: int, n_t_blocks: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    u_col = u_ref[0].T                               # (N, 1): scales k-dim

    def step(i, S):
        r_i = r_ref[0, i][None, :]                   # (1, N)
        k_i = k_ref[0, i][None, :]
        v_i = v_ref[0, i][None, :]
        w_i = w_ref[0, i][None, :]
        kv = k_i.T @ v_i                             # (N, N) outer product
        o_i = r_i @ (S + u_col * kv)                 # (1, N)
        o_ref[0, i] = o_i[0]
        return w_i.T * S + kv

    s_fin = jax.lax.fori_loop(0, blk_t, step, s_scr[...])
    s_scr[...] = s_fin

    @pl.when(t == n_t_blocks - 1)
    def _flush():
        sf_ref[0] = s_fin


@functools.partial(jax.jit, static_argnames=("blk_t", "interpret"))
def rwkv6_scan(r, k, v, w, u, s0, *, blk_t: int = 128,
               interpret: bool = False):
    """r/k/v/w: (BH, S, N) f32; u: (BH, N); s0: (BH, N, N).
    Returns (o (BH, S, N), s_fin (BH, N, N))."""
    bh, s, n = r.shape
    blk_t = min(blk_t, s)
    if s % blk_t:
        raise ValueError(f"seq {s} % blk_t {blk_t}")
    n_t = s // blk_t
    grid = (bh, n_t)
    return pl.pallas_call(
        functools.partial(_kernel, blk_t=blk_t, n_t_blocks=n_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_t, n), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, blk_t, n), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, blk_t, n), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, blk_t, n), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, n), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, n, n), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_t, n), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, n, n), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, n), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(bh, 1, n), s0)

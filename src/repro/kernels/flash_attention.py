"""Pallas TPU kernel: blocked causal attention with online softmax.

Beyond-paper optimisation (DESIGN.md §6): the prefill_32k roofline is
dominated by the quadratic attention; a dense-masked softmax materialises the
(S × S) score matrix in HBM and computes the masked upper triangle anyway.
This kernel streams KV blocks through VMEM with the online-softmax recurrence
(running max m, normaliser l, accumulator in f32 scratch) and *skips*
strictly-future blocks, halving both HBM traffic and MXU work for causal
shapes. GQA is handled in the index_map (query head h reads KV head h // G) —
no materialised repeat of K/V.

grid = (B, Hq, S/blk_q, S/blk_k), KV innermost for accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, blk_q: int, blk_k: int, n_k_blocks: int,
            causal: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: block (qi, ki) contributes iff ki·blk_k ≤ qi·blk_q + blk_q − 1.
    live = (ki * blk_k <= qi * blk_q + blk_q - 1) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                      # (blk_q, d)
        k = k_ref[0, 0].astype(jnp.float32)                      # (blk_k, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            kpos = ki * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_scr[...]                                   # (blk_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_k_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "blk_q",
                                             "blk_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D), Hq % Hkv == 0 (GQA)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    blk_q, blk_k = min(blk_q, s), min(blk_k, s)
    if s % blk_q or s % blk_k:
        raise ValueError(f"seq {s} not divisible by blocks {blk_q}/{blk_k}")
    n_k_blocks = s // blk_k
    grid = (b, hq, s // blk_q, n_k_blocks)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k,
                          n_k_blocks=n_k_blocks, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d),
                         lambda bb, h, i, kk: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda bb, h, i, kk: (bb, h // group, kk, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda bb, h, i, kk: (bb, h // group, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, d),
                               lambda bb, h, i, kk: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)

"""Public, jit-compiled entry points for the kernel package.

Every op dispatches between the Pallas kernel and the pure-jnp reference
path.

``use_pallas=True`` compiles the Mosaic kernel for the TPU; off a TPU it
fails rather than fall back. Pass ``interpret=True`` (through ``**kw``) to
execute the kernel body in the Pallas interpreter instead, as the CPU
tests do. ``tests/test_tpu_compile.py`` compiles every kernel for a
described v5e chip.
"""
from __future__ import annotations

import jax

from . import ref
from .flash_attention import flash_attention as _flash_pallas
from .fused_sigmoid_matmul import fused_sigmoid_matmul as _fsm_pallas
from .moe_dispatch import moe_dispatch as _dispatch_pallas
from .onehot_embed import onehot_embed as _embed_pallas
from .relational_matmul import relational_matmul as _relmm_pallas
from .rwkv6_scan import rwkv6_scan as _rwkv6_pallas


def relational_matmul(row_ids, col_ids, vals, b, m: int, *,
                      use_pallas: bool = False, **kw) -> jax.Array:
    if use_pallas:
        return _relmm_pallas(row_ids, col_ids, vals, b, m, **kw)
    return ref.relational_matmul(row_ids, col_ids, vals, b, m)


def fused_sigmoid_matmul(x, w, *, use_pallas: bool = False, **kw) -> jax.Array:
    if use_pallas:
        return _fsm_pallas(x, w, **kw)
    return ref.fused_sigmoid_matmul(x, w)


def onehot_embed(ids, table, *, use_pallas: bool = False, **kw) -> jax.Array:
    if use_pallas:
        return _embed_pallas(ids, table, **kw)
    return ref.onehot_embed(ids, table)


def moe_dispatch(x, sort_idx, gates, *, use_pallas: bool = False, **kw
                 ) -> jax.Array:
    if use_pallas:
        return _dispatch_pallas(x, sort_idx, gates, **kw)
    return ref.moe_dispatch(x, sort_idx, gates)


def moe_combine(expert_out, row_ids, n_tokens: int) -> jax.Array:
    return ref.moe_combine(expert_out, row_ids, n_tokens)


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    use_pallas: bool = False, **kw) -> jax.Array:
    if use_pallas:
        return _flash_pallas(q, k, v, causal=causal, scale=scale, **kw)
    return ref.flash_attention(q, k, v, causal=causal, scale=scale)


def rwkv6_scan(r, k, v, w, u, s0, *, use_pallas: bool = False, **kw):
    if use_pallas:
        return _rwkv6_pallas(r, k, v, w, u, s0, **kw)
    return ref.rwkv6_scan(r, k, v, w, u, s0)

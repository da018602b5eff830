"""Matrix products at a stated precision, the same on every backend.

``f32``  float32 operands, float32 products and sums (``HIGHEST``).
``high`` the three-pass bfloat16 product (``Precision.HIGH``): each operand
         split into a bfloat16 head and a bfloat16 tail, and the tail·tail
         term dropped; written out so that a CPU, which ignores the
         precision flag, computes it too.
``fp8``  each operand scaled to the float8 e4m3 range and rounded to it,
         then multiplied exactly; in the backward pass the incoming
         gradient is rounded too, so that every product of a training
         step is an fp8 product.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "high", "fp8")


def _bf16(x):
    # reduce_precision, not a cast there and back, which the TPU compiler
    # may drop as excess precision
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def fp8_round(x):
    """Per-tensor scaled rounding to float8 e4m3 (largest finite 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    return _ein(spec, fp8_round(a), fp8_round(b))


def _ein_fp8_fwd(spec, a, b):
    ra, rb = fp8_round(a), fp8_round(b)
    return _ein(spec, ra, rb), (ra, rb)


def _ein_fp8_bwd(spec, res, g):
    return jax.vjp(partial(_ein, spec), *res)[1](fp8_round(g))


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def einsum(spec: str, a, b, mode: str = "f32"):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    ein = partial(_ein, spec)
    if mode == "f32":
        return ein(a, b)
    if mode == "fp8":
        return _ein_fp8(spec, a, b)
    if mode == "high":
        ah, bh = _bf16(a), _bf16(b)
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return ein(ah, bh) + ein(ah, bl) + ein(al, bh)
    raise ValueError(f"unknown precision mode {mode!r}; have {MODES}")


def dot(a, b, mode: str = "f32"):
    return einsum("...k,kn->...n", a, b, mode)

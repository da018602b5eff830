"""The one traffic generator: every mix is a data file under ``traffic/``
read by the functions here, and every input is a pure function of
``--seed``.

Seeds may exceed 32 bits; the JAX key made by :func:`seed_key` keeps all
of their bits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A JAX key from all bits of a non-negative ``seed`` and a stream."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def token_batch(key, step, batch: int, seq_len: int, vocab: int) -> dict:
    """Training batch ``step``: uniform token ids, labels shifted by one.
    Jit it with ``step`` traced so that every step is one program."""
    toks = jax.random.randint(jax.random.fold_in(key, step),
                              (batch, seq_len + 1), 0, vocab, jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

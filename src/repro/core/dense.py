"""The "array data type" engine (paper Section 5).

The paper's second backend extends SQL arrays (``float[][]``) with matrix
algebra: ``**`` (matmul), ``*`` (Hadamard), ``-``, ``transpose``, ``sig`` and
elementwise aggregation. Here the array data type is simply a dense
``jnp.ndarray`` and the operations map 1:1 onto XLA ops; XLA's fusion pass
performs the "condensing of subsequent calls" that §6.3.2 plans as future
work for the database's query optimiser.

``eval_node`` is the single-node semantics shared with the relational
engine's fallback path (``core.rel_engine`` densifies, applies the same
rule, re-pivots) — one place defines what every zoo primitive means.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import expr as E
from .autodiff import MapDeriv, ReduceDeriv


def topk_mask(v: jnp.ndarray, k: int) -> jnp.ndarray:
    """The 0/1 indicator of each row's k largest entries, ties broken
    toward the smaller column index — byte-for-byte the ordering of the SQL
    lowering (``order by v desc, j asc``): rank(i, j) = #{m: v[i,m] >
    v[i,j]} + #{m < j: v[i,m] = v[i,j]}."""
    c = v.shape[1]
    gt = (v[:, None, :] > v[:, :, None]).sum(-1)            # (r, j) strict
    tri = jnp.tril(jnp.ones((c, c), dtype=bool), -1)        # m < j
    eq = ((v[:, None, :] == v[:, :, None]) & tri[None]).sum(-1)
    return ((gt + eq) < k).astype(v.dtype)


def row_shift(xv: jnp.ndarray, offset: int) -> jnp.ndarray:
    """out[t] = x[t - offset], zero fill (positive offset shifts down)."""
    t = xv.shape[0]
    if offset == 0:
        return xv
    out = jnp.zeros_like(xv)
    if abs(offset) >= t:
        return out
    if offset > 0:
        return out.at[offset:].set(xv[:-offset])
    return out.at[:offset].set(xv[-offset:])


def affine_scan(av: jnp.ndarray, bv: jnp.ndarray,
                reverse: bool) -> jnp.ndarray:
    """s_t = a_t ∘ s_{t∓1} + b_t down (or up) the rows, s outside = 0."""

    def step(s, ab):
        s2 = ab[0] * s + ab[1]
        return s2, s2

    _, outs = jax.lax.scan(step, jnp.zeros_like(av[0]), (av, bv),
                           reverse=reverse)
    return outs


def mat_affine_scan(av: jnp.ndarray, bv: jnp.ndarray, reverse: bool,
                    transposed: bool) -> jnp.ndarray:
    """s_t = s_{t∓1} · A_t + b_t with row-vector state; ``av`` is the
    (T·D, D) block stack, A_t = av[(t-1)D:tD] (transposed: A_tᵀ)."""
    t, d = bv.shape
    blocks = av.reshape(t, d, d)
    if transposed:
        blocks = jnp.swapaxes(blocks, 1, 2)

    def step(s, ab):
        s2 = s @ ab[0] + ab[1]
        return s2, s2

    _, outs = jax.lax.scan(step, jnp.zeros_like(bv[0]), (blocks, bv),
                           reverse=reverse)
    return outs


def _index_column(node: E.Expr, ev, n_rows: int) -> jnp.ndarray:
    """The (S,) int index column of a Gather/Scatter, bounds-checked when
    concrete.  Out-of-range indices are a contract violation the backends
    resolve differently in silence (jnp clamps gathers, the SQL join drops
    the tuple and the pivot zero-fills), so raise on every eager
    evaluation; under jit tracing the values are abstract and the check is
    skipped — behaviour there is backend-defined."""
    idx = ev(node.idx)[:, 0]
    if not isinstance(idx, jax.core.Tracer):
        lo, hi = int(jnp.min(idx)), int(jnp.max(idx))
        if idx.shape[0] and (lo < 0 or hi >= n_rows):
            raise ValueError(
                f"{type(node).__name__} index relation out of range: "
                f"values span [{lo}, {hi}], valid rows 0..{n_rows - 1}")
    return idx.astype(jnp.int32)


def eval_node(node: E.Expr, ev) -> jnp.ndarray:
    """One node's dense value; ``ev(child)`` supplies child values."""
    if isinstance(node, E.Const):
        return jnp.full(node.shape, node.value, dtype=jnp.float32)
    if isinstance(node, E.MatMul):
        return ev(node.x) @ ev(node.y)
    if isinstance(node, E.Hadamard):
        return ev(node.x) * ev(node.y)
    if isinstance(node, E.Add):
        return ev(node.x) + ev(node.y)
    if isinstance(node, E.Sub):
        return ev(node.x) - ev(node.y)
    if isinstance(node, E.Scale):
        return node.c * ev(node.x)
    if isinstance(node, E.Transpose):
        return ev(node.x).T
    if isinstance(node, MapDeriv):
        return node.fn.df(ev(node.x), ev(node.fx))
    if isinstance(node, ReduceDeriv):
        return (ev(node.x) == ev(node.red)).astype(jnp.float32)
    if isinstance(node, E.Map):
        return node.fn.fn(ev(node.x))
    if isinstance(node, E.RowReduce):
        red = jnp.sum if node.kind == "sum" else jnp.max
        return red(ev(node.x), axis=node.axis, keepdims=True)
    if isinstance(node, E.Softmax):
        return jax.nn.softmax(ev(node.x), axis=1)
    if isinstance(node, E.ArgTopK):
        return topk_mask(ev(node.x), node.k)
    if isinstance(node, E.Gather):
        return ev(node.x)[_index_column(node, ev, node.x.shape[0])]
    if isinstance(node, E.Scatter):
        return jax.ops.segment_sum(ev(node.x),
                                   _index_column(node, ev, node.shape[0]),
                                   num_segments=node.shape[0])
    if isinstance(node, E.RowShift):
        return row_shift(ev(node.x), node.offset)
    if isinstance(node, E.Recurrence):
        return affine_scan(ev(node.a), ev(node.b), node.reverse)
    if isinstance(node, E.MatRecurrence):
        return mat_affine_scan(ev(node.a), ev(node.b), node.reverse,
                               node.transposed)
    if isinstance(node, E.StepOuter):
        xv, yv = ev(node.x), ev(node.y)
        return (xv[:, :, None] * yv[:, None, :]).reshape(node.shape)
    raise TypeError(f"unknown node {type(node)}")


def node_scope(node: E.Expr):
    """The named scope ``ir.<Kind>`` a node's device ops carry, ``<Kind>``
    being its class name as the SQL profiler's ``kind`` column has it, so
    a device trace and a SQL profile name a node alike. Compile-time
    metadata only; a child evaluated inside keeps its own innermost scope."""
    return jax.named_scope(f"ir.{type(node).__name__}")


def evaluate(roots: list[E.Expr], env: dict[str, jnp.ndarray]) -> list[jnp.ndarray]:
    """Evaluate expression DAG(s) with per-node memoisation (CTE caching)."""
    cache: dict[int, jnp.ndarray] = {}

    def ev(node: E.Expr) -> jnp.ndarray:
        if id(node) in cache:
            return cache[id(node)]
        if isinstance(node, E.Var):
            out = env[node.name]
        else:
            with node_scope(node):
                out = eval_node(node, ev)
        cache[id(node)] = out
        return out

    return [ev(r) for r in roots]

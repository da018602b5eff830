"""Relational (SQL-92) engine: evaluates the expression DAG over RelTensors.

Mirrors ``core.dense`` but every node is computed with the relational
building blocks of Listing 4; each memoised node is one CTE of the generated
query (``core.sqlgen`` prints the actual SQL for the same DAG).

The DAG-zoo tier (RowReduce/Softmax/ArgTopK/Gather/Scatter/RowShift/
Recurrence) evaluates through ``dense.eval_node`` on the densified children
and re-pivots the result — the relations stay canonical (dense cell set),
so the round trip is exact; the genuinely relational execution of these
nodes is the generated SQL itself (``core.sqlgen`` → ``repro.db``).
"""
from __future__ import annotations

import jax.numpy as jnp

from . import dense
from . import expr as E
from .autodiff import MapDeriv
from .relational import RelTensor


def evaluate(roots: list[E.Expr], env: dict[str, RelTensor]) -> list[RelTensor]:
    cache: dict[int, RelTensor] = {}

    def ev(node: E.Expr) -> RelTensor:
        if id(node) in cache:
            return cache[id(node)]
        if isinstance(node, E.Var):
            out = env[node.name]
            if not isinstance(out, RelTensor):
                raise TypeError(f"relational engine needs RelTensor for {node.name}")
        else:
            with dense.node_scope(node):
                out = eval_node(node, ev)
        cache[id(node)] = out
        return out

    return [ev(r) for r in roots]


def eval_node(node: E.Expr, ev) -> RelTensor:
    """One node's relation; ``ev(child)`` supplies child relations."""
    if isinstance(node, E.Const):
        return RelTensor.from_dense(
            jnp.full(node.shape, node.value, dtype=jnp.float32))
    if isinstance(node, E.MatMul):
        return ev(node.x).matmul(ev(node.y))
    if isinstance(node, E.Hadamard):
        return ev(node.x).hadamard(ev(node.y))
    if isinstance(node, E.Add):
        return ev(node.x).add(ev(node.y))
    if isinstance(node, E.Sub):
        return ev(node.x).sub(ev(node.y))
    if isinstance(node, E.Scale):
        return ev(node.x).scale(node.c)
    if isinstance(node, E.Transpose):
        return ev(node.x).transpose()
    if isinstance(node, MapDeriv):
        xv, fxv = ev(node.x), ev(node.fx)
        return RelTensor(i=xv.i, j=xv.j, v=node.fn.df(xv.v, fxv.v),
                         shape=xv.shape)
    if isinstance(node, E.Map):
        return ev(node.x).map(node.fn.fn)
    # zoo tier (and ReduceDeriv): shared dense semantics
    return RelTensor.from_dense(
        dense.eval_node(node, lambda c: ev(c).to_dense()))

"""Unified engine interface over the three representations.

``Engine("dense")``      — the array-data-type backend (paper Section 5).
``Engine("relational")`` — the SQL-92 relational backend (paper Section 4).
``Engine("sql")``        — the *in-database* backend: the same DAG rendered
                           as SQL and executed by sqlite/duckdb
                           (:mod:`repro.db.sql_engine`).

All three evaluate the same expression DAG; gradients come from Algorithm 1
(``core.autodiff``), *not* ``jax.grad`` — jax.grad is used only as a test
oracle. ``value_and_grad_fn`` returns a jit-compiled function for the JAX
backends and a plain function for the SQL backend (its "compilation" is the
one-time SQL rendering).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import autodiff, dense, expr as E, rel_engine
from .relational import RelTensor

KINDS = ("dense", "relational", "sql")


class Engine:
    def __init__(self, kind: str, **db_opts):
        """``db_opts`` (``backend=``, ``path=``) reach
        :class:`repro.db.sql_engine.SQLEngine` when ``kind == "sql"``."""
        if kind not in KINDS:
            raise ValueError(f"unknown engine kind {kind!r}; have {KINDS}")
        if db_opts and kind != "sql":
            raise ValueError(f"db options {sorted(db_opts)} only apply to "
                             f"Engine('sql')")
        self.kind = kind
        self._sql = None
        if kind == "sql":
            from ..db.sql_engine import SQLEngine  # lazy: core ↛ db cycle

            self._sql = SQLEngine(**db_opts)

    # -- representation conversion ------------------------------------------
    def lift(self, x: jnp.ndarray):
        return RelTensor.from_dense(x) if self.kind == "relational" else x

    def lower(self, x) -> jnp.ndarray:
        return x.to_dense() if isinstance(x, RelTensor) else x

    # -- evaluation -----------------------------------------------------------
    def evaluate(self, roots: list[E.Expr], env: dict):
        if self.kind == "sql":
            return self._sql.evaluate(roots, env)
        ev = rel_engine.evaluate if self.kind == "relational" else dense.evaluate
        return ev(roots, env)

    def eval_fn(self, roots: list[E.Expr]) -> Callable:
        """jit-compiled evaluator: env dict (dense arrays) → dense outputs.
        For the SQL backend the query is rendered once and executed per
        call (no jit — the database is the executor)."""
        if self.kind == "sql":
            return self._sql.eval_fn(roots)

        @jax.jit
        def fn(env: dict[str, jnp.ndarray]):
            lifted = {k: self.lift(v) for k, v in env.items()}
            return [self.lower(o) for o in self.evaluate(roots, lifted)]

        return fn

    def value_and_grad_fn(self, loss: E.Expr, wrt: list[E.Var]) -> Callable:
        """jit fn: env → (loss value, {var name: gradient}) via Algorithm 1."""
        if self.kind == "sql":
            return self._sql.value_and_grad_fn(loss, wrt)
        grads = autodiff.gradients(loss, wrt)
        roots = [loss] + [grads[v] for v in wrt]

        @jax.jit
        def fn(env: dict[str, jnp.ndarray]):
            lifted = {k: self.lift(v) for k, v in env.items()}
            outs = self.evaluate(roots, lifted)
            loss_val = self.lower(outs[0])
            return loss_val, {v.name: self.lower(g)
                              for v, g in zip(wrt, outs[1:])}

        return fn

    def close(self) -> None:
        if self._sql is not None:
            self._sql.close()


def sgd_step_fn(loss: E.Expr, wrt: list[E.Var], lr: float, engine: Engine
                ) -> Callable:
    """One gradient-descent update — the recursive step of Listing 7/10:
    ``select iter+1, w.v - γ·d_w.v from w_, d_w where …``."""
    vg = engine.value_and_grad_fn(loss, wrt)

    if engine.kind == "sql":
        # every forward/backward evaluation runs in the database; the
        # weight update mirrors Listing 7's final select on the host
        def step(weights, data_env):
            env = {**weights, **data_env}
            loss_val, grads = vg(env)
            new_w = {k: np.asarray(weights[k]) - lr * grads[k]
                     for k in weights}
            return new_w, float(np.mean(loss_val))

        return step

    @jax.jit
    def step(weights: dict[str, jnp.ndarray], data_env: dict[str, jnp.ndarray]):
        env = {**weights, **data_env}
        loss_val, grads = vg(env)
        with jax.named_scope("ir.Update"):
            new_w = {k: weights[k] - lr * grads[k] for k in weights}
        return new_w, jnp.mean(loss_val)

    return step

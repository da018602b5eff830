"""The paper's network trained by recursive-CTE queries (``nn2sql.train``
on an ``Engine``), one query after another, the weights carried over.

Set-up makes the inputs and weights from the seed, compiles the query and
runs the first three queries through it; the window goes on from there.
The check follows those three queries with Listing 2's float64 loop.

The mix's ``ahead_s`` (default 0) is how many seconds of queries, by the
last set-up query's time, the window sends ahead of the one it waits for,
so that a stall of the host leaves the chip fed. At 0 the window waits
for each query's weights before it sends the next."""
from __future__ import annotations

import collections
import math
import time

import jax
import numpy as np

from .. import flops
from ..references import mlp as ref
from ..traffic import seed_key
from .common import norm_gap

CHECK_QUERIES = 3


class Driver:
    SPANS = ("mlp.query",)

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.n = (cfg["rows"], cfg["features"], cfg["hidden"],
                  cfg["classes"])
        self.iters = mix["iters_per_query"]
        self.precision = cfg["matmul_precision"]
        self.attempted = self.failed = 0

    # -- the program ----------------------------------------------------
    def setup(self):
        from repro.core import Engine, nn2sql

        rows, feats, hidden, classes = self.n
        graph = nn2sql.build_graph(nn2sql.MLPSpec(
            rows, feats, hidden, classes, lr=self.cfg["lr"]))
        engine = Engine(self.mix["engine"])
        iters = self.iters

        def mlp_query(w, x, y):
            return nn2sql.train(graph, w, x, y, iters, engine)[0]

        self._query = jax.jit(mlp_query)
        x, y, w0 = jax.jit(
            lambda k: ref.make_inputs(k, self.mix["batches"], rows, feats,
                                      classes, hidden))(seed_key(self.seed))
        self.batches = [(x[b], y[b]) for b in range(self.mix["batches"])]
        self.w0 = jax.tree.map(np.asarray, w0)
        self.w = w0
        self.queries = 0
        self.after = []
        for _ in range(CHECK_QUERIES):
            t0 = time.perf_counter()
            jax.block_until_ready(self.send())
            took = time.perf_counter() - t0
            self.after.append(jax.tree.map(np.asarray, self.w))
        ahead_s = self.mix.get("ahead_s", 0)
        self.ahead = max(1, round(ahead_s / took)) if ahead_s else 0

    def send(self):
        """Dispatch the next query; its weights, not waited for."""
        x, y = self.batches[self.queries % len(self.batches)]
        with jax.default_matmul_precision(self.precision):
            self.w = self._query(self.w, x, y)
        self.queries += 1
        return self.w

    def window(self, seconds: float):
        """Send queries until ``seconds`` have passed, at most ``ahead``
        beyond the one waited for; then wait for all that were sent."""
        start, t0 = self.queries, time.perf_counter()
        sent = collections.deque()
        while True:
            with jax.profiler.TraceAnnotation("mlp.query"):
                sent.append(self.send())
                if len(sent) > self.ahead:
                    jax.block_until_ready(sent.popleft())
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(self.w)
        self.attempted = self.queries - start
        finite = all(np.isfinite(np.asarray(v)).all()
                     for v in jax.tree.leaves(self.w))
        self.failed = 0 if finite else self.attempted

    def end_to_end(self, window_s: float) -> dict:
        iterations = (self.attempted - self.failed) * self.iters
        self.facts = {"window_s": window_s, "model_flops":
                      iterations * flops.mlp_iteration_flops(*self.n)}
        return {self.mix["metric"]: iterations * self.n[0] / window_s}

    def report(self):
        return [f"mlp {self.mix['engine']}: {self.attempted} queries of "
                f"{self.iters} iterations at {'x'.join(map(str, self.n))} "
                f"in the window, up to {self.ahead} sent ahead"]

    def free(self):
        used = self.batches[:CHECK_QUERIES]
        self.xh = [np.asarray(x) for x, _ in used]
        self.yh = [np.asarray(y) for _, y in used]
        del self.batches, self.w, self._query

    # -- the check ------------------------------------------------------
    def reference(self, train) -> list[dict]:
        """Weights after each of the first queries, by ``train``."""
        w, out = self.w0, []
        for q in range(CHECK_QUERIES):
            b = q % len(self.xh)
            w = train(self.xh[b], self.yh[b], w)
            out.append(w)
        return out

    def readings(self, got: list[dict]) -> dict:
        """Per layer, over the weights whose float64 change in the first
        query is at least a thousandth of the largest, ``<layer>_change_err``
        is the median of |change − float64 change| over |float64 change|,
        and ``<layer>_f32_multiple`` is that over the same median for the
        float64 loop rounded to float32 at each step on the same seed: the
        error in units of what a float32 system cannot avoid, which in the
        saturated sigmoids of this network swings from seed to seed. Also
        printed: the worst leaf's gap of change norms after the first and
        the last check query."""
        want = self.reference(self.f64)
        best = ref.train_f64(self.xh[0], self.yh[0], self.w0, self.iters,
                             self.cfg["lr"], rounded=True)

        def change(ws):
            return {k: float(np.linalg.norm(np.asarray(ws[k], np.float64)
                                             - self.w0[k])) for k in ws}

        out = {}
        for layer, key in (("out", "w_ho"), ("hidden", "w_xh")):
            err = self.elem_err(got[0], want[0], key)
            floor = self.elem_err(best, want[0], key)
            out[f"{layer}_change_err"] = err
            out[f"{layer}_f32_multiple"] = (err / floor if floor else
                                            0.0 if not err else math.inf)
        out["first_change_gap"] = norm_gap(change(got[0]), change(want[0]))
        out["change_gap"] = norm_gap(change(got[-1]), change(want[-1]))
        return out

    def elem_err(self, got: dict, want: dict, key: str) -> float:
        ref_d = np.asarray(want[key], np.float64) - self.w0[key]
        got_d = np.asarray(got[key], np.float64) - self.w0[key]
        big = np.abs(ref_d) >= 1e-3 * np.abs(ref_d).max()
        if not ref_d.any():
            return 0.0 if not got_d.any() else math.inf
        return float(np.median(np.abs(got_d[big] - ref_d[big])
                               / np.abs(ref_d[big])))

    def f64(self, x, y, w):
        return ref.train_f64(x, y, w, self.iters, self.cfg["lr"])

    def check(self) -> dict:
        return self.readings(self.after)

"""Core-library tests: relational algebra, Algorithm-1 autodiff, engines."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install repro[test])")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro import obs
from repro.core import Engine, autodiff, dense, nn2sql
from repro.core import expr as E
from repro.core.recursive_cte import history_bytes, recursive_cte
from repro.core.relational import (RelTensor, join_intermediate_bytes,
                                   one_hot, one_hot_dense, relation_bytes)

RNG = np.random.RandomState(0)


def rnd(*shape):
    return jnp.asarray(RNG.randn(*shape), jnp.float32)


def assert_same_tuples(got: RelTensor, want: RelTensor):
    """Bitwise the same relation: shape, and i, j, v in the same order."""
    assert got.shape == want.shape
    for a in "ijv":
        got_a, want_a = getattr(got, a), getattr(want, a)
        assert got_a.dtype == want_a.dtype
        np.testing.assert_array_equal(got_a, want_a)


# ---------------------------------------------------------------------------
# relational representation (paper §4, Listing 4 building blocks)
# ---------------------------------------------------------------------------

class TestRelTensor:
    def test_roundtrip(self):
        a = rnd(7, 5)
        assert np.allclose(RelTensor.from_dense(a).to_dense(), a)

    def test_matmul_matches_dense(self):
        a, b = rnd(6, 9), rnd(9, 4)
        out = RelTensor.from_dense(a).matmul(RelTensor.from_dense(b))
        np.testing.assert_allclose(out.to_dense(), a @ b, rtol=1e-5)

    def test_transpose_is_index_rename(self):
        a = rnd(5, 8)
        np.testing.assert_allclose(
            RelTensor.from_dense(a).transpose().to_dense(), a.T)

    def test_hadamard_join(self):
        a, b = rnd(4, 6), rnd(4, 6)
        out = RelTensor.from_dense(a).hadamard(RelTensor.from_dense(b))
        np.testing.assert_allclose(out.to_dense(), a * b, rtol=1e-6)

    def test_sparse_matmul_with_padding(self):
        """Padding tuples (i == m) must vanish like non-matching joins."""
        b = rnd(8, 5)
        rows = jnp.array([0, 0, 2, 3, 3, 3], jnp.int32)
        cols = jnp.array([1, 3, 0, 7, 2, 2], jnp.int32)
        vals = rnd(6)
        rel = RelTensor(i=jnp.concatenate([rows, jnp.full((4,), 4,
                                                          jnp.int32)]),
                        j=jnp.concatenate([cols,
                                           jnp.zeros((4,), jnp.int32)]),
                        v=jnp.concatenate([vals, jnp.ones((4,))]),
                        shape=(4, 8))
        expect = np.zeros((4, 5), np.float32)
        for r, c, v in zip(rows, cols, vals):
            expect[int(r)] += float(v) * np.asarray(b[int(c)])
        np.testing.assert_allclose(rel.matmul(RelTensor.from_dense(b))
                                   .to_dense(), expect, rtol=1e-5)

    def test_one_hot_matches_listing5(self):
        labels = jnp.array([0, 2, 1, 2], jnp.int32)
        oh = one_hot(labels, 3).to_dense()
        np.testing.assert_allclose(oh, jax.nn.one_hot(labels, 3))
        assert one_hot_dense(labels, 3).is_canonical()

    def test_memory_model_fig5(self):
        """Fig. 5: relational storage = 3× array; join blow-up = 1000×
        tuples per entry for 1000×1000 matmul."""
        assert relation_bytes((1000, 1000)) == 3 * 1000 * 1000 * 8
        assert (join_intermediate_bytes(1000, 1000, 1000)
                == 1000 ** 3 * 24)

    @given(m=st.integers(2, 6), k=st.integers(2, 6), n=st.integers(2, 6),
           seed=st.integers(0, 2 ** 16))
    @settings(max_examples=15, deadline=None)
    def test_matmul_property(self, m, k, n, seed):
        r = np.random.RandomState(seed)
        a = jnp.asarray(r.randn(m, k), jnp.float32)
        b = jnp.asarray(r.randn(k, n), jnp.float32)
        out = RelTensor.from_dense(a).matmul(RelTensor.from_dense(b))
        np.testing.assert_allclose(out.to_dense(), a @ b,
                                   rtol=1e-4, atol=1e-5)

    @given(m=st.integers(2, 6), n=st.integers(2, 6),
           seed=st.integers(0, 2 ** 16))
    @settings(max_examples=15, deadline=None)
    def test_transpose_involution(self, m, n, seed):
        r = np.random.RandomState(seed)
        a = jnp.asarray(r.randn(m, n), jnp.float32)
        rel = RelTensor.from_dense(a)
        np.testing.assert_allclose(rel.transpose().transpose().to_dense(),
                                   a)

    # the canonical re-sort as a sort: the construction a canonical
    # relation's transpose must reproduce without one
    @staticmethod
    def argsort_transpose(rel):
        m, n = rel.shape
        order = jnp.argsort(rel.j * m + rel.i)
        return RelTensor(i=rel.j[order], j=rel.i[order], v=rel.v[order],
                         shape=(n, m))

    # 1×n, n×1, square, and the relational cell's 2000:784 (img) and
    # 2000:200 (a_xh) ratios at CPU sizes
    SHAPES = [(1, 7), (7, 1), (6, 6), (250, 98), (100, 10)]

    @pytest.mark.parametrize("shape", SHAPES)
    def test_canonical_transpose_is_the_argsort_construction(self, shape):
        rel = RelTensor.from_dense(rnd(*shape))
        for got, want in [(rel.transpose(), self.argsort_transpose(rel)),
                          (jax.jit(RelTensor.transpose)(rel),
                           jax.jit(self.argsort_transpose)(rel))]:
            assert got.shape == shape[::-1] and got.is_canonical()
            assert_same_tuples(got, want)

    @pytest.mark.parametrize("shape", SHAPES)
    def test_transpose_twice_is_identity(self, shape):
        rel = RelTensor.from_dense(rnd(*shape))
        assert_same_tuples(rel.transpose().transpose(), rel)

    @pytest.mark.parametrize("classes", [2, 3, 10])
    def test_sparse_one_hot_transposes_by_sort(self, classes):
        labels = jnp.asarray(RNG.randint(0, classes, 9), jnp.int32)
        rel = one_hot(labels, classes)
        assert not rel.is_canonical()
        t = rel.transpose()
        assert t.shape == (classes, 9)
        np.testing.assert_array_equal(t.to_dense(),
                                      jax.nn.one_hot(labels, classes).T)
        # re-sorted into the transposed relation's clustered order
        key = np.asarray(t.i) * 9 + np.asarray(t.j)
        assert (np.diff(key) > 0).all()
        np.testing.assert_array_equal(t.transpose().to_dense(),
                                      rel.to_dense())

    def test_padded_relation_transposes_by_sort(self):
        """Padding tuples (i == m) stay out of the transposed matrix."""
        rows = jnp.array([0, 0, 2, 3, 3], jnp.int32)
        cols = jnp.array([1, 3, 0, 7, 2], jnp.int32)
        vals = rnd(5)
        rel = RelTensor(i=jnp.concatenate([rows, jnp.full((3,), 4,
                                                          jnp.int32)]),
                        j=jnp.concatenate([cols, jnp.zeros((3,), jnp.int32)]),
                        v=jnp.concatenate([vals, jnp.ones((3,))]),
                        shape=(4, 8))
        expect = np.zeros((4, 8), np.float32)
        expect[np.asarray(rows), np.asarray(cols)] = np.asarray(vals)
        t = rel.transpose()
        assert t.shape == (8, 4) and t.capacity == rel.capacity
        np.testing.assert_array_equal(t.to_dense(), expect.T)
        np.testing.assert_array_equal(t.transpose().to_dense(), expect)

    # the group-by as a scatter: the gather-join and segment sum that a
    # canonical left operand's contraction must agree with
    @staticmethod
    def scatter_matmul(rel, other):
        m, k = rel.shape
        joined = rel.v[:, None] * other.v.reshape(k, other.shape[1])[rel.j]
        return RelTensor.from_dense(
            jax.ops.segment_sum(joined, rel.i, num_segments=m))

    @staticmethod
    def assert_f32_product(got, a, b, want):
        """|got − want| within float32 rounding of the sum of the products'
        magnitudes, ``rtol=1e-5`` of ``|a| @ |b|`` per cell."""
        a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err = np.abs(np.asarray(got, np.float64) - np.asarray(want,
                                                             np.float64))
        assert (err <= 1e-5 * (np.abs(a64) @ np.abs(b64))).all()

    # single cells, a row, a column and a one-wide product, and the
    # relational cell's products at CPU sizes (n = 10: the output layer)
    PRODUCTS = [(1, 1, 1), (1, 9, 4), (6, 1, 5), (6, 9, 1), (7, 5, 3),
                (64, 96, 130), (200, 784, 10)]

    @pytest.mark.parametrize("m,k,n", PRODUCTS)
    def test_canonical_matmul_is_the_scatter_construction(self, m, k, n):
        a, b = rnd(m, k), rnd(k, n)
        ra, rb = RelTensor.from_dense(a), RelTensor.from_dense(b)
        want64 = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        for got, want in [(ra.matmul(rb), self.scatter_matmul(ra, rb)),
                          (jax.jit(RelTensor.matmul)(ra, rb),
                           jax.jit(self.scatter_matmul)(ra, rb))]:
            assert got.shape == (m, n) and got.is_canonical()
            np.testing.assert_array_equal(got.i, want.i)
            np.testing.assert_array_equal(got.j, want.j)
            self.assert_f32_product(got.v.reshape(m, n), a, b,
                                    want.v.reshape(m, n))
            self.assert_f32_product(got.v.reshape(m, n), a, b, want64)

    @pytest.mark.parametrize("m,k,n", [(7, 5, 3), (64, 96, 130),
                                       (200, 784, 10)])
    def test_canonical_matmul_is_one_pinned_contraction(self, m, k, n):
        """Join and group-by of a canonical left operand compile to exactly
        one dot, with no reduce and no scatter, asked for at float32
        ("highest") whatever precision the caller's context sets: the
        values are the same under the default and the highest context."""
        ra = RelTensor.from_dense(rnd(m, k))
        rb = RelTensor.from_dense(rnd(k, n))
        got = {}
        for precision in ("default", "highest"):
            with jax.default_matmul_precision(precision):
                lowered = jax.jit(RelTensor.matmul).lower(ra, rb)
            assert "precision = [HIGHEST, HIGHEST]" in lowered.as_text()
            compiled = lowered.compile()
            text = compiled.as_text()
            assert len(re.findall(r"\bdot\(", text)) == 1
            assert not re.search(r"\b(?:reduce|scatter)\(", text)
            got[precision] = compiled(ra, rb).v
        np.testing.assert_array_equal(got["default"], got["highest"])

    @pytest.mark.parametrize("shape", SHAPES)
    def test_canonical_to_dense_is_the_scatter(self, shape):
        rel = RelTensor.from_dense(rnd(*shape))
        scattered = jnp.zeros(shape).at[rel.i, rel.j].add(rel.v)
        for got in (rel.to_dense(), jax.jit(RelTensor.to_dense)(rel)):
            np.testing.assert_array_equal(got, scattered)

    def test_group_by_counts_its_path(self):
        """A canonical left operand takes the ordered path (one contraction);
        the sparse one-hot and a padded relation take the scatter."""
        b = RelTensor.from_dense(rnd(8, 5))
        padded = RelTensor(i=jnp.array([0, 2, 3, 4, 4], jnp.int32),
                           j=jnp.array([1, 0, 7, 0, 0], jnp.int32),
                           v=rnd(5), shape=(4, 8))
        sparse = one_hot(jnp.array([3, 0, 7], jnp.int32), 8)
        for rel, path in [(RelTensor.from_dense(rnd(4, 8)), "ordered"),
                          (padded, "scatter"), (sparse, "scatter")]:
            tracer = obs.Tracer()
            with obs.use(tracer):
                out = rel.matmul(b)
            assert tracer.counters == {f"rel.group_by.{path}": 1}
            self.assert_f32_product(out.to_dense(), rel.to_dense(),
                                    b.to_dense(),
                                    rel.to_dense() @ b.to_dense())


# ---------------------------------------------------------------------------
# Algorithm 1 (reverse-mode AD over matrix expressions)
# ---------------------------------------------------------------------------

class TestAlgorithm1:
    def _graph_env(self, rows=12, feats=4, hidden=6, classes=3, seed=0):
        spec = nn2sql.MLPSpec(rows, feats, hidden, classes)
        g = nn2sql.build_graph(spec)
        r = np.random.RandomState(seed)
        env = {"img": jnp.asarray(r.rand(rows, feats), jnp.float32),
               "one_hot": jnp.asarray(
                   jax.nn.one_hot(r.randint(0, classes, rows), classes)),
               **nn2sql.init_weights(spec, seed=1)}
        return g, env

    def test_matches_jax_grad(self):
        g, env = self._graph_env()
        grads = autodiff.gradients(g.loss, [g.w_xh, g.w_ho])
        gx, gh = dense.evaluate([grads[g.w_xh], grads[g.w_ho]], env)

        def loss(wxh, who):
            axh = jax.nn.sigmoid(env["img"] @ wxh)
            aho = jax.nn.sigmoid(axh @ who)
            return jnp.sum((aho - env["one_hot"]) ** 2)

        jx, jh = jax.grad(loss, argnums=(0, 1))(env["w_xh"], env["w_ho"])
        np.testing.assert_allclose(gx, jx, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(gh, jh, rtol=1e-4, atol=1e-6)

    def test_matches_paper_equations_6_to_11(self):
        """Algorithm 1's output graph == hand-derived Eqs. 6–11."""
        g, env = self._graph_env()
        alg = autodiff.gradients(g.loss, [g.w_xh, g.w_ho])
        man = nn2sql.manual_gradients(g)
        a = dense.evaluate([alg[g.w_xh], alg[g.w_ho]], env)
        m = dense.evaluate([man[g.w_xh], man[g.w_ho]], env)
        np.testing.assert_allclose(a[0], m[0], rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(a[1], m[1], rtol=1e-5, atol=1e-7)

    def test_shared_subexpression_accumulates(self):
        """d/dx (x∘x) = 2x·seed — the leaf rule must accumulate."""
        x = E.var("x", (3, 3))
        z = E.hadamard(x, x)
        grads = autodiff.derive(z, E.const(1.0, (3, 3)))
        val = jnp.asarray(RNG.randn(3, 3), jnp.float32)
        (gx,) = dense.evaluate([grads[x]], {"x": val})
        np.testing.assert_allclose(gx, 2 * val, rtol=1e-6)

    @given(rows=st.integers(2, 10), hidden=st.integers(2, 8),
           seed=st.integers(0, 2 ** 10))
    @settings(max_examples=10, deadline=None)
    def test_property_grad_equivalence(self, rows, hidden, seed):
        g, env = self._graph_env(rows=rows, hidden=hidden, seed=seed)
        grads = autodiff.gradients(g.loss, [g.w_xh])
        (gx,) = dense.evaluate([grads[g.w_xh]], env)

        def loss(wxh):
            axh = jax.nn.sigmoid(env["img"] @ wxh)
            aho = jax.nn.sigmoid(axh @ env["w_ho"])
            return jnp.sum((aho - env["one_hot"]) ** 2)

        np.testing.assert_allclose(gx, jax.grad(loss)(env["w_xh"]),
                                   rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# engines agree with each other and with the NumPy baseline (Listing 2)
# ---------------------------------------------------------------------------

class TestEngines:
    def test_both_engines_match_numpy_listing2(self):
        spec = nn2sql.MLPSpec(30, 4, 8, 3)
        g = nn2sql.build_graph(spec)
        r = np.random.RandomState(3)
        x = jnp.asarray(r.rand(30, 4), jnp.float32)
        y = jnp.asarray(jax.nn.one_hot(r.randint(0, 3, 30), 3))
        w0 = nn2sql.init_weights(spec)
        wn = nn2sql.numpy_train(np.asarray(x), np.asarray(y), 8, 10)
        for kind in ("dense", "relational"):
            wf, _ = nn2sql.train(g, w0, x, y, 10, Engine(kind))
            np.testing.assert_allclose(wf["w_xh"], wn["w_xh"],
                                       rtol=3e-4, atol=3e-5)
            np.testing.assert_allclose(wf["w_ho"], wn["w_ho"],
                                       rtol=3e-4, atol=3e-5)

    def test_relational_equals_dense_forward(self):
        spec = nn2sql.MLPSpec(20, 4, 5, 3)
        g = nn2sql.build_graph(spec)
        r = np.random.RandomState(7)
        x = jnp.asarray(r.rand(20, 4), jnp.float32)
        w = nn2sql.init_weights(spec)
        outs = {}
        for kind in ("dense", "relational"):
            probs = nn2sql.infer(g, Engine(kind))(w, x)
            outs[kind] = probs
        np.testing.assert_allclose(outs["dense"], outs["relational"],
                                   rtol=1e-5, atol=1e-6)

    def test_relational_training_query_sorts_nothing(self, monkeypatch):
        """Every relation the training graph transposes (img, a_xh, w_ho) is
        canonical, so the compiled query holds no sort, and its weights are
        those of the same query re-sorting each transpose by argsort, within
        float32 rounding. The two transposes give the same tuples in the
        same order (``test_canonical_transpose_is_the_argsort_construction``),
        but the argsort's gathered operand is not folded into the contraction
        as the fixed permutation is, so the two queries sum in other orders."""
        spec = nn2sql.MLPSpec(40, 24, 12, 10)
        g = nn2sql.build_graph(spec)
        r = np.random.RandomState(5)
        x = jnp.asarray(r.rand(40, 24), jnp.float32)
        y = jnp.asarray(jax.nn.one_hot(r.randint(0, 10, 40), 10))
        w0 = nn2sql.init_weights(spec)
        eng = Engine("relational")

        def query():
            return jax.jit(
                lambda w, x, y: nn2sql.train(g, w, x, y, 4, eng)[0])

        compiled = query().lower(w0, x, y).compile()
        assert not re.search(r"\bsort\(", compiled.as_text())
        got = compiled(w0, x, y)
        monkeypatch.setattr(RelTensor, "transpose",
                            TestRelTensor.argsort_transpose)
        sorting = query().lower(w0, x, y).compile()
        assert re.search(r"\bsort\(", sorting.as_text())
        want = sorting(w0, x, y)
        for k in ("w_xh", "w_ho"):
            np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)

    def test_relational_training_query_scatters_nothing(self, monkeypatch):
        """Every product of the training graph has a canonical left operand:
        tracing the query counts five ordered group-bys (two forward, three
        backward) and no scatter, the compiled query holds no scatter, and
        its weights are those of the same query grouping by segment sum,
        within float32 rounding."""
        spec = nn2sql.MLPSpec(40, 24, 12, 10)
        g = nn2sql.build_graph(spec)
        r = np.random.RandomState(6)
        x = jnp.asarray(r.rand(40, 24), jnp.float32)
        y = jnp.asarray(jax.nn.one_hot(r.randint(0, 10, 40), 10))
        w0 = nn2sql.init_weights(spec)
        eng = Engine("relational")

        def query():
            return jax.jit(
                lambda w, x, y: nn2sql.train(g, w, x, y, 4, eng)[0])

        tracer = obs.Tracer()
        with obs.use(tracer):
            compiled = query().lower(w0, x, y).compile()
        assert tracer.counters == {"rel.group_by.ordered": 5}
        assert not re.search(r"\bscatter\(", compiled.as_text())
        got = compiled(w0, x, y)
        monkeypatch.setattr(RelTensor, "matmul", TestRelTensor.scatter_matmul)
        scattering = query().lower(w0, x, y).compile()
        assert re.search(r"\bscatter\(", scattering.as_text())
        want = scattering(w0, x, y)
        for k in ("w_xh", "w_ho"):
            np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# recursive CTE semantics (paper §8)
# ---------------------------------------------------------------------------

class TestRecursiveCTE:
    def test_scan_equals_history_final(self):
        base = {"w": jnp.ones((4,))}
        step = lambda c, it: {"w": c["w"] * 0.5}
        fin1, hist = recursive_cte(base, step, 5, materialize_history=True)
        fin2, none = recursive_cte(base, step, 5)
        assert none is None
        np.testing.assert_allclose(fin1["w"], fin2["w"])
        assert hist["w"].shape == (6, 4)          # base + 5 iterations
        np.testing.assert_allclose(hist["w"][-1], fin1["w"])

    def test_history_memory_grows_linearly(self):
        """The paper's observed UNION-ALL growth (§8)."""
        base = {"w": jnp.ones((128, 128))}
        assert history_bytes(base, 10) == 11 * 128 * 128 * 4

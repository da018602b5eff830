"""MoE: the paper's two representations must agree exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install repro[test])")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.nn.moe import MoEConfig, _route, init_moe, moe_ffn

RNG = np.random.RandomState(0)


def make(t=32, d=16, e=8, k=2, ff=32, n_shared=0, cf=1.25, rsm="pre",
         seed=0):
    cfg = lambda impl: MoEConfig(n_experts=e, top_k=k, d_model=d, d_ff=ff,
                                 n_shared=n_shared, capacity_factor=cf,
                                 router_softmax=rsm, impl=impl)
    p = init_moe(jax.random.PRNGKey(seed), cfg("einsum"))
    x = jnp.asarray(np.random.RandomState(seed).randn(t, d), jnp.float32)
    return cfg, p, x


def test_einsum_equals_sort():
    """Array representation ≡ relational representation (same drops)."""
    cfg, p, x = make()
    o1, a1 = moe_ffn(p, x, cfg("einsum"))
    o2, a2 = moe_ffn(p, x, cfg("sort"))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-5)


def test_einsum_equals_sort_with_drops():
    """Tight capacity forces drops; priority must match between impls."""
    cfg, p, x = make(t=64, cf=0.5)
    o1, _ = moe_ffn(p, x, cfg("einsum"))
    o2, _ = moe_ffn(p, x, cfg("sort"))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-3, atol=2e-4)


def test_post_softmax_router_and_shared():
    cfg, p, x = make(n_shared=1, rsm="post", seed=3)
    o1, _ = moe_ffn(p, x, cfg("einsum"))
    o2, _ = moe_ffn(p, x, cfg("sort"))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-3, atol=2e-4)
    assert jnp.isfinite(o1).all()


def test_route_gates_normalised():
    cfg, p, x = make()
    gates, idx, aux = _route(p, x, cfg("einsum"))
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert idx.shape == (32, 2) and float(aux) > 0


def test_gradients_flow_both_impls():
    cfg, p, x = make()
    for impl in ("einsum", "sort"):
        g = jax.grad(lambda pp: jnp.sum(moe_ffn(pp, x, cfg(impl))[0] ** 2)
                     )(p)
        total = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
        assert np.isfinite(total) and total > 0, impl


@given(t=st.integers(8, 48), e=st.sampled_from([4, 8]),
       k=st.integers(1, 3), seed=st.integers(0, 99))
@settings(max_examples=10, deadline=None)
def test_property_impls_agree(t, e, k, seed):
    cfg, p, x = make(t=t, e=e, k=min(k, e), seed=seed)
    o1, _ = moe_ffn(p, x, cfg("einsum"))
    o2, _ = moe_ffn(p, x, cfg("sort"))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=5e-3, atol=5e-4)


def test_shard_partials_sum_to_full():
    """Expert-owner partial combine: summing per-owner partials over a
    partition of the expert range equals the relational result over
    every expert (``_moe_sort_one``, the sort path's body)."""
    from repro.nn.moe import _capacity, _moe_sort_local, _moe_sort_one, _route
    cfg_f, p, x = make(t=40, e=8, k=2, seed=5)
    cfg = cfg_f("sort")
    gates, idx, _ = _route(p, x, cfg)
    cap = _capacity(x.shape[0], cfg)
    full = _moe_sort_one(p, x, cfg, gates, idx)
    halves = sum(
        _moe_sort_local(p["wi"][lo:lo + 4], p["wg"][lo:lo + 4],
                        p["wo"][lo:lo + 4], x, cfg, gates, idx,
                        lo, 4, cap)
        for lo in (0, 4))
    np.testing.assert_allclose(np.asarray(full), np.asarray(halves),
                               rtol=2e-3, atol=2e-4)

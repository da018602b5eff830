"""The FLOP formulas against hand counts, the inputs as a pure function
of the seed, and the float32 unit of the MLP check."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from benchmarks.chip import flops, harness, traffic

GRANITE = harness.resolve(harness.load_spec(),
                          "granite_3_8b_l2.train_4x2048")["config"]
MLP = harness.resolve(harness.load_spec(),
                      "mlp_mnist_h200.rel_train")["config"]


def test_mlp_iteration_flops_by_hand():
    # img·w_xh and imgᵀ·d_xh are 2000x784x200 products of 2*m*k*n each;
    # a_xh·w_ho, d_ho·w_hoᵀ and a_xhᵀ·d_ho are 2000x200x10
    want = 2 * (2 * 2000 * 784 * 200) + 3 * (2 * 2000 * 200 * 10)
    assert want == 1_278_400_000
    assert flops.mlp_iteration_flops(MLP["rows"], MLP["features"],
                                     MLP["hidden"], MLP["classes"]) == want


def test_granite_parameter_count_by_hand():
    d, ff, v = 4096, 12800, 49155
    attn = d * 4096 * 2 + d * 1024 * 2        # q, o and the 8 kv heads
    layer = attn + 3 * d * ff
    assert flops.lm_matmul_params(GRANITE) == 2 * layer + v * d \
        == 599_797_760
    assert flops.lm_params(GRANITE) == 599_797_760 + 5 * d == 599_818_240


def test_granite_params_match_the_programs_layout():
    from benchmarks.chip.kinds.common import program_lm

    lm = program_lm(GRANITE)
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == flops.lm_params(GRANITE)


def test_train_flops_per_token_by_hand():
    # 6N plus causal attention: per layer 3 passes x 2 products x
    # 2*4096 FLOPs per key x 2049/2 keys on average
    want = 6 * 599_797_760 + 2 * 3 * 2 * 2 * 4096 * 2049 / 2
    assert flops.lm_train_flops_per_token(GRANITE, 2048) == want
    assert 3.6e9 < want < 3.8e9


def test_mlp_inputs_are_a_pure_function_of_the_seed():
    from benchmarks.chip.references import mlp as ref

    make = lambda s: ref.make_inputs(traffic.seed_key(s), 2, 16, 8, 3, 4)
    a, b, c = make(2**31 + 5), make(2**31 + 5), make(2**31 + 6)
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(u, v)
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (2, 16, 8) and a[1].shape == (2, 16, 3)
    assert 0.0 <= float(a[0].min()) and float(a[0].max()) < 1.0


def test_rounded_reference_is_float32_at_its_best():
    # every result rounded to float32 stays within float32's rounding of
    # the exact loop, and a float32 loop lands as near to it as to that
    from benchmarks.chip.references import mlp as ref

    x, y, w = ref.make_inputs(traffic.seed_key(3), 1, 64, 32, 4, 16)
    x, y = np.asarray(x[0]), np.asarray(y[0])
    w = {k: np.asarray(v) for k, v in w.items()}
    exact = ref.train_f64(x, y, w, 5, 0.01)
    best = ref.train_f64(x, y, w, 5, 0.01, rounded=True)
    f32 = ref.train_jnp(x, y, w, 5, 0.01, "f32")
    for k in w:
        assert best[k].dtype == np.float64
        assert np.array_equal(best[k], best[k].astype(np.float32))
        gap = np.abs(best[k] - exact[k]).max()
        assert 0 < gap < 1e-5
        assert np.abs(np.asarray(f32[k], np.float64) - exact[k]).max() \
            < 10 * gap


def test_seed_keys_keep_every_bit():
    k = lambda s: np.asarray(jax.random.key_data(traffic.seed_key(s)))
    assert not np.array_equal(k(5), k(5 + 2**32))
    assert np.array_equal(k(2**33 + 1), k(2**33 + 1))
    t1 = traffic.token_batch(traffic.seed_key(9), 3, 2, 8, 50)
    t2 = traffic.token_batch(traffic.seed_key(9), 3, 2, 8, 50)
    assert np.array_equal(t1["tokens"], t2["tokens"])
    assert np.array_equal(t1["tokens"][:, 1:], t1["labels"][:, :-1])

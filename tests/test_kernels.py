"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle.

Every kernel compiles for the TPU by default; these CPU tests ask for
the interpreter explicitly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.RandomState(42)


def rnd(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.randn(*shape), jnp.float32).astype(dtype)


def tol(dtype):
    return dict(rtol=2e-4, atol=2e-5) if dtype == jnp.float32 \
        else dict(rtol=6e-2, atol=3e-2)


@pytest.mark.parametrize("m,k,n,blk_t,blk_n",
                         [(8, 16, 128, 32, 64), (16, 32, 256, 128, 128),
                          (64, 64, 128, 256, 128), (12, 16, 384, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_relational_matmul_dense_coo(m, k, n, blk_t, blk_n, dtype):
    a = rnd((m, k), dtype)
    b = rnd((k, n), dtype)
    rows = jnp.repeat(jnp.arange(m, dtype=jnp.int32), k)
    cols = jnp.tile(jnp.arange(k, dtype=jnp.int32), m)
    vals = a.reshape(-1)
    out = ops.relational_matmul(rows, cols, vals, b, m, use_pallas=True,
                                interpret=True, blk_t=blk_t, blk_n=blk_n)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.relational_matmul(
                                   rows, cols, vals, b, m), np.float32),
                               **tol(dtype))


@pytest.mark.parametrize("nnz,pad", [(32, 0), (48, 16), (8, 56)])
def test_relational_matmul_sparse_padding(nnz, pad):
    m, k, n = 16, 32, 128
    b = rnd((k, n))
    rows = jnp.sort(jnp.asarray(RNG.randint(0, m, nnz), jnp.int32))
    rows = jnp.concatenate([rows, jnp.full((pad,), m, jnp.int32)])
    cols = jnp.asarray(RNG.randint(0, k, nnz + pad), jnp.int32)
    vals = rnd((nnz + pad,))
    out = ops.relational_matmul(rows, cols, vals, b, m, use_pallas=True,
                                interpret=True, blk_t=min(64, nnz + pad),
                                blk_n=64)
    np.testing.assert_allclose(out, ref.relational_matmul(rows, cols, vals,
                                                          b, m),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 256),
                                   (128, 512, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_sigmoid_matmul(m, k, n, dtype):
    x, w = rnd((m, k), dtype), rnd((k, n), dtype)
    out = ops.fused_sigmoid_matmul(x, w, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.fused_sigmoid_matmul(x, w),
                                          np.float32), **tol(dtype))


@pytest.mark.parametrize("t,v,d", [(16, 100, 64), (64, 1000, 128),
                                   (128, 333, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_onehot_embed(t, v, d, dtype):
    ids = jnp.asarray(RNG.randint(0, v, t), jnp.int32)
    table = rnd((v, d), dtype)
    out = ops.onehot_embed(ids, table, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(table[ids], np.float32))


@pytest.mark.parametrize("t,slots,d", [(32, 64, 64), (64, 96, 128)])
def test_moe_dispatch(t, slots, d):
    x = rnd((t, d))
    idx = jnp.asarray(RNG.randint(0, t, slots), jnp.int32)
    gates = jnp.asarray(RNG.rand(slots), jnp.float32)
    out = ops.moe_dispatch(x, idx, gates, use_pallas=True, interpret=True)
    np.testing.assert_allclose(out, ref.moe_dispatch(x, idx, gates),
                               rtol=1e-6)


@pytest.mark.parametrize("b,hq,hkv,s,d,blk",
                         [(1, 4, 4, 128, 64, 64), (2, 8, 2, 256, 64, 128),
                          (1, 8, 1, 256, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, hq, hkv, s, d, blk, causal, dtype):
    q = rnd((b, hq, s, d), dtype)
    k = rnd((b, hkv, s, d), dtype)
    v = rnd((b, hkv, s, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, use_pallas=True,
                              interpret=True, blk_q=blk, blk_k=blk)
    expect = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


def test_flash_attention_matches_jnp_flash():
    """Pallas kernel ≡ the jnp online-softmax twin used by the models."""
    from repro.nn.layers import attend_flash
    q, k, v = rnd((2, 4, 256, 64)), rnd((2, 2, 256, 64)), rnd((2, 2, 256, 64))
    a = ops.flash_attention(q, k, v, causal=True, use_pallas=True,
                            interpret=True)
    b = attend_flash(q, k, v, chunk=128)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("bh,s,n,blk", [(2, 32, 16, 16), (4, 64, 32, 32),
                                        (1, 128, 64, 64)])
def test_rwkv6_scan(bh, s, n, blk):
    r = rnd((bh, s, n))
    k = rnd((bh, s, n))
    v = rnd((bh, s, n))
    w = jnp.asarray(RNG.rand(bh, s, n) * 0.5 + 0.4, jnp.float32)
    u = rnd((bh, n))
    s0 = rnd((bh, n, n)) * 0.1
    o, sf = ops.rwkv6_scan(r, k, v, w, u, s0, use_pallas=True,
                           interpret=True, blk_t=blk)
    o_ref, s_ref = ref.rwkv6_scan(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(s_ref),
                               rtol=3e-4, atol=3e-4)


def test_flash_bf16_scores_close_to_f32():
    """§Perf lever: bf16 score/prob blocks must stay within bf16 noise."""
    from repro.nn.layers import attend_flash
    q = rnd((1, 4, 256, 64), jnp.bfloat16)
    k = rnd((1, 2, 256, 64), jnp.bfloat16)
    v = rnd((1, 2, 256, 64), jnp.bfloat16)
    a = attend_flash(q, k, v, chunk=64)
    b = attend_flash(q, k, v, chunk=64, bf16_scores=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=6e-2, atol=3e-2)

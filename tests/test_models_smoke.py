"""Per-architecture smoke tests (assignment deliverable f).

Each of the ten assigned architectures is instantiated with a REDUCED
config of the same family and runs one forward/train/prefill/decode step on
CPU, asserting output shapes and no NaNs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.nn import layers as L
from repro.nn.model import LM

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def make_batch(cfg):
    if cfg.stub_frontend:
        return {"embeds": jax.random.normal(KEY, (B, S, cfg.d_model),
                                            jnp.float32),
                "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab)}
    return {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
            "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab)}


@pytest.fixture(scope="module")
def models():
    return {}


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_dimensions_match_assignment(arch_id):
    cfg = get_config(arch_id)
    expect = {
        "yi_6b": (32, 4096, 32, 4, 11008, 64000),
        "qwen3_8b": (36, 4096, 32, 8, 12288, 151936),
        "qwen2_5_14b": (48, 5120, 40, 8, 13824, 152064),
        "granite_3_8b": (40, 4096, 32, 8, 12800, 49155),
        "deepseek_v2_lite_16b": (27, 2048, 16, 16, 1408, 102400),
        "dbrx_132b": (40, 6144, 48, 8, 10752, 100352),
        "musicgen_medium": (48, 1536, 24, 24, 6144, 2048),
        "rwkv6_7b": (32, 4096, 64, 64, 14336, 65536),
        "internvl2_1b": (24, 896, 14, 2, 4864, 151655),
        "zamba2_2_7b": (54, 2560, 32, 32, 10240, 32000),
    }[arch_id]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expect, f"{arch_id}: {got} != {expect}"


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_train_step(arch_id):
    cfg = get_config(arch_id, reduced=True)
    lm = LM(cfg)
    params = lm.init(KEY)
    batch = make_batch(cfg)
    loss, metrics = jax.jit(lm.loss_fn)(params, batch)
    assert loss.shape == () and bool(jnp.isfinite(loss)), arch_id
    assert bool(jnp.isfinite(metrics["ce"]))
    grads = jax.grad(lambda p: lm.loss_fn(p, batch)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch_id


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_prefill_and_decode(arch_id):
    cfg = get_config(arch_id, reduced=True)
    lm = LM(cfg)
    params = lm.init(KEY)
    batch = make_batch(cfg)
    logits, cache = jax.jit(lm.prefill)(params, batch)
    assert logits.shape == (B, 1, cfg.vocab), arch_id
    assert bool(jnp.isfinite(logits).all()), arch_id
    cache0 = lm.init_cache(B, S)
    db = ({"embeds": batch["embeds"][:, :1]} if cfg.stub_frontend
          else {"tokens": batch["tokens"][:, :1]})
    lg, cache1 = jax.jit(lm.decode_step)(params, db, cache0, jnp.int32(0))
    assert lg.shape == (B, 1, cfg.vocab) and bool(jnp.isfinite(lg).all())
    # second step must accept the returned cache (stable pytree/dtypes)
    lg2, _ = jax.jit(lm.decode_step)(params, db, cache1, jnp.int32(1))
    assert bool(jnp.isfinite(lg2).all())


@pytest.mark.parametrize("arch_id", ["yi_6b", "deepseek_v2_lite_16b",
                                     "rwkv6_7b", "zamba2_2_7b"])
def test_prefill_matches_decode_path(arch_id):
    """Greedy next-token from prefill == from token-by-token decode."""
    cfg = get_config(arch_id, reduced=True)
    lm = LM(cfg)
    params = lm.init(KEY)
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, 8), 0, cfg.vocab)
    logits_p, _ = jax.jit(lm.prefill)(params, {"tokens": toks})
    cache = lm.init_cache(1, 16)
    for t in range(8):
        logits_d, cache = jax.jit(lm.decode_step)(
            params, {"tokens": toks[:, t:t + 1]}, cache, jnp.int32(t))
    np.testing.assert_allclose(
        np.asarray(logits_p[0, 0], np.float32),
        np.asarray(logits_d[0, 0], np.float32), rtol=0.08, atol=0.05)
    assert int(jnp.argmax(logits_p)) == int(jnp.argmax(logits_d)), arch_id


@pytest.mark.parametrize(
    "hq,hkv,sq,skv,chunk,causal,scale,dtype",
    [pytest.param(4, 2, 32, 32, 8, True, None, jnp.float32, id="gqa"),
     pytest.param(4, 4, 32, 32, 8, True, None, jnp.float32, id="mha"),
     pytest.param(8, 1, 32, 32, 8, True, None, jnp.float32, id="mqa"),
     pytest.param(4, 2, 32, 32, 16, True, 1 / 128, jnp.float32,
                  id="granite_scale"),
     pytest.param(4, 2, 30, 30, 8, True, None, jnp.float32,
                  id="chunk_not_dividing_falls_back"),
     pytest.param(4, 2, 32, 32, 8, False, None, jnp.float32,
                  id="non_causal"),
     pytest.param(4, 2, 16, 32, 8, True, None, jnp.float32,
                  id="q_offset_16"),
     pytest.param(4, 2, 32, 32, 32, True, None, jnp.float32,
                  id="single_block"),
     pytest.param(4, 2, 32, 32, 8, True, None, jnp.bfloat16, id="bf16")])
def test_attend_flash_matches_attend(hq, hkv, sq, skv, chunk, causal,
                                     scale, dtype):
    """The blocked online softmax that full-sequence attention runs equals
    the plain masked softmax: GQA/MHA/MQA grouping, a given scale, the
    fallback when ``chunk`` does not divide the length, no mask, a query
    block that starts at ``skv - sq``, one block, bf16 operands."""
    ks = jax.random.split(jax.random.PRNGKey(hq * 100 + skv + chunk), 3)
    dh = 16
    q = jax.random.normal(ks[0], (2, hq, sq, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (2, hkv, skv, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (2, hkv, skv, dh), jnp.float32).astype(dtype)
    q_offset = skv - sq
    got = L.attend_flash(q, k, v, chunk=chunk, q_offset=q_offset,
                         causal=causal, scale=scale)
    want = L.attend(q, k, v, causal=causal, q_offset=q_offset, scale=scale)
    assert got.shape == want.shape and got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)

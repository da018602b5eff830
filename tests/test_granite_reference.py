"""Granite-3.0's block in the system's ``LM`` against a plain float32
reference written here from the published description (the token
embedding times ``embedding_multiplier``, attention scores times
``attention_multiplier``, each block's attention and MLP output times
``residual_multiplier`` before its residual add, logits divided by
``logits_scaling``, RMSNorm at ``norm_eps``), on seeded random weights at
a small size on the CPU.

The reference reads the system's parameter layout and nothing of
``repro.nn``. For the comparisons the system computes in float32
(``COMPUTE_DTYPE`` patched), so that what is left between the two is the
order of float32 sums; the bfloat16 step is compared with a reference at
the published widths on the chip (``benchmarks/chip``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config

HIGHEST = jax.lax.Precision.HIGHEST
B, S = 2, 16
GRANITE = {"embedding_multiplier": 12.0, "attention_multiplier": 0.0078125,
           "residual_multiplier": 0.22, "logits_scaling": 16.0,
           "norm_eps": 1e-5}
NEUTRAL = {"embedding_multiplier": 1.0, "attention_multiplier": None,
           "residual_multiplier": 1.0, "logits_scaling": 1.0,
           "norm_eps": 1e-6}
#: float32 throughout, sums in another order (flash attention's online
#: softmax, fused norms): a few units in the last place per op, grown
#: through two layers and a 256-way softmax
LOSS_RTOL = 1e-5
#: per leaf, the gradient's norm of differences over its norm: the same
#: rounding through the backward pass (reads 6e-7); leaving out any one
#: of granite's settings reads 0.06 or more
GRAD_RTOL = 1e-5
#: logits of prefill and of decoding through the cache against the full
#: forward pass: float32 sums in another order over at most 16 positions
LOGIT_ATOL = 2e-5


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate-half rotary positions 0..S-1 on x (B,S,H,dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(c, p, x):
    b, s, _ = x.shape
    dh, hkv = c.d_head, c.n_kv_heads
    g = c.n_heads // hkv
    scale = (c.attention_multiplier if c.attention_multiplier is not None
             else dh ** -0.5)
    a = p["attn"]
    h = rmsnorm(x, p["norm1"]["w"], c.norm_eps)
    q = rope(ein("bsd,dk->bsk", h, a["wq"]).reshape(b, s, -1, dh),
             c.rope_theta).reshape(b, s, hkv, g, dh)     # head kv·G + g
    k = rope(ein("bsd,dk->bsk", h, a["wk"]).reshape(b, s, hkv, dh),
             c.rope_theta)
    v = ein("bsd,dk->bsk", h, a["wv"]).reshape(b, s, hkv, dh)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=HIGHEST) * scale
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST).reshape(b, s, -1)
    x = x + c.residual_multiplier * ein("bsk,kd->bsd", o, a["wo"])
    h = rmsnorm(x, p["norm2"]["w"], c.norm_eps)
    m = p["mlp"]
    up = ein("bsd,df->bsf", h, m["wi"]) * jax.nn.silu(
        ein("bsd,df->bsf", h, m["wg"]))
    return x + c.residual_multiplier * ein("bsf,fd->bsd", up, m["wo"])


def ref_logits(c, params, tokens):
    """Logits (B,S,V) of the tied-embedding decoder."""
    x = params["embed"][tokens] * c.embedding_multiplier
    for i in range(c.n_layers):
        x = block(c, jax.tree.map(lambda a: a[i], params["layers"]), x)
    x = rmsnorm(x, params["final_norm"]["w"], c.norm_eps)
    return ein("bsd,vd->bsv", x, params["embed"]) / c.logits_scaling


def ref_loss(c, params, tokens, labels):
    lg = ref_logits(c, params, tokens)
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


# ---------------------------------------------------------------------------
# the system, seeded weights and batch
# ---------------------------------------------------------------------------

def arch(**fields):
    base = get_config("granite_3_8b", reduced=True)
    return dataclasses.replace(base, n_layers=2, d_model=64, n_heads=4,
                               n_kv_heads=2, d_head=16, d_ff=128, vocab=256,
                               **fields)


@pytest.fixture
def f32(monkeypatch):
    from repro.nn import layers
    monkeypatch.setattr(layers, "COMPUTE_DTYPE", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def weights(lm, seed=0):
    """The system's initial weights, with norm gains drawn too so that
    every leaf takes part, and the embedding at a twentieth of its scale,
    so that RMSNorm's epsilon is not lost beside the activations' mean
    square."""
    params = lm.init(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    key = jax.random.PRNGKey(seed + 1)

    def draw(i, path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("['w']"):
            return 1 + 0.2 * jax.random.normal(jax.random.fold_in(key, i),
                                               a.shape)
        return a / 20 if name == "['embed']" else a

    return jax.tree.unflatten(treedef, [draw(i, p, a) for i, (p, a)
                                        in enumerate(leaves)])


def batch(vocab, seed=3):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0, vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def gaps(lm, c_ref, params, b) -> tuple[float, float]:
    """The system's loss and gradients against the reference's at
    ``c_ref``: the loss's relative gap, and the worst leaf's norm of
    gradient differences over its reference norm."""
    got, g_got = jax.value_and_grad(lambda p: lm.loss_fn(p, b)[0])(params)
    want, g_want = jax.value_and_grad(
        lambda p: ref_loss(c_ref, p, b["tokens"], b["labels"]))(params)
    grad = max(float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
               for x, y in zip(jax.tree.leaves(g_got),
                               jax.tree.leaves(g_want)))
    return abs(float(got) - float(want)) / abs(float(want)), grad


def test_loss_and_gradients_match_the_reference(f32):
    from repro.nn.model import LM

    c = arch(**GRANITE)
    lm = LM(c)
    loss_gap, grad_gap = gaps(lm, c, weights(lm), batch(c.vocab))
    assert loss_gap < LOSS_RTOL and grad_gap < GRAD_RTOL, (loss_gap,
                                                           grad_gap)


@pytest.mark.parametrize("field", sorted(GRANITE))
def test_each_multiplier_moves_the_comparison_beyond_its_tolerance(f32,
                                                                   field):
    """The reference with one of granite's settings left neutral is far
    from the system with all of them: the comparison above would catch a
    setting the system dropped."""
    from repro.nn.model import LM

    c = arch(**GRANITE)
    lm = LM(c)
    other = dataclasses.replace(c, **{field: NEUTRAL[field]})
    loss_gap, grad_gap = gaps(lm, other, weights(lm), batch(c.vocab))
    assert max(loss_gap / LOSS_RTOL, grad_gap / GRAD_RTOL) > 10, (
        field, loss_gap, grad_gap)


def test_prefill_then_decode_match_the_full_forward(f32):
    from repro.nn.model import LM

    c = arch(**GRANITE)
    lm = LM(c)
    params = weights(lm)
    toks = batch(c.vocab)["tokens"]
    want = ref_logits(c, params, toks)
    n = S // 2
    last, (ks, vs) = jax.jit(lm.prefill)(params, {"tokens": toks[:, :n]})
    np.testing.assert_allclose(last[:, 0], want[:, n - 1], atol=LOGIT_ATOL)
    ck, cv = lm.init_cache(B, S)
    ck = jax.lax.dynamic_update_slice_in_dim(ck, ks.astype(ck.dtype), 0, 3)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, vs.astype(cv.dtype), 0, 3)
    cache = (ck, cv)
    step = jax.jit(lm.decode_step)
    for t in range(n, S):
        lg, cache = step(params, {"tokens": toks[:, t:t + 1]}, cache,
                         jnp.int32(t))
        np.testing.assert_allclose(lg[:, 0], want[:, t], atol=LOGIT_ATOL,
                                   err_msg=f"position {t}")


#: a multiplier with no bfloat16 value: 0.22 rounds to 0.2197265625 in
#: bfloat16 (0.124 % low) and 1/0.22 to 4.53125 (0.31 % low)
INEXACT = {"embedding_multiplier": 0.22, "residual_multiplier": 0.22,
           "logits_scaling": 1 / 0.22}
#: the mean relative error of 4096 values rounded once to bfloat16 each:
#: unbiased, with a spread of 2e-5 (2**-9 / sqrt(12 * 4096)); a constant
#: rounded to bfloat16 biases it by 1.2e-3 or more
BIAS_ATOL = 1e-4


@pytest.mark.parametrize("field", sorted(INEXACT))
def test_bfloat16_program_applies_each_multiplier_at_its_value(field):
    """In the bfloat16 program (``COMPUTE_DTYPE`` as it is) a scaling is
    taken in float32 and rounded once to bfloat16, never with its
    constant rounded to bfloat16: each scaled value is the exact product
    rounded to nearest, and the values together carry no bias."""
    from repro.nn import layers as L
    from repro.nn.model import LM

    lm = LM(arch(**{field: INEXACT[field]}))
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    val = jax.random.normal(k1, (4, 16, 64)).astype(jnp.bfloat16)
    if field == "residual_multiplier":
        got = lm._residual(jnp.zeros_like(val), val)
        exact = val.astype(jnp.float32) * INEXACT[field]
        x = jax.random.normal(k2, val.shape).astype(jnp.bfloat16)
        np.testing.assert_array_equal(
            lm._residual(x, val), (x.astype(jnp.float32) + exact)
            .astype(jnp.bfloat16))
    elif field == "embedding_multiplier":
        table = val.reshape(-1, 64)
        tokens = jnp.arange(table.shape[0]).reshape(4, 16)
        got = lm._embed({"embed": table}, {"tokens": tokens})
        exact = val.astype(jnp.float32) * INEXACT[field]
    else:
        norm = {"w": jnp.ones((64,), jnp.float32)}
        got = lm._head_input({"final_norm": norm}, val)
        exact = (L.rmsnorm(norm, val, lm.cfg.norm_eps)
                 .astype(jnp.float32) / INEXACT[field])
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got, exact.astype(jnp.bfloat16))
    bias = float(jnp.mean(got.astype(jnp.float32) / exact)) - 1
    assert abs(bias) < BIAS_ATOL, bias


def plain_lm(cfg):
    """``LM`` with the multiplier hooks written as the plain operations
    the model had before granite's scalings: what a neutral
    configuration has to compile to."""
    from repro.nn import layers as L
    from repro.nn.model import LM

    class Plain(LM):
        def _attn_scale(self, dim):
            return dim ** -0.5

        def _residual(self, x, out):
            return x + out

        def _norm(self, p, x):
            return (L.rmsnorm(p, x) if self.cfg.norm == "rmsnorm"
                    else L.layernorm(p, x))

        def _embed(self, params, batch):
            if "embeds" in batch:
                return batch["embeds"].astype(L.COMPUTE_DTYPE)
            return params["embed"][batch["tokens"]].astype(L.COMPUTE_DTYPE)

        def _head_input(self, params, x):
            return self._norm(params["final_norm"], x)

    return Plain(cfg)


@pytest.mark.parametrize("entry", ["loss_fn", "prefill", "decode_step"])
def test_neutral_multipliers_add_nothing_to_the_program(entry):
    """At the neutral values (the defaults, which the one-chip granite
    cell builds from) each entry point lowers to the program of the plain
    operations, and the loss is bitwise that program's."""
    from repro.nn.model import LM

    c = arch(**NEUTRAL)
    lm, plain = LM(c), plain_lm(c)
    params, b = weights(lm), batch(c.vocab)
    args = {"loss_fn": (params, b),
            "prefill": (params, {"tokens": b["tokens"]}),
            "decode_step": (params, {"tokens": b["tokens"][:, :1]},
                            lm.init_cache(B, S), jnp.int32(0))}[entry]
    text = lambda m: jax.jit(getattr(m, entry)).lower(*args).as_text(
        debug_info=False)
    assert text(lm) == text(plain)
    if entry == "loss_fn":
        got = np.float32(jax.jit(lm.loss_fn)(*args)[0])
        want = np.float32(jax.jit(plain.loss_fn)(*args)[0])
        assert got.tobytes() == want.tobytes()
    assert get_config("granite_3_8b") == dataclasses.replace(
        get_config("granite_3_8b"), **NEUTRAL)

"""A plain float32 decoder of the dense GQA family (Llama-style, as the
granite-3.0 dense models are): token embedding, per layer RMSNorm →
grouped-query attention with rotary positions → residual → RMSNorm →
SwiGLU MLP → residual, final RMSNorm, output head tied to the embedding,
mean cross-entropy. AdamW with global-norm clipping for training.

Written from the published description, in plain ``jax.numpy``; every
product goes through :mod:`.precision`, so that the same code is the
reference (``f32``) and the lower-precision control (``fp8``). To fit one
chip at the published widths it recomputes each layer in the backward
pass and takes attention one (sequence, key-value head) at a time and the
loss a block of tokens at a time; that changes the memory, not the
arithmetic.

Parameters use the layout the system under test reads (stacked layers),
made here from the seed: ``initializer_range`` normal weights, unit norm
gains.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import precision as P

LOSS_BLOCK = 1024


def shapes(cfg: dict) -> dict:
    d, dh, ff = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    h, hkv, n = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["num_hidden_layers"])
    out = {"embed": (cfg["vocab_size"], d),
           "final_norm": {"w": (d,)},
           "layers": {"norm1": {"w": (n, d)}, "norm2": {"w": (n, d)},
                      "attn": {"wq": (n, d, h * dh), "wk": (n, d, hkv * dh),
                               "wv": (n, d, hkv * dh), "wo": (n, h * dh, d)},
                      "mlp": {"wi": (n, d, ff), "wg": (n, d, ff),
                              "wo": (n, ff, d)}}}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (d, cfg["vocab_size"])
    return out


def init_params(cfg: dict, key) -> dict:
    """Weights normal with std ``initializer_range``, RMSNorm gains one.
    Call under ``jax.jit`` so that it is one program on the device."""
    tree = shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    leaves = []
    for k, (path, shape) in zip(keys, paths):
        if jax.tree_util.keystr(path).endswith("['w']"):
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(jax.random.normal(k, shape, jnp.float32)
                          * cfg["initializer_range"])
    return jax.tree.unflatten(treedef, leaves)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate-half rotary embedding over positions 0..S-1; x (B,S,H,dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mode):
    """Causal softmax attention; q (B,S,H,dh), k and v (B,S,Hkv,dh), query
    head ``kv·G + g`` reading key-value head ``kv``."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, dh).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b * hkv, g, s, dh)
    kk = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, dh)
    vv = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, dh)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one(args):
        qc, kc, vc = args
        sc = P.einsum("gqd,kd->gqk", qc, kc, mode) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return P.einsum("gqk,kd->gqd", pr, vc, mode)

    o = jax.lax.map(one, (qg, kk, vv)).reshape(b, hkv, g, s, dh)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s, h * dh)


def block(cfg, mode, p, x):
    b, s, _ = x.shape
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    a = p["attn"]
    hn = rmsnorm(x, p["norm1"]["w"], eps)
    q = P.dot(hn, a["wq"], mode).reshape(b, s, -1, dh)
    k = P.dot(hn, a["wk"], mode).reshape(b, s, -1, dh)
    v = P.dot(hn, a["wv"], mode).reshape(b, s, -1, dh)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    x = x + P.dot(attention(q, k, v, mode), a["wo"], mode)
    hn = rmsnorm(x, p["norm2"]["w"], eps)
    m = p["mlp"]
    up = P.dot(hn, m["wi"], mode) * jax.nn.silu(P.dot(hn, m["wg"], mode))
    return x + P.dot(up, m["wo"], mode)


def hidden(cfg, mode, params, tokens):
    """Final-normed hidden states (B,S,d) of ``tokens`` (B,S)."""
    x = params["embed"][tokens]
    layer = jax.checkpoint(partial(block, cfg, mode))
    for i in range(cfg["num_hidden_layers"]):
        x = layer(jax.tree.map(lambda a: a[i], params["layers"]), x)
    return rmsnorm(x, params["final_norm"]["w"], cfg["rms_norm_eps"])


def head(params):
    """The output head as (V, d): the embedding itself when tied."""
    return params["lm_head"].T if "lm_head" in params else params["embed"]


def loss(cfg, mode, params, tokens, labels):
    """Mean next-token cross-entropy, a block of tokens at a time."""
    h = hidden(cfg, mode, params, tokens)
    n, d = tokens.size, h.shape[-1]
    blk = min(LOSS_BLOCK, n)
    w = head(params)

    @jax.checkpoint
    def one(args):
        hc, lc = args
        lg = P.einsum("cd,vd->cv", hc, w, mode)
        gold = jnp.take_along_axis(lg, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    parts = jax.lax.map(one, (h.reshape(n // blk, blk, d),
                              labels.reshape(n // blk, blk)))
    return jnp.sum(parts) / n


def leaf_norms(tree) -> dict:
    """``{path: ‖leaf‖₂}`` as device scalars."""
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in
        jax.tree_util.tree_flatten_with_path(tree)[0]}


def make_train_step(cfg: dict, opt: dict, mode: str):
    """One AdamW step (decoupled weight decay on every leaf, gradients
    clipped to a global norm first). Returns the new state, the loss and
    the per-leaf norms of the clipped gradient."""
    b1, b2 = opt["b1"], opt["b2"]

    def step(p, m, v, t, tokens, labels):
        lval, g = jax.value_and_grad(partial(loss, cfg, mode))(
            p, tokens, labels)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["clip_norm"] / (gnorm + 1e-9)), g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        tf = t.astype(jnp.float32)
        c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf
        p = jax.tree.map(lambda w, a, s: w - opt["lr"] * (
            (a / c1) / (jnp.sqrt(s / c2) + opt["eps"])
            + opt["weight_decay"] * w), p, m, v)
        return p, m, v, lval, leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))

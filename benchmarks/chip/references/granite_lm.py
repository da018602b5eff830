"""A plain float32 Granite-3.0 decoder, trained with AdamW.

Granite's block is the dense GQA block of :mod:`.dense_lm` with four
scalings, as the published modelling code applies them:

* the token embedding times ``embedding_multiplier``;
* attention scores times ``attention_multiplier`` (in place of
  1/sqrt(head_dim));
* each block's attention and MLP output times ``residual_multiplier``
  before its residual add;
* the logits divided by ``logits_scaling``;

and RMSNorm at the file's ``rms_norm_eps``. Parameters, their seeded
values, rotary positions and the norm are :mod:`.dense_lm`'s; every
product goes through :mod:`.precision`, so the same code is the reference
(``f32``) and the lower-precision control (``fp8``).

The training state of the published widths (p, m and v of 8 layers,
20.7 GB) does not fit one chip. The reference places it itself over a
1-D mesh of the devices it is given: each leaf split along the largest
of its dimensions that the number of devices divides (a width, never the
stack of layers, so that each layer's gradient is made split too;
replicated where none divides), batches and activations along their
batch; the compiler partitions the arithmetic from there. To bound the
memory it recomputes each layer in the backward pass (the layers a
``lax.scan``, which also keeps the compile short), takes attention one
key-value head at a time (all sequences together) and the loss a block
of positions at a time; that changes the memory, not the arithmetic.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import precision as P
from .dense_lm import init_params, leaf_norms, rmsnorm, rope, shapes

__all__ = ["init_params", "leaf_norms", "shapes", "mesh", "placed",
           "shardings", "batch_placed", "split_batch", "init_state", "loss",
           "make_train_step"]

AXIS = "chips"
#: positions of every sequence whose logits are formed at once
SEQ_BLOCK = 256


def mesh(devices) -> Mesh:
    """A 1-D mesh of ``devices``."""
    return Mesh(np.asarray(devices), (AXIS,))


def placed(shape, m: Mesh) -> NamedSharding:
    """A leaf split along its largest dimension the mesh's size divides
    (the first of equals)."""
    n = m.devices.size
    spec = [None] * len(shape)
    fits = [i for i, d in enumerate(shape) if d % n == 0]
    if fits:
        spec[max(fits, key=lambda i: (shape[i], -i))] = AXIS
    return NamedSharding(m, PartitionSpec(*spec))


def shardings(tree, m: Mesh):
    """:func:`placed` for every leaf of a tree of arrays or shapes."""
    return jax.tree.map(lambda a: placed(a.shape, m), tree)


def batch_placed(shape, m: Mesh) -> NamedSharding:
    """A batch (rows first) split along its rows where they divide."""
    n = m.devices.size
    return NamedSharding(m, PartitionSpec(
        AXIS if shape[0] % n == 0 else None, *[None] * (len(shape) - 1)))


def split_batch(x, m: Mesh | None, dim: int = 0):
    """Activations ``x`` laid out with their batch dimension ``dim`` over
    ``m`` where it divides (``x`` as it is without a mesh)."""
    if m is None:
        return x
    spec = [None] * x.ndim
    if x.shape[dim] % m.devices.size == 0:
        spec[dim] = AXIS
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, PartitionSpec(*spec)))


def attention(q, k, v, scale, mode, m=None):
    """Causal softmax attention, scores times ``scale``; q (B,S,H,dh), k
    and v (B,S,Hkv,dh), query head ``kv·G + g`` reading key-value head
    ``kv``."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = split_batch(q.reshape(b, s, hkv, g, dh).transpose(2, 0, 3, 1, 4),
                     m, 1)
    kk = split_batch(k.transpose(2, 0, 1, 3), m, 1)
    vv = split_batch(v.transpose(2, 0, 1, 3), m, 1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one(args):
        qc, kc, vc = args
        sc = P.einsum("bgqd,bkd->bgqk", qc, kc, mode) * scale
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return P.einsum("bgqk,bkd->bgqd", pr, vc, mode)

    o = jax.lax.map(one, (qg, kk, vv))              # (Hkv, B, G, S, dh)
    return o.transpose(1, 3, 0, 2, 4).reshape(b, s, h * dh)


def block(cfg, mode, m, p, x):
    b, s, _ = x.shape
    x = split_batch(x, m)
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    r = cfg["residual_multiplier"]
    a = p["attn"]
    hn = rmsnorm(x, p["norm1"]["w"], eps)
    q = P.dot(hn, a["wq"], mode).reshape(b, s, -1, dh)
    k = P.dot(hn, a["wk"], mode).reshape(b, s, -1, dh)
    v = P.dot(hn, a["wv"], mode).reshape(b, s, -1, dh)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    o = attention(q, k, v, cfg["attention_multiplier"], mode, m)
    x = x + r * P.dot(o, a["wo"], mode)
    hn = rmsnorm(x, p["norm2"]["w"], eps)
    mlp = p["mlp"]
    up = P.dot(hn, mlp["wi"], mode) * jax.nn.silu(P.dot(hn, mlp["wg"],
                                                          mode))
    return split_batch(x + r * P.dot(up, mlp["wo"], mode), m)


def hidden(cfg, mode, params, tokens, m=None):
    """Final-normed hidden states (B,S,d) of ``tokens`` (B,S)."""
    x = split_batch(params["embed"][tokens] * cfg["embedding_multiplier"], m)
    layer = jax.checkpoint(partial(block, cfg, mode, m))
    x, _ = jax.lax.scan(lambda h, p: (layer(p, h), None), x,
                        params["layers"])
    return rmsnorm(x, params["final_norm"]["w"], cfg["rms_norm_eps"])


def loss(cfg, mode, params, tokens, labels, m=None):
    """Mean next-token cross-entropy, ``SEQ_BLOCK`` positions of every
    sequence at a time (the head is the tied embedding)."""
    h = hidden(cfg, mode, params, tokens, m)
    b, s, d = h.shape
    c = min(SEQ_BLOCK, s)
    w = params["embed"]

    @jax.checkpoint
    def one(args):
        hc, lc = args
        lg = P.einsum("bcd,vd->bcv", hc, w, mode) / cfg["logits_scaling"]
        gold = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    hb = split_batch(h.reshape(b, s // c, c, d).transpose(1, 0, 2, 3), m, 1)
    lb = labels.reshape(b, s // c, c).transpose(1, 0, 2)
    return jnp.sum(jax.lax.map(one, (hb, lb))) / (b * s)


def init_state(cfg: dict, key, m: Mesh, moments: bool = True):
    """Seeded parameters, and zero Adam moments unless not ``moments``,
    made placed on ``m``."""
    def make(k):
        p = init_params(cfg, k)
        z = lambda: jax.tree.map(jnp.zeros_like, p)
        return (p, z(), z()) if moments else p

    sh = shardings(jax.eval_shape(partial(init_params, cfg), key), m)
    return jax.jit(make, out_shardings=(sh, sh, sh) if moments else sh)(key)


def make_train_step(cfg: dict, opt: dict, mode: str, m: Mesh,
                    batch_shape):
    """One AdamW step (decoupled weight decay on every leaf, gradients
    clipped to a global norm first) on ``m``: p, m and v stay placed by
    :func:`shardings`, tokens and labels of ``batch_shape`` by
    :func:`batch_placed`. Returns the new state, the loss and the per-leaf norms
    of the clipped gradient."""
    b1, b2 = opt["b1"], opt["b2"]

    def step(p, mo, v, t, tokens, labels):
        lval, g = jax.value_and_grad(partial(loss, cfg, mode, m=m))(
            p, tokens, labels)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["clip_norm"] / (gnorm + 1e-9)), g)
        mo = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mo, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        tf = t.astype(jnp.float32)
        c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf
        p = jax.tree.map(lambda w, a, s: w - opt["lr"] * (
            (a / c1) / (jnp.sqrt(s / c2) + opt["eps"])
            + opt["weight_decay"] * w), p, mo, v)
        return p, mo, v, lval, leaf_norms(g)

    state = shardings(jax.eval_shape(partial(init_params, cfg),
                                     jax.random.PRNGKey(0)), m)
    rep = NamedSharding(m, PartitionSpec())
    tok = batch_placed(batch_shape, m)
    return jax.jit(step, donate_argnums=(0, 1, 2),
                   in_shardings=(state, state, state, rep, tok, tok),
                   out_shardings=(state, state, state, rep, rep))

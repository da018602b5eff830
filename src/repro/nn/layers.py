"""Shared NN building blocks (pure JAX, dict-of-arrays params).

Conventions:
  * params are nested dicts of jnp arrays; init functions take an rng key
    and return the dict. Stacked-layer params get a leading L axis and are
    consumed by ``lax.scan`` (scan-over-layers keeps HLO size and compile
    time O(1) in depth).
  * compute dtype is bf16 (params stored f32, cast at use); softmax,
    normalisation statistics and losses are f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

COMPUTE_DTYPE = jnp.bfloat16
#: mesh axes that split the batch (``launch/mesh.py``'s data axes)
DATA_AXES = ("pod", "data")


def cdt(x):
    return x.astype(COMPUTE_DTYPE)


def split_tokens(x, over_model: bool = False):
    """Activations (B, S, ...) laid out with the batch split over the data
    axes of the mesh the program is traced under (``jax.set_mesh``) and,
    ``over_model``, the positions over its ``model`` axis, where the axes
    divide the dims. Left to itself GSPMD may gather the batch to meet
    the FSDP-split weights. Without a mesh ``x`` is returned as it is,
    with nothing added to the program."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = [None] * x.ndim
    dp = tuple(a for a in mesh.axis_names if a in DATA_AXES)
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    if dp and x.shape[0] % n == 0:
        spec[0] = dp
    if (over_model and "model" in mesh.axis_names
            and x.shape[1] % mesh.shape["model"] == 0):
        spec[1] = "model"
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, scale: float | None = None):
    fan_in = shape[0] if len(shape) == 2 else shape[-2]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int):
    return {"w": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["w"]).astype(x.dtype)


def layernorm_init(d: int):
    return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_table(seq_len: int, dim: int, theta: float = 1e4, offset: int = 0):
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)  # (S, dim/2)


def apply_rope(x, cos, sin):
    """x: (..., S, d). Rotate-half convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    shape = (1,) * (x.ndim - 2) + cos.shape
    c = cos.reshape(shape).astype(x.dtype)
    s = sin.reshape(shape).astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, d: int, ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi": dense_init(k1, (d, ff)), "wg": dense_init(k2, (d, ff)),
            "wo": dense_init(k3, (ff, d))}


def swiglu(p, x):
    h = jnp.dot(x, cdt(p["wi"])) * jax.nn.silu(jnp.dot(x, cdt(p["wg"])))
    return jnp.dot(h, cdt(p["wo"]))


def gelu_mlp_init(key, d: int, ff: int):
    k1, k2 = jax.random.split(key)
    return {"wi": dense_init(k1, (d, ff)), "bi": jnp.zeros((ff,), jnp.float32),
            "wo": dense_init(k2, (ff, d)), "bo": jnp.zeros((d,), jnp.float32)}


def gelu_mlp(p, x):
    h = jax.nn.gelu(jnp.dot(x, cdt(p["wi"])) + cdt(p["bi"]))
    return jnp.dot(h, cdt(p["wo"])) + cdt(p["bo"])


# ---------------------------------------------------------------------------
# attention (GQA, optional qk-norm / qkv-bias); dense-masked jnp path.
# ---------------------------------------------------------------------------

def gqa_init(key, d: int, n_heads: int, n_kv: int, d_head: int,
             qkv_bias: bool = False, qk_norm: bool = False):
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d, n_heads * d_head)),
         "wk": dense_init(ks[1], (d, n_kv * d_head)),
         "wv": dense_init(ks[2], (d, n_kv * d_head)),
         "wo": dense_init(ks[3], (n_heads * d_head, d))}
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv * d_head,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv * d_head,), jnp.float32)
    if qk_norm:
        p["q_norm"] = rmsnorm_init(d_head)
        p["k_norm"] = rmsnorm_init(d_head)
    return p


def _split_heads(x, n, d_head):
    b, s, _ = x.shape
    return x.reshape(b, s, n, d_head).transpose(0, 2, 1, 3)  # (B, H, S, dh)


def gqa_project_qkv(p, x, n_heads: int, n_kv: int, d_head: int,
                    cos=None, sin=None):
    q = jnp.dot(x, cdt(p["wq"]))
    k = jnp.dot(x, cdt(p["wk"]))
    v = jnp.dot(x, cdt(p["wv"]))
    if "bq" in p:
        q, k, v = q + cdt(p["bq"]), k + cdt(p["bk"]), v + cdt(p["bv"])
    q = _split_heads(q, n_heads, d_head)
    k = _split_heads(k, n_kv, d_head)
    v = _split_heads(v, n_kv, d_head)
    if "q_norm" in p:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def attend(q, k, v, causal: bool = True, q_offset: int = 0,
           kv_len_mask=None, scale: float | None = None):
    """softmax(scale·q·kᵀ)·v with GQA head grouping. q: (B,Hq,Sq,dh), k/v
    (B,Hkv,Skv,dh).

    ``q_offset``: absolute position of q[...,0,:] (decode: Skv-1).
    ``kv_len_mask``: optional (B, Skv) validity mask for ragged caches.
    ``scale``: the score scale, 1/sqrt(dh) when None (every ``attend*``).
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = dh ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, group, sq, dh)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal and sq > 1:
        qpos = q_offset + jnp.arange(sq)
        kpos = jnp.arange(skv)
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    if kv_len_mask is not None:
        logits = jnp.where(kv_len_mask[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, sq, v.shape[-1]).astype(q.dtype)  # d_v may ≠ d_q (MLA)


def attend_flash(q, k, v, chunk: int = 1024, q_offset: int = 0,
                 causal: bool = True, bf16_scores: bool = False,
                 scale: float | None = None):
    """Online-softmax blocked attention (jnp twin of kernels/flash_attention).

    Unrolled q/kv chunk loops: strictly-future blocks are *not emitted*, so
    the compiled HLO carries only the ~S²/2 causal work and O(chunk²) live
    score blocks, half the attention compute of a dense mask. When
    ``chunk`` does not divide both lengths it falls back to ``attend``.
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = dh ** -0.5 if scale is None else scale
    if sq % chunk or skv % chunk:
        return attend(q, k, v, causal=causal, q_offset=q_offset,
                      scale=scale)
    sdt = jnp.bfloat16 if bf16_scores else jnp.float32
    qg = q.reshape(b, hkv, group, sq, dh)
    outs = []
    for c in range(sq // chunk):
        q_c = qg[:, :, :, c * chunk:(c + 1) * chunk].astype(sdt)
        hi_pos = q_offset + (c + 1) * chunk          # last visible kv + 1
        n_kv = skv // chunk if not causal else -(-hi_pos // chunk)
        m = jnp.full(q_c.shape[:-1], -1e30, jnp.float32)
        l = jnp.zeros(q_c.shape[:-1], jnp.float32)
        acc = jnp.zeros(q_c.shape[:-1] + (v.shape[-1],), jnp.float32)
        for i in range(n_kv):
            k_c = k[:, :, i * chunk:(i + 1) * chunk].astype(sdt)
            v_c = v[:, :, i * chunk:(i + 1) * chunk].astype(sdt)
            # with bf16_scores the S and P blocks — the dominant HBM
            # traffic of long-context attention — stay bf16; the online
            # max/normaliser statistics remain f32
            s = (jnp.einsum("bhgqd,bhkd->bhgqk", q_c, k_c,
                            preferred_element_type=jnp.float32) * scale)
            if causal and (i + 1) * chunk > q_offset + c * chunk:
                qpos = (q_offset + c * chunk +
                        jnp.arange(chunk)[:, None])
                kpos = i * chunk + jnp.arange(chunk)[None, :]
                s = jnp.where((qpos >= kpos)[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]).astype(sdt)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1).astype(jnp.float32)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, v_c,
                preferred_element_type=jnp.float32)
            m = m_new
        outs.append((acc / l[..., None]))
    out = jnp.concatenate(outs, axis=3)
    return out.reshape(b, hq, sq, v.shape[-1]).astype(q.dtype)


def auto_chunk(seq_len: int) -> int:
    """Flash chunk size: ≥1024, ≤4096, ~seq/8 (bounds HLO size at 32k)."""
    return max(1024, min(4096, seq_len // 8))


def merge_heads(x):
    b, h, s, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * dh)


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention (kv_lora compression)
# ---------------------------------------------------------------------------

def mla_init(key, d: int, n_heads: int, kv_lora: int, d_nope: int,
             d_rope: int, d_v: int):
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, n_heads * (d_nope + d_rope))),
        "wkv_a": dense_init(ks[1], (d, kv_lora)),       # compress
        "kv_a_norm": rmsnorm_init(kv_lora),
        "wk_b": dense_init(ks[2], (kv_lora, n_heads * d_nope)),
        "wv_b": dense_init(ks[3], (kv_lora, n_heads * d_v)),
        "wk_rope": dense_init(ks[4], (d, d_rope)),      # shared rope key
        "wo": dense_init(ks[5], (n_heads * d_v, d)),
    }


def mla_qkv(p, x, n_heads: int, d_nope: int, d_rope: int, d_v: int,
            cos, sin):
    """Returns q (B,H,S,d_nope+d_rope), k (same), v (B,H,S,d_v).

    The latent c_kv (B,S,kv_lora) + shared k_rope (B,S,d_rope) are what a
    serving cache stores — the paper-style memory saving; here we expand to
    full heads for the attention product (the decode path,
    ``LM._mla_block_decode``, absorbs the up-projections instead)."""
    b, s, _ = x.shape
    q = jnp.dot(x, cdt(p["wq"])).reshape(b, s, n_heads, d_nope + d_rope)
    q = q.transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :d_nope], q[..., d_nope:]
    q_rope = apply_rope(q_rope, cos, sin)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    c_kv = rmsnorm(p["kv_a_norm"], jnp.dot(x, cdt(p["wkv_a"])))
    k_nope = jnp.dot(c_kv, cdt(p["wk_b"])).reshape(b, s, n_heads, d_nope)
    k_nope = k_nope.transpose(0, 2, 1, 3)
    k_rope = apply_rope(jnp.dot(x, cdt(p["wk_rope"]))[:, None], cos, sin)
    k_rope = jnp.broadcast_to(k_rope, (b, n_heads, s, d_rope))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    v = jnp.dot(c_kv, cdt(p["wv_b"])).reshape(b, s, n_heads, d_v)
    v = v.transpose(0, 2, 1, 3)
    return q, k, v, c_kv

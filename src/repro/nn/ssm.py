"""Recurrent sequence mixers: RWKV-6 (Finch) and Mamba-2 (SSD).

The paper's technique (matmul-as-join) does not apply to these data-dependent
recurrences — they are implemented as first-class JAX layers so the
assigned ``rwkv6-7b`` and ``zamba2-2.7b`` architectures run without it.

RWKV-6 time-mix: per-head matrix state S (N×N), *vector*-valued
data-dependent decay w_t (the Finch contribution, arXiv:2404.05892):

    o_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ)
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

Executed as a ``lax.scan`` over time (vectorised over batch × heads). A
chunkwise-parallel form exists (GLA-style) but its factorised decay
``exp(−a_i)`` overflows f32 for fast-decaying channels; the scan is exact.

Mamba-2 SSD: *scalar*-per-head decay makes the chunked form stable, so we
implement the block-decomposition of the SSD paper (arXiv:2405.21060):
diagonal blocks use the masked-decay matmul, off-diagonal blocks flow through
a chunk-state recurrence. A naive scan oracle validates it in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import dense_init, rmsnorm, rmsnorm_init


# ---------------------------------------------------------------------------
# RWKV-6 time mix
# ---------------------------------------------------------------------------

def rwkv6_init(key, d: int, n_heads: int, lora_rank: int = 64):
    n = d // n_heads
    ks = jax.random.split(key, 10)
    return {
        "mu": {nm: jnp.full((d,), 0.5, jnp.float32)
               for nm in ("r", "k", "v", "w", "g")},
        "wr": dense_init(ks[0], (d, d)), "wk": dense_init(ks[1], (d, d)),
        "wv": dense_init(ks[2], (d, d)), "wg": dense_init(ks[3], (d, d)),
        "wo": dense_init(ks[4], (d, d)),
        "w0": jnp.full((d,), -2.0, jnp.float32),     # base decay ≈ exp(-e^-2)
        "w_lora_a": dense_init(ks[5], (d, lora_rank)),
        "w_lora_b": dense_init(ks[6], (lora_rank, d), scale=1e-2),
        "u": dense_init(ks[7], (n_heads, n), scale=0.5),
        "ln_x": {"w": jnp.ones((d,), jnp.float32),
                 "b": jnp.zeros((d,), jnp.float32)},
    }


def _token_shift(x, x_prev):
    """x_{t-1} stream; ``x_prev`` (B, 1, d) is the carry entering this call."""
    return jnp.concatenate([x_prev.astype(x.dtype), x[:, :-1]], axis=1)


def _rwkv6_projections(p, x, x_prev, n_heads: int):
    b, s, d = x.shape
    n = d // n_heads
    xs = _token_shift(x, x_prev)
    mix = {nm: x + (xs - x) * p["mu"][nm].astype(x.dtype)
           for nm in ("r", "k", "v", "w", "g")}
    r = jnp.dot(mix["r"], p["wr"].astype(x.dtype))
    k = jnp.dot(mix["k"], p["wk"].astype(x.dtype))
    v = jnp.dot(mix["v"], p["wv"].astype(x.dtype))
    g = jax.nn.silu(jnp.dot(mix["g"], p["wg"].astype(x.dtype)))
    # Finch: data-dependent vector decay via LoRA
    lora = jnp.dot(jnp.tanh(jnp.dot(mix["w"].astype(jnp.float32),
                                    p["w_lora_a"])), p["w_lora_b"])
    w = jnp.exp(-jnp.exp((p["w0"] + lora).astype(jnp.float32)))  # (B,S,d)
    hd = lambda t: t.reshape(b, s, n_heads, n)
    return hd(r), hd(k), hd(v), g, hd(w)


def rwkv6_time_mix(p, x, n_heads: int, state=None):
    """x: (B, S, d). state: (x_prev (B,1,d), S (B,H,N,N)) or None.
    Returns (out (B,S,d), new_state)."""
    b, s, d = x.shape
    n = d // n_heads
    if state is None:
        x_prev = jnp.zeros((b, 1, d), x.dtype)
        s0 = jnp.zeros((b, n_heads, n, n), jnp.float32)
    else:
        x_prev, s0 = state
    r, k, v, g, w = _rwkv6_projections(p, x, x_prev, n_heads)
    u = p["u"]                                           # (H, N)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                         # (B,H,N) each
        kv = k_t[..., :, None] * v_t[..., None, :]       # (B,H,N,N)
        out_t = jnp.einsum("bhi,bhij->bhj",
                           r_t, S + u[None, :, :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, out_t

    seq = (r.transpose(1, 0, 2, 3).astype(jnp.float32),
           k.transpose(1, 0, 2, 3).astype(jnp.float32),
           v.transpose(1, 0, 2, 3).astype(jnp.float32),
           w.transpose(1, 0, 2, 3))
    s_fin, outs = jax.lax.scan(step, s0, seq)
    o = outs.transpose(1, 0, 2, 3).reshape(b, s, d)      # (B,S,d)
    # per-head group norm (ln over each head's channels)
    o = o.reshape(b, s, n_heads, n)
    mu = o.mean(-1, keepdims=True)
    var = o.var(-1, keepdims=True)
    o = ((o - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(b, s, d)
    o = o * p["ln_x"]["w"] + p["ln_x"]["b"]
    o = (o.astype(x.dtype) * g)
    out = jnp.dot(o, p["wo"].astype(x.dtype))
    return out, (x[:, -1:].astype(jnp.float32), s_fin)


def rwkv6_channel_mix_init(key, d: int, ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"mu_k": jnp.full((d,), 0.5, jnp.float32),
            "mu_r": jnp.full((d,), 0.5, jnp.float32),
            "wk": dense_init(k1, (d, ff)), "wv": dense_init(k2, (ff, d)),
            "wr": dense_init(k3, (d, d))}


def rwkv6_channel_mix(p, x, state=None):
    b, s, d = x.shape
    x_prev = jnp.zeros((b, 1, d), x.dtype) if state is None else state
    xs = _token_shift(x, x_prev)
    xk = x + (xs - x) * p["mu_k"].astype(x.dtype)
    xr = x + (xs - x) * p["mu_r"].astype(x.dtype)
    h = jnp.square(jax.nn.relu(jnp.dot(xk, p["wk"].astype(x.dtype))))
    r = jax.nn.sigmoid(jnp.dot(xr, p["wr"].astype(x.dtype)))
    return r * jnp.dot(h, p["wv"].astype(x.dtype)), x[:, -1:].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) — chunked block decomposition
# ---------------------------------------------------------------------------

def mamba2_init(key, d: int, n_heads: int, d_state: int, d_conv: int = 4,
                expand: int = 2):
    d_inner = expand * d
    head_p = d_inner // n_heads
    ks = jax.random.split(key, 4)
    return {
        # in_proj emits z (gate), x, B, C, dt
        "in_proj": dense_init(ks[0], (d, 2 * d_inner + 2 * d_state + n_heads)),
        "conv_w": dense_init(ks[1], (d_conv, d_inner + 2 * d_state),
                             scale=0.5),
        "a_log": jnp.zeros((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm": rmsnorm_init(d_inner),
        "out_proj": dense_init(ks[2], (d_inner, d)),
    }


def _segsum(a):
    """exp-able segment sums: out[..., t, s] = Σ_{r=s+1..t} a[..., r] (t ≥ s)."""
    t = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, a, b_in, c_in, chunk: int = 64, h0=None,
                compute_dtype=jnp.float32):
    """Mamba-2 SSD. x: (B,S,H,P), a: (B,S,H) log-decay (≤0),
    b_in/c_in: (B,S,N). Returns (y (B,S,H,P), h_fin (B,H,N,P)).
    ``compute_dtype=bf16`` keeps the big chunk tensors low-precision
    (decay cumsums stay f32) to save memory."""
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    assert s % chunk == 0 or s == 1
    if s == 1:  # decode step: plain recurrence
        h_prev = jnp.zeros((bsz, h, n, p), jnp.float32) if h0 is None else h0
        da = jnp.exp(a[:, 0])                                     # (B,H)
        hb = h_prev * da[..., None, None] + jnp.einsum(
            "bn,bhp->bhnp", b_in[:, 0].astype(jnp.float32),
            x[:, 0].astype(jnp.float32))
        y = jnp.einsum("bn,bhnp->bhp", c_in[:, 0].astype(jnp.float32), hb)
        return y[:, None].astype(x.dtype), hb
    nc = s // chunk
    xs = x.reshape(bsz, nc, chunk, h, p).astype(compute_dtype)
    As = a.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)       # (B,H,nc,C)
    Bs = b_in.reshape(bsz, nc, chunk, n).astype(compute_dtype)
    Cs = c_in.reshape(bsz, nc, chunk, n).astype(compute_dtype)
    A_cum = jnp.cumsum(As, axis=-1)                               # (B,H,nc,C)
    # 1. diagonal blocks
    L = jnp.exp(_segsum(As)).astype(compute_dtype)                # (B,H,nc,C,C)
    y_diag = jnp.einsum("bzln,bzsn,bhzls,bzshp->bzlhp",
                        Cs, Bs, L, xs,
                        preferred_element_type=jnp.float32)
    # 2. chunk states (decay to chunk end)
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)         .astype(compute_dtype)                                    # (B,H,nc,C)
    states = jnp.einsum("bzcn,bhzc,bzchp->bzhnp", Bs, decay_states, xs,
                        preferred_element_type=jnp.float32)
    # 3. inter-chunk recurrence
    chunk_decay = jnp.exp(A_cum[..., -1])                         # (B,H,nc)

    def scan_fn(hprev, inp):
        st, dk = inp                                              # (B,H,N,P),(B,H)
        hnew = hprev * dk[..., None, None] + st
        return hnew, hprev

    h_init = jnp.zeros((bsz, h, n, p), jnp.float32) if h0 is None else h0
    h_fin, h_prevs = jax.lax.scan(
        scan_fn, h_init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(2, 0, 1)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)                    # (B,nc,H,N,P)
    # 4. off-diagonal contribution (state entering each chunk)
    state_decay = jnp.exp(A_cum).astype(compute_dtype)            # (B,H,nc,C)
    y_off = jnp.einsum("bzln,bhzl,bzhnp->bzlhp", Cs, state_decay,
                       h_prevs.astype(compute_dtype),
                       preferred_element_type=jnp.float32)
    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y.astype(x.dtype), h_fin


def ssd_naive(x, a, b_in, c_in, h0=None):
    """Step-by-step oracle for ssd_chunked."""
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    hst = jnp.zeros((bsz, h, n, p), jnp.float32) if h0 is None else h0
    ys = []
    for t in range(s):
        da = jnp.exp(a[:, t])
        hst = hst * da[..., None, None] + jnp.einsum(
            "bn,bhp->bhnp", b_in[:, t].astype(jnp.float32),
            x[:, t].astype(jnp.float32))
        ys.append(jnp.einsum("bn,bhnp->bhp",
                             c_in[:, t].astype(jnp.float32), hst))
    return jnp.stack(ys, axis=1).astype(x.dtype), hst


def mamba2_mixer(p, xin, dims: tuple[int, int, int, int], state=None,
                 chunk: int = 64, compute_dtype=jnp.float32):
    """Full Mamba-2 block mixer. xin: (B,S,d); dims (static) =
    (d_inner, head_dim, d_state, d_conv).
    state: (conv_state (B, d_conv-1, d_inner+2N), h (B,H,N,P)) or None."""
    d_inner, head_p, n, d_conv = dims
    b, s, _ = xin.shape
    n_heads = d_inner // head_p
    zxbcdt = jnp.dot(xin, p["in_proj"].astype(xin.dtype))
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * n], axis=-1)
    # causal depthwise conv over (x, B, C)
    if state is None:
        conv_in = jnp.pad(xbc, ((0, 0), (d_conv - 1, 0), (0, 0)))
    else:
        conv_in = jnp.concatenate([state[0].astype(xbc.dtype), xbc], axis=1)
    wconv = p["conv_w"].astype(xbc.dtype)
    xbc_c = sum(conv_in[:, i:i + s] * wconv[i][None, None]
                for i in range(d_conv))
    xbc_c = jax.nn.silu(xbc_c)
    xpart, b_in, c_in = jnp.split(xbc_c, [d_inner, d_inner + n], axis=-1)
    dt_f = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a = -jnp.exp(p["a_log"])[None, None] * dt_f                    # log decay
    xh = (xpart.reshape(b, s, n_heads, head_p)
          * dt_f[..., None].astype(xpart.dtype))
    h0 = None if state is None else state[1]
    y, h_fin = ssd_chunked(xh, a, b_in, c_in, chunk=min(chunk, s), h0=h0,
                           compute_dtype=compute_dtype)
    y = y + p["d_skip"][None, None, :, None].astype(y.dtype) * xh
    y = y.reshape(b, s, d_inner)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z))
    out = jnp.dot(y, p["out_proj"].astype(xin.dtype))
    new_conv = conv_in[:, -(d_conv - 1):] if d_conv > 1 else conv_in[:, :0]
    return out, (new_conv.astype(jnp.float32), h_fin)

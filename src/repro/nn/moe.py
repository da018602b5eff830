"""Mixture-of-Experts with the paper's two matrix representations.

The router's output *is* the paper's relation ``{[i, j, v]}``: token i is
assigned to expert j with gate value v. The two execution strategies the
paper benchmarks against each other (relational vs array data type) both
exist here, selectable per config (``MoEConfig.impl``):

``impl="einsum"`` — the ARRAY representation (paper Section 5): the
    assignment is materialised per token-group as a dense one-hot
    dispatch/combine tensor (g, E, C) and dispatch/combine are einsums
    (GShard-style). Fully pjit-friendly, but pays O(E·C/k) redundant
    multiply-adds per token — the array analogue of the paper's join
    blow-up (Fig. 5): the one-hot matrix materialises every (token, slot)
    cell even though only k per token are live.

``impl="sort"`` — the RELATIONAL representation (paper Section 4): the
    assignment stays a sparse relation; dispatch is the *join* (gather rows
    by token id), the per-expert rank comes from a sort (the paper's §8
    sort-based aggregation), and combine is the *group-by token, sum* — a
    segment sum. O(T·k·d) data movement, no redundant FLOPs.
    ``kernels/moe_dispatch`` + ``kernels/relational_matmul`` are the Pallas
    twins of the gather and segment-sum.

Tokens are processed in GROUPS (GShard's group dimension): capacity,
sorting and dispatch are all group-local, so with groups sharded over the
data axes every device handles its own relation and the only cross-device
traffic is the expert-parallel all-to-all. Both impls drop overflow beyond
expert capacity with identical rank-major priority, so their outputs match
exactly (tested).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .layers import cdt, dense_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                 # per-expert hidden
    n_shared: int = 0         # shared experts (DeepSeek)
    capacity_factor: float = 1.25
    router_softmax: str = "pre"   # "pre": softmax→topk (DeepSeek);
                                  # "post": topk→softmax (DBRX/Mixtral)
    impl: str = "einsum"
    group_size: int = 2048


def init_moe(key, cfg: MoEConfig):
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": dense_init(ks[0], (d, e)),
        "wi": dense_init(ks[1], (e, d, f)),
        "wg": dense_init(ks[2], (e, d, f)),
        "wo": dense_init(ks[3], (e, f, d)),
    }
    if cfg.n_shared:
        p["shared"] = {
            "wi": dense_init(ks[4], (d, cfg.n_shared * f)),
            "wg": dense_init(jax.random.fold_in(ks[4], 1),
                             (d, cfg.n_shared * f)),
            "wo": dense_init(jax.random.fold_in(ks[4], 2),
                             (cfg.n_shared * f, d)),
        }
    return p


def _route(p, x, cfg: MoEConfig):
    """Top-k routing over flat tokens. Returns (gates, idx, aux_loss)."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32))
    if cfg.router_softmax == "pre":
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.top_k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    else:
        top_logits, idx = jax.lax.top_k(logits, cfg.top_k)
        gates = jax.nn.softmax(top_logits, axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
    # Switch-style load-balancing aux loss (fraction × mean prob).
    me = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32),
                axis=-2), axis=tuple(range(idx.ndim - 1)))
    aux = cfg.n_experts * jnp.sum(me * ce) / cfg.top_k
    return gates, idx, aux


def _capacity(group: int, cfg: MoEConfig) -> int:
    c = int(group * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def _expert_ffn(p, xs):
    """xs: (..., E, C, d) → SwiGLU per expert."""
    h = jnp.einsum("...ecd,edf->...ecf", xs, cdt(p["wi"]))
    g = jnp.einsum("...ecd,edf->...ecf", xs, cdt(p["wg"]))
    return jnp.einsum("...ecf,efd->...ecd", h * jax.nn.silu(g),
                      cdt(p["wo"]))


# ---------------------------------------------------------------------------
# array representation: dense one-hot dispatch/combine (GShard), grouped
# ---------------------------------------------------------------------------

def _moe_einsum(p, xg, cfg: MoEConfig, gates, idx):
    """xg: (G, g, d); gates/idx: (G, g, k)."""
    _, g, d = xg.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(g, cfg)
    pos_offset = jnp.zeros(idx.shape[:1] + (e,), jnp.int32)     # (G, E)
    dispatch = None
    combine = None
    for r in range(k):
        mask_r = jax.nn.one_hot(idx[..., r], e, dtype=jnp.int32)  # (G,g,E)
        pos_r = jnp.cumsum(mask_r, axis=1) - 1 + pos_offset[:, None]
        pos_offset = pos_offset + jnp.sum(mask_r, axis=1)
        pos_tok = jnp.sum(mask_r * pos_r, axis=-1)                # (G, g)
        keep = pos_tok < cap
        oh_pos = jax.nn.one_hot(jnp.where(keep, pos_tok, cap), cap,
                                dtype=jnp.float32)                # (G,g,C)
        d_r = mask_r.astype(jnp.float32)[..., :, None] * oh_pos[..., None, :]
        dispatch = d_r if dispatch is None else dispatch + d_r
        combine = (d_r * gates[..., r][..., None, None]
                   if combine is None
                   else combine + d_r * gates[..., r][..., None, None])
    xs = jnp.einsum("gsec,gsd->gecd", dispatch.astype(xg.dtype), xg)
    ys = _expert_ffn(p, xs)
    return jnp.einsum("gsec,gecd->gsd", combine.astype(xg.dtype), ys)


# ---------------------------------------------------------------------------
# relational representation: sort (join) + segment sum (group-by), grouped
# ---------------------------------------------------------------------------

def _moe_sort_local(p_wi, p_wg, p_wo, x, cfg, gates, idx, e_lo, e_loc,
                    cap):
    """One group's relation (x: (g, d); gates/idx: (g, k)) through the
    experts [e_lo, e_lo + e_loc), whose weights are ``p_w*``. Slots outside
    the range drop like non-matching join tuples, so partials over a
    partition of the experts sum to the result over all of them."""
    g, d = x.shape
    k = cfg.top_k
    # the relation, rank-major to match the einsum path's drop priority
    expert_s = idx.T.reshape(-1) - e_lo                 # (S,) S = k·g
    token_s = jnp.tile(jnp.arange(g, dtype=jnp.int32), k)
    gate_s = gates.T.reshape(-1)
    owned = (expert_s >= 0) & (expert_s < e_loc)
    expert_s = jnp.where(owned, expert_s, e_loc)        # park in drop bucket
    order = jnp.argsort(expert_s, stable=True)      # sort-based aggregation
    expert_sorted = expert_s[order]
    token_sorted = token_s[order]
    gate_sorted = jnp.where(owned[order], gate_s[order], 0.0)
    counts = jnp.bincount(expert_s, length=e_loc + 1)
    seg_start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                 jnp.cumsum(counts)[:-1]])
    pos_sorted = (jnp.arange(k * g, dtype=jnp.int32)
                  - seg_start[expert_sorted])
    keep = (pos_sorted < cap) & (expert_sorted < e_loc)
    # JOIN: gather token rows; scatter into per-expert capacity buckets
    xs_slots = x[token_sorted]
    buf = jnp.zeros((e_loc, cap, d), x.dtype)
    buf = buf.at[jnp.where(keep, expert_sorted, e_loc),
                 jnp.where(keep, pos_sorted, cap)].add(xs_slots,
                                                       mode="drop")
    ys = _expert_ffn({"wi": p_wi, "wg": p_wg, "wo": p_wo}, buf)
    # gather back per slot; GROUP BY token, SUM (segment sum)
    y_slots = ys[jnp.where(keep, expert_sorted, 0),
                 jnp.where(keep, pos_sorted, 0)] * keep[:, None]
    weighted = y_slots.astype(jnp.float32) * gate_sorted[:, None]
    out = jax.ops.segment_sum(weighted, token_sorted, num_segments=g)
    return out.astype(x.dtype)


def _moe_sort_one(p, x, cfg: MoEConfig, gates, idx):
    """x: (g, d); gates/idx: (g, k) — one group's relation, every expert."""
    return _moe_sort_local(p["wi"], p["wg"], p["wo"], x, cfg, gates, idx,
                           0, cfg.n_experts, _capacity(x.shape[0], cfg))


def moe_ffn(p, x, cfg: MoEConfig):
    """x: (T, d) flat tokens → (out (T, d), aux_loss)."""
    t, d = x.shape
    g = min(cfg.group_size, t)
    if t % g:
        g = t                                        # tiny/odd batches
    xg = x.reshape(t // g, g, d)
    gates, idx, aux = _route(p, xg, cfg)
    if cfg.impl == "einsum":
        out = _moe_einsum(p, xg, cfg, gates, idx).reshape(t, d)
    elif cfg.impl == "sort":
        out = jax.vmap(
            lambda xx, gg, ii: _moe_sort_one(p, xx, cfg, gg, ii)
        )(xg, gates, idx).reshape(t, d)
    else:
        raise ValueError(cfg.impl)
    if cfg.n_shared:
        sh = p["shared"]
        h = jnp.dot(x, cdt(sh["wi"])) * jax.nn.silu(jnp.dot(x, cdt(sh["wg"])))
        out = out + jnp.dot(h, cdt(sh["wo"]))
    return out, aux

"""Architecture assembly: one LM class covering all ten assigned configs.

Families (configs.base.ArchConfig.family):
  dense   — llama-style GQA + SwiGLU (yi, qwen3, qwen2.5, granite)
  vlm     — dense backbone, stub vision frontend feeds embeddings (internvl2)
  audio   — MHA + LayerNorm + GELU over stub EnCodec frame embeds (musicgen)
  moe     — GQA or MLA attention + routed experts (dbrx, deepseek-v2-lite)
  ssm     — RWKV-6 time/channel mix (rwkv6)
  hybrid  — Mamba-2 backbone + shared attention block (zamba2)

Structure is scan-over-layers (stacked params, leading L axis) so HLO size
and compile time are depth-independent — a hard requirement for the 40-cell
multi-pod dry-run. Heterogeneous layers (DeepSeek's leading dense-FFN layer,
Zamba2's shared block every 6 layers) live outside the scanned stack.

Entry points consumed by the launcher:
  init(key) → params
  loss_fn(params, batch) → (scalar loss, metrics)        [train_4k]
  prefill(params, batch) → (last-token logits, cache)    [prefill_32k]
  decode_step(params, batch, cache, pos) → (logits, cache)  [decode shapes]

Named scopes mark the layers on the profiler's clock (compile-time metadata
only): ``lm.embed``, ``lm.layers`` (every scanned stack), ``lm.attention``,
``lm.mlp`` (dense FFN and MoE), ``lm.head`` and ``lm.loss``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import layers as L
from . import moe as M
from . import ssm as S


def _moe_cfg(cfg: ArchConfig) -> M.MoEConfig:
    m = cfg.moe
    return M.MoEConfig(
        n_experts=m.n_experts, top_k=m.top_k, d_model=cfg.d_model,
        d_ff=m.d_ff_expert, n_shared=m.n_shared,
        capacity_factor=m.capacity_factor,
        router_softmax=m.router_softmax, impl=m.impl)


class LM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def _scan(self, body, carry, xs):
        """lax.scan over the layer stack, or the same scan fully unrolled
        when ``cfg.scan_layers`` is False. The dry-run unrolls so that
        cost_analysis counts every layer (rolled scan bodies are counted
        once); training examples scan for O(1)-in-depth compile time.
        Using ``lax.scan(unroll=n)`` — not a hand-written Python loop —
        keeps both paths bitwise identical (same slicing and stacking ops,
        same bf16 rounding), which test_scan_and_unrolled_paths_agree
        pins."""
        unroll = (1 if self.cfg.scan_layers
                  else max(jax.tree.leaves(xs)[0].shape[0], 1))
        with jax.named_scope("lm.layers"):
            return jax.lax.scan(body, carry, xs, unroll=unroll)

    def _attend_full(self, q, k, v):
        """Full-sequence attention dispatch (cfg.attn_impl)."""
        cfg = self.cfg
        s = q.shape[2]
        chunk = cfg.attn_chunk or L.auto_chunk(s)
        scale = self._attn_scale(q.shape[-1])
        if cfg.attn_impl == "flash":
            if cfg.flash_impl == "scan":
                return L.attend_flash_scan(q, k, v, chunk=min(chunk, s),
                                           scale=scale)
            return L.attend_flash(q, k, v, chunk=min(chunk, s),
                                  bf16_scores=cfg.attn_bf16_scores,
                                  scale=scale)
        if cfg.attn_impl == "chunked":
            return L.attend_chunked(q, k, v, chunk=min(chunk, s),
                                    scale=scale)
        return L.attend(q, k, v, causal=True, scale=scale)

    # granite's scalings; at the neutral defaults each is the plain
    # operation, with nothing added to the program
    def _attn_scale(self, dim: int) -> float:
        """Attention score scale: ``attention_multiplier``, else
        1/sqrt(dim)."""
        m = self.cfg.attention_multiplier
        return dim ** -0.5 if m is None else m

    def _residual(self, x, out):
        """``x`` plus a sub-block's ``out`` times ``residual_multiplier``,
        taken in float32 and rounded once to ``x``'s dtype: a multiplier
        such as 0.22 has no bfloat16 value (it rounds to 0.2197), and a
        bfloat16 product would shrink every block's output by that much."""
        r = self.cfg.residual_multiplier
        if r == 1.0:
            return x + out
        return (x + out.astype(jnp.float32) * r).astype(x.dtype)

    def _norm(self, p, x):
        """The block norm: RMSNorm at ``norm_eps``, or LayerNorm."""
        if self.cfg.norm == "rmsnorm":
            return L.rmsnorm(p, x, self.cfg.norm_eps)
        return L.layernorm(p, x)

    def _embed(self, params, batch) -> jax.Array:
        """Token embeddings (or stub-frontend embeds) times
        ``embedding_multiplier``, in the compute dtype."""
        x = (batch["embeds"] if "embeds" in batch
             else params["embed"][batch["tokens"]])
        m = self.cfg.embedding_multiplier
        if m != 1.0:
            x = x.astype(jnp.float32) * m
        return x.astype(L.COMPUTE_DTYPE)

    # ------------------------------------------------------------------ init
    def _init_block(self, key) -> dict:
        cfg = self.cfg
        d = cfg.d_model
        norm_init = (L.rmsnorm_init if cfg.norm == "rmsnorm"
                     else L.layernorm_init)
        ks = jax.random.split(key, 4)
        p: dict[str, Any] = {"norm1": norm_init(d), "norm2": norm_init(d)}
        if cfg.family in ("dense", "vlm", "audio") or (
                cfg.family == "moe" and cfg.mla is None):
            p["attn"] = L.gqa_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.d_head, qkv_bias=cfg.qkv_bias,
                                   qk_norm=cfg.qk_norm)
        elif cfg.family == "moe":                        # MLA
            m = cfg.mla
            p["attn"] = L.mla_init(ks[0], d, cfg.n_heads, m.kv_lora,
                                   m.d_nope, m.d_rope, m.d_v)
        if cfg.family in ("dense", "vlm", "audio"):
            p["mlp"] = (L.swiglu_init(ks[1], d, cfg.d_ff)
                        if cfg.mlp == "swiglu"
                        else L.gelu_mlp_init(ks[1], d, cfg.d_ff))
        elif cfg.family == "moe":
            p["moe"] = M.init_moe(ks[1], _moe_cfg(cfg))
        elif cfg.family == "ssm":
            p["tmix"] = S.rwkv6_init(ks[0], d, d // cfg.ssm.head_dim)
            p["cmix"] = S.rwkv6_channel_mix_init(ks[1], d, cfg.d_ff)
        elif cfg.family == "hybrid":
            p.pop("norm2")
            p["mixer"] = S.mamba2_init(ks[0], d, cfg.n_heads_mamba(),
                                       cfg.ssm.d_state, cfg.ssm.d_conv,
                                       cfg.ssm.expand)
        return p

    def init(self, key) -> dict:
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab
        k_emb, k_layers, k_head, k_extra = jax.random.split(key, 4)
        n_scanned = cfg.n_layers - (cfg.moe.first_k_dense if cfg.moe else 0)
        layer_keys = jax.random.split(k_layers, n_scanned)
        stacked = jax.vmap(self._init_block)(layer_keys)
        params: dict[str, Any] = {
            "embed": L.dense_init(k_emb, (v, d), scale=0.02),
            "layers": stacked,
            "final_norm": (L.rmsnorm_init(d) if cfg.norm == "rmsnorm"
                           else L.layernorm_init(d)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(k_head, (d, v))
        if cfg.moe and cfg.moe.first_k_dense:
            def dense_block(key):
                ks = jax.random.split(key, 2)
                norm_init = (L.rmsnorm_init if cfg.norm == "rmsnorm"
                             else L.layernorm_init)
                p = {"norm1": norm_init(d), "norm2": norm_init(d)}
                if cfg.mla is None:
                    p["attn"] = L.gqa_init(ks[0], d, cfg.n_heads,
                                           cfg.n_kv_heads, cfg.d_head)
                else:
                    m = cfg.mla
                    p["attn"] = L.mla_init(ks[0], d, cfg.n_heads, m.kv_lora,
                                           m.d_nope, m.d_rope, m.d_v)
                p["mlp"] = L.swiglu_init(ks[1], d, cfg.moe.d_ff_dense)
                return p
            params["prologue"] = jax.vmap(dense_block)(
                jax.random.split(k_extra, cfg.moe.first_k_dense))
        if cfg.shared_attn_every:
            ks = jax.random.split(k_extra, 4)
            params["shared_block"] = {
                "in_proj": L.dense_init(ks[0], (2 * d, d)),
                "norm1": L.rmsnorm_init(d), "norm2": L.rmsnorm_init(d),
                "attn": L.gqa_init(ks[1], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.d_head),
                "mlp": L.swiglu_init(ks[2], d, cfg.d_ff),
            }
        if cfg.param_dtype == "bfloat16":
            # low-precision parameters: matrices in bf16 (collectives and
            # HBM reads halve); f32 masters live in the optimizer state
            params = jax.tree.map(
                lambda a: a.astype(jnp.bfloat16)
                if a.ndim >= 2 and a.dtype == jnp.float32 else a, params)
        return params

    # ------------------------------------------------------------- embedding
    @jax.named_scope("lm.embed")
    def embed_inputs(self, params, batch) -> jax.Array:
        """tokens (B,S) → (B,S,d), or pass through stub-frontend embeds."""
        x = self._embed(params, batch)
        if self.cfg.family == "audio" and not self.cfg.rope:
            b, s, d = x.shape
            pos = self._sinusoid(s, d, offset=0)
            x = x + pos[None].astype(x.dtype)
        return x

    @staticmethod
    def _sinusoid(s, d, offset=0):
        pos = jnp.arange(offset, offset + s, dtype=jnp.float32)[:, None]
        i = jnp.arange(0, d, 2, dtype=jnp.float32)[None]
        ang = pos / jnp.power(1e4, i / d)
        return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)

    @jax.named_scope("lm.head")
    def unembed(self, params, x) -> jax.Array:
        x = self._head_input(params, x)
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        return jnp.dot(x, head.astype(x.dtype))

    def _head_input(self, params, x):
        """The final norm, over ``logits_scaling`` (in float32, rounded
        once): the head is linear, so this divides the logits (exactly,
        for a power of two) at a twelfth of the cost for a 49k vocabulary,
        and with no logits-sized buffer in the backward pass."""
        x = self._norm(params["final_norm"], x)
        ls = self.cfg.logits_scaling
        if ls == 1.0:
            return x
        return (x.astype(jnp.float32) / ls).astype(x.dtype)

    # ------------------------------------------------------ layer-stack body
    @jax.named_scope("lm.attention")
    def _attn_block(self, p, x, cos, sin, cache=None, pos=None):
        """Returns (out, new_kv) — new_kv is this call's K/V (full-seq) or
        the updated cache slice (decode)."""
        cfg = self.cfg
        if cfg.mla is not None:
            q, k, v, c_kv = L.mla_qkv(p, x, cfg.n_heads, cfg.mla.d_nope,
                                      cfg.mla.d_rope, cfg.mla.d_v, cos, sin)
            o = self._attend_full(q, k, v)
            return L.merge_heads(o) @ L.cdt(p["wo"]), None
        q, k, v = L.gqa_project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.d_head, cos, sin)
        if cache is None:
            o = self._attend_full(q, k, v)
            return L.merge_heads(o) @ L.cdt(p["wo"]), (k, v)
        # decode: write this step's k/v at pos, attend over valid prefix
        ck, cv = cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                 pos, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                 pos, axis=2)
        valid = (jnp.arange(ck.shape[2]) <= pos)[None]
        o = L.attend(q, ck.astype(q.dtype), cv.astype(q.dtype), causal=False,
                     kv_len_mask=jnp.broadcast_to(valid, (x.shape[0],
                                                          ck.shape[2])),
                     scale=self._attn_scale(cfg.d_head))
        return L.merge_heads(o) @ L.cdt(p["wo"]), (ck, cv)

    def _block(self, p, x, cos, sin, cache=None, pos=None):
        """One transformer block. Returns (x, aux_loss, new_cache)."""
        cfg = self.cfg
        norm = self._norm
        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "ssm":
            o, st_t = S.rwkv6_time_mix(
                p["tmix"], norm(p["norm1"], x),
                cfg.d_model // cfg.ssm.head_dim,
                state=None if cache is None else cache[0])
            x = self._residual(x, o)
            o, st_c = S.rwkv6_channel_mix(
                p["cmix"], norm(p["norm2"], x),
                state=None if cache is None else cache[1])
            x = self._residual(x, o)
            return x, aux, (st_t, st_c)
        if cfg.family == "hybrid":
            dims = (cfg.ssm.expand * cfg.d_model, cfg.ssm.head_dim,
                    cfg.ssm.d_state, cfg.ssm.d_conv)
            o, st = S.mamba2_mixer(p["mixer"], norm(p["norm1"], x), dims,
                                   state=cache, chunk=cfg.ssm.chunk,
                                   ssd_impl=cfg.ssd_impl,
                                   compute_dtype=(jnp.bfloat16
                                                  if cfg.ssm_bf16
                                                  else jnp.float32))
            return self._residual(x, o), aux, st
        attn_out, kv = self._attn_block(p["attn"], norm(p["norm1"], x),
                                        cos, sin, cache=cache, pos=pos)
        x = self._residual(x, attn_out)
        h = norm(p["norm2"], x)
        with jax.named_scope("lm.mlp"):
            if "moe" in p:
                b, s, d = h.shape
                out, aux = M.moe_ffn(p["moe"], h.reshape(b * s, d),
                                     _moe_cfg(cfg))
                out = out.reshape(b, s, d)
            else:
                out = (L.swiglu(p["mlp"], h) if cfg.mlp == "swiglu"
                       else L.gelu_mlp(p["mlp"], h))
        return self._residual(x, out), aux, kv

    def _mla_block_decode(self, p, x, cos, sin, cache, pos):
        """Absorbed-matmul MLA decode: attend in the compressed latent space.
        Cache stores (c_kv (B,S,kv_lora), k_rope (B,S,d_rope)) only — the
        MLA memory saving."""
        cfg, m = self.cfg, self.cfg.mla
        b = x.shape[0]
        a = p["attn"]
        q = jnp.dot(x, L.cdt(a["wq"])).reshape(b, 1, cfg.n_heads,
                                               m.d_nope + m.d_rope)
        q = q.transpose(0, 2, 1, 3)
        q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
        q_rope = L.apply_rope(q_rope, cos, sin)
        c_kv_t = L.rmsnorm(a["kv_a_norm"], jnp.dot(x, L.cdt(a["wkv_a"])))
        k_rope_t = L.apply_rope(
            jnp.dot(x, L.cdt(a["wk_rope"]))[:, None], cos, sin)[:, 0]
        ckv, krope = cache
        ckv = jax.lax.dynamic_update_slice_in_dim(
            ckv, c_kv_t.astype(ckv.dtype), pos, axis=1)
        krope = jax.lax.dynamic_update_slice_in_dim(
            krope, k_rope_t.astype(krope.dtype), pos, axis=1)
        # absorbed matmul: q_abs (B,H,kv_lora) = q_nope · wk_bᵀ, so the
        # attention product runs in the compressed latent space
        wk_b = a["wk_b"].reshape(m.kv_lora, cfg.n_heads, m.d_nope)
        q_abs = jnp.einsum("bhd,chd->bhc",
                           q_nope[:, :, 0].astype(jnp.float32),
                           wk_b.astype(jnp.float32))
        logits = (jnp.einsum("bhc,bsc->bhs", q_abs,
                             ckv.astype(jnp.float32)) +
                  jnp.einsum("bhr,bsr->bhs",
                             q_rope[:, :, 0].astype(jnp.float32),
                             krope.astype(jnp.float32)))
        logits = logits * self._attn_scale(m.d_nope + m.d_rope)
        valid = (jnp.arange(ckv.shape[1]) <= pos)[None, None]
        logits = jnp.where(valid, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        lat = jnp.einsum("bhs,bsc->bhc", probs, ckv.astype(jnp.float32))
        wv_b = a["wv_b"].reshape(m.kv_lora, cfg.n_heads, m.d_v)
        o = jnp.einsum("bhc,chd->bhd", lat, wv_b.astype(jnp.float32))
        o = o.reshape(b, 1, cfg.n_heads * m.d_v).astype(x.dtype)
        return jnp.dot(o, L.cdt(a["wo"])), (ckv, krope)

    # ------------------------------------------------------------- forward
    def _scan_blocks(self, params, x, cos, sin):
        cfg = self.cfg

        def body(carry, lp):
            xx, aux = carry
            out, a, _ = self._block(lp, L.split_tokens(xx), cos, sin)
            return (out, aux + a), None

        if cfg.remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif cfg.remat == "dots":
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        (x, aux), _ = self._scan(body, (x, jnp.zeros((), jnp.float32)),
                                 params["layers"])
        return x, aux

    def backbone(self, params, batch):
        """Full-sequence forward up to (but excluding) the LM head.
        Returns (hidden (B,S,d), aux_loss)."""
        cfg = self.cfg
        x = L.split_tokens(self.embed_inputs(params, batch))
        s = x.shape[1]
        cos, sin = (L.rope_table(s, self._rope_dim(), cfg.rope_theta)
                    if cfg.rope else (None, None))
        aux = jnp.zeros((), jnp.float32)
        if "prologue" in params:
            def pro_body(carry, lp):
                xx, a = carry
                out, a2, _ = self._block(lp, xx, cos, sin)
                return (out, a + a2), None
            (x, aux), _ = self._scan(pro_body, (x, aux),
                                     params["prologue"])
        if cfg.shared_attn_every:
            x, aux = self._hybrid_forward(params, x, cos, sin)
        else:
            x, aux2 = self._scan_blocks(params, x, cos, sin)
            aux = aux + aux2
        return x, aux

    def forward(self, params, batch):
        """Full-sequence forward → (logits (B,S,V), aux_loss)."""
        x, aux = self.backbone(params, batch)
        return self.unembed(params, x), aux

    def _hybrid_forward(self, params, x, cos, sin):
        """Zamba2: scan 6-layer Mamba segments, shared attn block between."""
        cfg = self.cfg
        x0 = x
        period = cfg.shared_attn_every
        n_seg = cfg.n_layers // period
        aux = jnp.zeros((), jnp.float32)
        seg_params = jax.tree.map(
            lambda a: a.reshape((n_seg, period) + a.shape[1:]),
            params["layers"])
        for seg in range(n_seg):
            x, _ = self._shared_block(params["shared_block"], x, x0,
                                      cos, sin)
            lp_seg = jax.tree.map(lambda a: a[seg], seg_params)

            def body(carry, lp):
                out, _, _ = self._block(lp, carry, cos, sin)
                return out, None
            body_fn = (jax.checkpoint(body, prevent_cse=False)
                       if cfg.remat != "none" else body)
            x, _ = self._scan(body_fn, x, lp_seg)
        return x, aux

    def _shared_block(self, p, x, x0, cos, sin, cache=None, pos=None):
        """Zamba2 shared block: concat(hidden, embeddings) → 2d→d proj →
        attn + MLP, residual back into the Mamba stream."""
        h = jnp.concatenate([x, x0], axis=-1) @ L.cdt(p["in_proj"])
        a_in = self._norm(p["norm1"], h)
        attn_out, kv = self._attn_block(p["attn"], a_in, cos, sin,
                                        cache=cache, pos=pos)
        h = self._residual(h, attn_out)
        m_in = self._norm(p["norm2"], h)
        with jax.named_scope("lm.mlp"):
            m_out = L.swiglu(p["mlp"], m_in)
        return x + self._residual(h, m_out), kv

    def _rope_dim(self):
        return (self.cfg.mla.d_rope if self.cfg.mla is not None
                else self.cfg.d_head)

    # ------------------------------------------------------------- training
    def loss_fn(self, params, batch):
        """Mean next-token cross-entropy (+ 0.01 × the MoE balance loss).
        Under a mesh the head and loss take the positions split over
        ``model`` (``L.split_tokens``) as well as the batch over the data
        axes."""
        if self.cfg.loss_impl == "chunked":
            return self._loss_chunked(params, batch)
        x, aux = self.backbone(params, batch)
        logits = self.unembed(params, L.split_tokens(x, over_model=True))
        labels = batch["labels"]
        with jax.named_scope("lm.loss"):
            logits = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            if self.cfg.loss_impl == "onehot":
                # gold logit via masked sum — unlike take_along_axis this
                # never gathers across the vocab(model)-sharded dim: GSPMD
                # lowers the reduction to a partial sum + psum (§Perf lever)
                vpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                                logits.ndim - 1)
                gold = jnp.sum(jnp.where(vpos == labels[..., None], logits,
                                         0.0), axis=-1)
            else:
                gold = jnp.take_along_axis(logits, labels[..., None],
                                           axis=-1)[..., 0]
            ce = jnp.mean(lse - gold)
            return ce + 0.01 * aux, {"ce": ce, "aux": aux}

    def _loss_chunked(self, params, batch):
        """Vocab-streamed cross-entropy: the (B,S,V) f32 logits tensor is
        never materialised — logsumexp and the gold logit accumulate over
        vocab chunks (beyond-paper memory optimisation, §Perf)."""
        cfg = self.cfg
        x, aux = self.backbone(params, batch)
        x = L.split_tokens(x, over_model=True)
        with jax.named_scope("lm.head"):
            xn = self._head_input(params, x)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
        labels = batch["labels"]
        b, s_, _ = xn.shape
        with jax.named_scope("lm.loss"):
            run_max = jnp.full((b, s_), -1e30, jnp.float32)
            run_se = jnp.zeros((b, s_), jnp.float32)
            gold = jnp.zeros((b, s_), jnp.float32)
        v = cfg.vocab
        chunk = cfg.loss_chunk
        for lo in range(0, v, chunk):
            hi = min(v, lo + chunk)
            with jax.named_scope("lm.head"):
                lc = jnp.dot(xn, head[:, lo:hi].astype(xn.dtype)
                             ).astype(jnp.float32)
            with jax.named_scope("lm.loss"):
                m_new = jnp.maximum(run_max, lc.max(axis=-1))
                run_se = (run_se * jnp.exp(run_max - m_new)
                          + jnp.exp(lc - m_new[..., None]).sum(axis=-1))
                run_max = m_new
                in_rng = (labels >= lo) & (labels < hi)
                idx = jnp.clip(labels - lo, 0, hi - lo - 1)
                gval = jnp.take_along_axis(lc, idx[..., None],
                                           axis=-1)[..., 0]
                gold = gold + jnp.where(in_rng, gval, 0.0)
        with jax.named_scope("lm.loss"):
            ce = jnp.mean(jnp.log(run_se) + run_max - gold)
            return ce + 0.01 * aux, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------- serving
    def init_cache(self, batch_size: int, max_len: int) -> Any:
        cfg = self.cfg
        ls = cfg.n_layers - (cfg.moe.first_k_dense if cfg.moe else 0)
        if cfg.family == "ssm":
            d, nh = cfg.d_model, cfg.d_model // cfg.ssm.head_dim
            n = cfg.ssm.head_dim
            z = lambda *s: jnp.zeros(s, jnp.float32)
            return ((z(ls, batch_size, 1, d),
                     z(ls, batch_size, nh, n, n)),
                    z(ls, batch_size, 1, d))
        if cfg.family == "hybrid":
            di = cfg.ssm.expand * cfg.d_model
            nh = di // cfg.ssm.head_dim
            z = lambda *s: jnp.zeros(s, jnp.float32)
            mamba = (z(cfg.n_layers, batch_size, cfg.ssm.d_conv - 1,
                       di + 2 * cfg.ssm.d_state),
                     z(cfg.n_layers, batch_size, nh, cfg.ssm.d_state,
                       cfg.ssm.head_dim))
            n_seg = cfg.n_layers // cfg.shared_attn_every
            attn = (jnp.zeros((n_seg, batch_size, cfg.n_kv_heads, max_len,
                               cfg.d_head), L.COMPUTE_DTYPE),
                    jnp.zeros((n_seg, batch_size, cfg.n_kv_heads, max_len,
                               cfg.d_head), L.COMPUTE_DTYPE))
            return (mamba, attn)
        if cfg.mla is not None:
            z = lambda *s: jnp.zeros(s, L.COMPUTE_DTYPE)
            lat = (z(ls, batch_size, max_len, cfg.mla.kv_lora),
                   z(ls, batch_size, max_len, cfg.mla.d_rope))
            if cfg.moe and cfg.moe.first_k_dense:
                pro = (z(cfg.moe.first_k_dense, batch_size, max_len,
                         cfg.mla.kv_lora),
                       z(cfg.moe.first_k_dense, batch_size, max_len,
                         cfg.mla.d_rope))
                return (pro, lat)
            return lat
        kv = lambda n: jnp.zeros((n, batch_size, cfg.n_kv_heads, max_len,
                                  cfg.d_head), L.COMPUTE_DTYPE)
        return (kv(ls), kv(ls))

    def decode_step(self, params, batch, cache, pos):
        """One token for every sequence. batch: {"tokens": (B,1)} or
        {"embeds": (B,1,d)}; pos: scalar int32 — current write position."""
        cfg = self.cfg
        x = self.embed_inputs_decode(params, batch, pos)
        cos, sin = (self._rope_at(pos) if cfg.rope else (None, None))
        if cfg.family == "ssm":
            (tm, cm) = cache

            def body(carry, lp_st):
                lp, st_t, st_c = lp_st
                out, _, (nt, nc) = self._block(
                    lp, carry, cos, sin,
                    cache=((st_t[0], st_t[1]), st_c))
                return out, ((nt[0], nt[1]), nc)
            x, new_states = self._scan(
                body, x, (params["layers"], (tm[0], tm[1]), cm))
            new_cache = ((new_states[0][0], new_states[0][1]),
                         new_states[1])
            return self.unembed(params, x), new_cache
        if cfg.family == "hybrid":
            return self._decode_hybrid(params, x, cache, pos, cos, sin)
        if cfg.mla is not None:
            return self._decode_mla(params, x, cache, pos, cos, sin)

        ck, cv = cache

        def body(carry, lp_kv):
            lp, k_l, v_l = lp_kv
            out, _, (nk, nv) = self._block(lp, carry, cos, sin,
                                           cache=(k_l, v_l), pos=pos)
            return out, (nk, nv)
        x, (nk, nv) = self._scan(body, x, (params["layers"], ck, cv))
        return self.unembed(params, x), (nk, nv)

    def _decode_mla(self, params, x, cache, pos, cos, sin):
        cfg = self.cfg
        if cfg.moe and cfg.moe.first_k_dense:
            pro_cache, lat_cache = cache

            def pbody(carry, lp_kv):
                lp, c1, c2 = lp_kv
                out, nc = self._mla_block_and_ffn(lp, carry, cos, sin,
                                                  (c1, c2), pos, dense=True)
                return out, nc
            x, new_pro = self._scan(
                pbody, x, (params["prologue"], pro_cache[0], pro_cache[1]))
        else:
            lat_cache = cache
            new_pro = None

        def body(carry, lp_kv):
            lp, c1, c2 = lp_kv
            out, nc = self._mla_block_and_ffn(lp, carry, cos, sin,
                                              (c1, c2), pos, dense=False)
            return out, nc
        x, new_lat = self._scan(
            body, x, (params["layers"], lat_cache[0], lat_cache[1]))
        new_cache = (new_lat if new_pro is None else (new_pro, new_lat))
        return self.unembed(params, x), new_cache

    def _mla_block_and_ffn(self, p, x, cos, sin, cache, pos, dense):
        cfg = self.cfg
        norm = self._norm
        with jax.named_scope("lm.attention"):
            o, new_cache = self._mla_block_decode(p, norm(p["norm1"], x),
                                                  cos, sin, cache, pos)
        x = self._residual(x, o)
        h = norm(p["norm2"], x)
        with jax.named_scope("lm.mlp"):
            if dense or "mlp" in p:
                out = L.swiglu(p["mlp"], h)
            else:
                b, s, d = h.shape
                out, _ = M.moe_ffn(p["moe"], h.reshape(b * s, d),
                                   _moe_cfg(cfg))
                out = out.reshape(b, s, d)
        return self._residual(x, out), new_cache

    def _decode_hybrid(self, params, x, cache, pos, cos, sin):
        cfg = self.cfg
        (conv_st, h_st), (ak, av) = cache
        x0 = x
        period = cfg.shared_attn_every
        n_seg = cfg.n_layers // period
        seg_params = jax.tree.map(
            lambda a: a.reshape((n_seg, period) + a.shape[1:]),
            params["layers"])
        conv_sg = conv_st.reshape((n_seg, period) + conv_st.shape[1:])
        h_sg = h_st.reshape((n_seg, period) + h_st.shape[1:])
        new_conv, new_h, new_ak, new_av = [], [], [], []
        for seg in range(n_seg):
            x, (nk, nv) = self._shared_block(
                params["shared_block"], x, x0, cos, sin,
                cache=(ak[seg], av[seg]), pos=pos)
            new_ak.append(nk)
            new_av.append(nv)
            lp_seg = jax.tree.map(lambda a: a[seg], seg_params)

            def body(carry, lp_st):
                lp, cst, hst = lp_st
                out, _, (nc, nh) = self._block(lp, carry, cos, sin,
                                               cache=(cst, hst))
                return out, (nc, nh)
            x, (nc, nh) = self._scan(
                body, x, (lp_seg, conv_sg[seg], h_sg[seg]))
            new_conv.append(nc)
            new_h.append(nh)
        new_cache = ((jnp.concatenate(new_conv), jnp.concatenate(new_h)),
                     (jnp.stack(new_ak), jnp.stack(new_av)))
        return self.unembed(params, x), new_cache

    @jax.named_scope("lm.embed")
    def embed_inputs_decode(self, params, batch, pos):
        x = self._embed(params, batch)
        if self.cfg.family == "audio" and not self.cfg.rope:
            d = x.shape[-1]
            pos_f = jnp.arange(0, d, 2, dtype=jnp.float32)
            ang = pos.astype(jnp.float32) / jnp.power(1e4, pos_f / d)
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None]
            x = x + pe.astype(x.dtype)
        return x

    def _rope_at(self, pos):
        dim = self._rope_dim()
        inv = 1.0 / (self.cfg.rope_theta **
                     (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        ang = pos.astype(jnp.float32) * inv
        return jnp.cos(ang)[None], jnp.sin(ang)[None]

    def prefill(self, params, batch):
        """Full-context forward that also materialises the decode cache.
        Returns (last-position logits, cache)."""
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        b, s, _ = x.shape
        cos, sin = (L.rope_table(s, self._rope_dim(), cfg.rope_theta)
                    if cfg.rope else (None, None))
        if cfg.family == "ssm":
            def body(carry, lp):
                out, _, st = self._block(lp, carry, cos, sin, cache=None)
                return out, st
            x, states = self._scan(body, x, params["layers"])
            # scan stacks each state leaf along L
            cache = ((states[0][0], states[0][1]), states[1])
            return self.unembed(params, x[:, -1:]), cache
        if cfg.family == "hybrid":
            return self._prefill_hybrid(params, x, cos, sin)
        if cfg.mla is not None:
            def body(carry, lp):
                xx = carry
                norm = self._norm
                h = norm(lp["norm1"], xx)
                with jax.named_scope("lm.attention"):
                    q, k, v, c_kv = L.mla_qkv(lp["attn"], h, cfg.n_heads,
                                              cfg.mla.d_nope, cfg.mla.d_rope,
                                              cfg.mla.d_v, cos, sin)
                    o = self._attend_full(q, k, v)
                    o = L.merge_heads(o) @ L.cdt(lp["attn"]["wo"])
                xx = self._residual(xx, o)
                hh = norm(lp["norm2"], xx)
                with jax.named_scope("lm.mlp"):
                    if "moe" in lp:
                        bb, ss, dd = hh.shape
                        out, _ = M.moe_ffn(lp["moe"],
                                           hh.reshape(bb * ss, dd),
                                           _moe_cfg(cfg))
                        out = out.reshape(bb, ss, dd)
                    else:
                        out = L.swiglu(lp["mlp"], hh)
                xx = self._residual(xx, out)
                with jax.named_scope("lm.attention"):
                    k_rope = jnp.dot(h, L.cdt(lp["attn"]["wk_rope"]))
                    k_rope = L.apply_rope(k_rope[:, None], cos, sin)[:, 0]
                return xx, (c_kv, k_rope)
            if "prologue" in params:
                x, pro_cache = self._scan(body, x, params["prologue"])
            x, lat_cache = self._scan(body, x, params["layers"])
            cache = ((pro_cache, lat_cache) if "prologue" in params
                     else lat_cache)
            return self.unembed(params, x[:, -1:]), cache

        def body(carry, lp):
            out, _, kv = self._block(lp, carry, cos, sin)
            return out, kv
        x, (ks, vs) = self._scan(body, x, params["layers"])
        return self.unembed(params, x[:, -1:]), (ks, vs)

    def _prefill_hybrid(self, params, x, cos, sin):
        cfg = self.cfg
        x0 = x
        period = cfg.shared_attn_every
        n_seg = cfg.n_layers // period
        seg_params = jax.tree.map(
            lambda a: a.reshape((n_seg, period) + a.shape[1:]),
            params["layers"])
        convs, hs, aks, avs = [], [], [], []
        for seg in range(n_seg):
            x, (k, v) = self._shared_block(params["shared_block"], x, x0,
                                           cos, sin)
            aks.append(k)
            avs.append(v)
            lp_seg = jax.tree.map(lambda a: a[seg], seg_params)

            def body(carry, lp):
                out, _, st = self._block(lp, carry, cos, sin)
                return out, st
            x, (nc, nh) = self._scan(body, x, lp_seg)
            convs.append(nc)
            hs.append(nh)
        cache = ((jnp.concatenate(convs), jnp.concatenate(hs)),
                 (jnp.stack(aks), jnp.stack(avs)))
        return self.unembed(params, x[:, -1:]), cache

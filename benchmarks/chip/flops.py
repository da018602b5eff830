"""Model operations, computed from the published shapes in a
configuration file (never from the program's own cost model).

Only the matrix products are counted: they are the same work however an
implementation does them, so a faster path (a fused kernel, a one-hot
MXU join) cannot make a share of the peak read over 100%.
"""
from __future__ import annotations


def mlp_iteration_flops(rows: int, features: int, hidden: int,
                        classes: int) -> int:
    """One gradient-descent iteration of the paper's network (Eqs. 4–11):
    two forward products, the back-propagated product of Eq. 8 and the two
    weight gradients of Eqs. 10–11, each 2·m·k·n."""
    fwd = 2 * rows * features * hidden + 2 * rows * hidden * classes
    bwd = (2 * rows * classes * hidden          # d_ho · w_hoᵀ   (Eq. 8)
           + 2 * hidden * rows * classes        # a_xhᵀ · d_ho  (Eq. 10)
           + 2 * features * rows * hidden)      # imgᵀ · d_xh   (Eq. 11)
    return fwd + bwd


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    attention and MLP projections of each layer and the output head (the
    tied embedding counts once, as the head; its lookup is a gather)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    attn = d * dh * (2 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"])
    mlp = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + cfg["vocab_size"] * d


def lm_params(cfg: dict) -> int:
    """All parameters: the matrix ones, the embedding when it is not tied,
    and the RMSNorm gains (two per layer and the final one)."""
    d = cfg["hidden_size"]
    emb = 0 if cfg["tie_word_embeddings"] else cfg["vocab_size"] * d
    return lm_matmul_params(cfg) + emb + (2 * cfg["num_hidden_layers"] + 1) * d


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per token: 6·N for the products
    with weights, and causal attention, where the token at position t
    attends to t + 1 keys, (S + 1)/2 on average: 2 products of
    2·heads·head_dim FLOPs per key, three times for forward and backward.
    Recomputation (remat) is not counted."""
    n = lm_matmul_params(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    attn = 3 * 2 * 2 * width * (seq_len + 1) / 2
    return 6 * n + cfg["num_hidden_layers"] * attn

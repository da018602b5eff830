"""Granite-3.0-8B — dense GQA, tied embeddings [hf:ibm-granite/granite-3.0].

The published model also scales its embedding (×12), attention scores
(×1/128, in place of 1/sqrt(128)), each block's output before its
residual add (×0.22) and its logits (÷16), with RMSNorm eps 1e-5. The
model implements all of them (``ArchConfig.embedding_multiplier``,
``attention_multiplier``, ``residual_multiplier``, ``logits_scaling``,
``norm_eps``), but ``CONFIG`` keeps the neutral defaults: the one-chip
benchmark cell ``granite_3_8b_l2.train_4x2048`` builds its program from
``CONFIG`` and compares it with a reference that has none of them. The
four-chip cell ``granite_3_8b_l8.train_2x2`` sets the published values
from its own configuration file. Moving ``CONFIG`` onto them goes with
moving the one-chip cell and its reference.
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="granite-3-8b", family="dense", n_layers=40, d_model=4096,
    n_heads=32, n_kv_heads=8, d_head=128, d_ff=12800, vocab=49155,
    tie_embeddings=True, rope_theta=1e4)


def reduced() -> ArchConfig:
    return ArchConfig(
        name="granite-3-8b-reduced", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, d_head=32, d_ff=256, vocab=256,
        tie_embeddings=True)

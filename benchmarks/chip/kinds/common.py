"""What the drivers share: leaf-norm comparisons, and the system's LM built
for a configuration file."""
from __future__ import annotations

import statistics

#: leaves whose reference gradient is under this share of the median
#: leaf's move under Adam by round-off alone and are not compared
TINY_GRAD = 1e-3


def norm_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf of |‖prog‖ − ‖ref‖|, over the larger of that leaf's
    reference norm and the median leaf's."""
    keep = set(ref) if keep is None else keep
    med = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def kept(grad_ref: dict) -> set:
    """Leaves whose reference gradient is not nought to rounding."""
    med = statistics.median(grad_ref.values())
    return {k for k, v in grad_ref.items() if v >= TINY_GRAD * med}


def program_lm(cfg: dict):
    """The system's ``LM`` for a dense configuration file, built from the
    program's own architecture entry with the file's sizes. Raises when
    the program's parameters are not laid out as the benchmark makes
    them."""
    import dataclasses
    from functools import partial

    import jax

    from repro.configs.base import get_config
    from repro.nn.model import LM

    from ..references.dense_lm import init_params

    arch = dataclasses.replace(
        get_config(cfg["program_arch"]), name=cfg["name"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"])
    lm = LM(arch)
    key = jax.random.PRNGKey(0)
    got = jax.eval_shape(lm.init, key)
    want = jax.eval_shape(partial(init_params, cfg), key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise RuntimeError(f"the program's parameters for {cfg['name']} are "
                           f"not laid out as the configuration states")
    return lm

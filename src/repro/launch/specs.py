"""ShapeDtypeStruct stand-ins for a model's parameters (no device
allocation)."""
from __future__ import annotations

import jax

from repro.configs.base import ArchConfig
from repro.nn.model import LM


def params_specs(cfg: ArchConfig):
    lm = LM(cfg)
    return jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))

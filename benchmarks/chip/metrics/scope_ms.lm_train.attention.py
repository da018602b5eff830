"""scope_ms.lm_train.attention: device self-milliseconds per train step in
attention (``lm.attention``: projections, RoPE, the attention kernel,
the output projection), forward, recompute and backward (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, ("lm.attention",))

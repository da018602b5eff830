"""scope_ms.lm_train.mlp: device self-milliseconds per train step in
the feed-forward block (``lm.mlp``), forward, recompute and
backward (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, ("lm.mlp",))

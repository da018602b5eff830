"""scope_ms.lm_train.unscoped: device self-milliseconds per train step in
ops that carry none of the program's scopes (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, (scopes.UNSCOPED,))

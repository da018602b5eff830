"""scope_ms.mlp_dense_train.unscoped: device self-milliseconds per training iteration in
ops that carry none of the program's scopes (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_iteration(ctx, (scopes.UNSCOPED,))

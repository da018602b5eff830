"""scope_ms.mlp_dense_train.matmul: device self-milliseconds per training iteration in
the dense engine's matrix products (``ir.MatMul``) (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_iteration(ctx, ("ir.MatMul",))

"""scope_ms.mlp_train.matmul: device self-milliseconds per training iteration in
the relational matrix products: ``ir.MatMul`` and its ``rel.join``
(gather-multiply) and ``rel.group_by`` (segment sum) (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_iteration(ctx, ("ir.MatMul", "rel.join", "rel.group_by"))

"""scope_ms.mlp_train.transpose: device self-milliseconds per training iteration in
the relational transposes (``ir.Transpose``: the argsort and the
gathers that re-sort the relation) (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_iteration(ctx, ("ir.Transpose",))

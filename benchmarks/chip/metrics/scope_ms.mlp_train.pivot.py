"""scope_ms.mlp_train.pivot: device self-milliseconds per training iteration in
the dense-relation pivots (``rel.pivot``: ``RelTensor.from_dense`` and
``to_dense``, so ``Engine.lift`` and ``Engine.lower`` too) (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_iteration(ctx, ("rel.pivot",))

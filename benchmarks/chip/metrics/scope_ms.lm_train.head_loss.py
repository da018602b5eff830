"""scope_ms.lm_train.head_loss: device self-milliseconds per train step in
the final norm and vocabulary head (``lm.head``) and the
cross-entropy (``lm.loss``), forward and backward (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, ("lm.head", "lm.loss"))

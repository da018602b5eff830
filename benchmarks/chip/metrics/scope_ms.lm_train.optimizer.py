"""scope_ms.lm_train.optimizer: device self-milliseconds per train step in
gradient clipping (``train.clip``) and the AdamW update
(``train.optimizer``) (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, ("train.clip", "train.optimizer"))

"""collective_ms.lm_train_2x2: device self-milliseconds per completed step
in collective operations, averaged over chips (``collectives.py``): the
collective time the chip's op stream waits for; transfers that overlap
compute do not show as op time."""
from benchmarks.chip import collectives


def read(ctx):
    return collectives.ms_per_step(ctx)

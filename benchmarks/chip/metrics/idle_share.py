"""idle_share.<cell kind>: the share of the traced window in which no
operation ran on the device, in percent.

    100 · (1 − busy / window), busy = the union of the device's operation
    intervals in the window (``trace.reduce``), averaged over its chips.

One reader for every ``idle_share.*`` metric; the suffix only names the
end-to-end metric it moves, which ``BENCHMARK.json`` states.
"""
from benchmarks.chip.trace import idle_percent


def read(ctx):
    return idle_percent(ctx)

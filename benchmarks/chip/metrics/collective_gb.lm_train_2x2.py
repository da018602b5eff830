"""collective_gb.lm_train_2x2: gigabytes of collective operands per chip
in one step of the sharded train step, summed over kinds (all-gather,
reduce-scatter, all-reduce, collective-permute, all-to-all).

The program's counters ``train.collective_bytes.<kind>`` (``MeshStep``),
recorded from the compiled step's HLO once per compile; the cell's
``Driver`` compiles the step once. ``None`` where the program records
none (a program without them)."""


def read(ctx):
    tracer = getattr(ctx["driver"], "tracer", None)
    counters = tracer.counters if tracer is not None else {}
    got = [v for k, v in counters.items()
           if k.startswith("train.collective_bytes.")]
    return sum(got) * 1e-9 if got else None

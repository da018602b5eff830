"""The chip benchmark: ``python3 -m benchmarks.chip.run --workload <cell>``.

Everything that decides a number lives here and not in ``src/``: the
peaks, the FLOP and byte formulas, the traffic generator, the trace
reduction, the plain references and the comparison that decides
``correct``. A cell, a traffic mix or a per-layer metric is added as new
files (``configs/``, ``traffic/``, ``metrics/``, ``limits/``) plus an entry
in ``BENCHMARK.json``.
"""

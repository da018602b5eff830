"""Device time in collective operations, from the run's profiler trace.

An operation of the device plane is a collective when its HLO text's
opcode is one (``all-gather``, ``reduce-scatter``, ``all-reduce``,
``collective-permute``, ``all-to-all``, or the ``-start``/``-done`` half
of one), when it is a fusion that calls a computation named for one (the
TPU compiler writes a reduce-scatter as a fusion calling
``all-reduce-scatter*``), or when it is the ``async-collective-done`` that
waits for an asynchronous collective fusion. Each such op's self time in
``bench.window`` (``trace.self_times``) counts, averaged over chips as
``trace.reduce`` averages busy time. A collective that runs beside compute
inside an asynchronous fusion is not op time of its own and does not
count: what is read is the time the op stream spends in collectives.
"""
from __future__ import annotations

import functools
import re

from . import scopes
from . import trace as trace_mod

KINDS = ("all-gather", "reduce-scatter", "all-reduce", "collective-permute",
         "all-to-all")
_OPCODE = re.compile(r"\s(?:%s)(?:-start|-done)?\(" % "|".join(KINDS))
_CALLS = re.compile(r"\bcalls=%%?(?:%s)" % "|".join(KINDS))
COLLECTIVE, OTHER = "collective", "other"


@functools.lru_cache(maxsize=1 << 16)
def is_collective(text: str) -> bool:
    name = trace_mod.op_name(text)
    return bool(_OPCODE.search(text) or _CALLS.search(text)
                or name.startswith("async-collective-done"))


def collective_ns(ops: dict, lo: float, hi: float) -> float:
    """Self nanoseconds in collectives inside ``[lo, hi]``, averaged over
    devices; ``ops`` as :func:`scopes.load` gives them."""
    total = 0.0
    for dev_ops in ops.values():
        named = [(a, b, COLLECTIVE if is_collective(t) else OTHER)
                 for a, b, t in dev_ops]
        total += trace_mod.self_times(named, lo, hi).get(COLLECTIVE, 0.0)
    return total / len(ops) if ops else 0.0


def ms_per_step(ctx: dict) -> float | None:
    """Collective milliseconds per completed step, or ``None`` untraced."""
    if not ctx.get("trace"):
        return None
    d = ctx["driver"]
    n = d.attempted - d.failed
    path = scopes.run_trace()
    if not path or n <= 0:
        return None
    ops, (lo, hi), _ = scopes.load(path)
    return collective_ns(ops, lo, hi) * 1e-6 / n

"""Device time charged to the program's own named scopes.

The program marks its layers with ``jax.named_scope``: ``ir.<Kind>`` for
each IR node and ``ir.Update`` on both JAX engines, ``rel.join``,
``rel.group_by`` and ``rel.pivot`` in the relational engine, ``lm.embed``,
``lm.layers``, ``lm.attention``, ``lm.mlp``, ``lm.head`` and ``lm.loss`` in
the model, ``train.clip`` and ``train.optimizer`` in the train step. XLA
keeps each op's scope path (``op_name``); the profiler writes it into the
device plane's event metadata as the ``tf_op`` stat, for example
``jit(f)/transpose(jvp(lm.layers))/while/body/closed_call/checkpoint/
rematted_computation/lm.attention/dot_general:``.

``jax.profiler.ProfileData`` gives each op's start, duration and full text
but not its metadata stats, so :func:`tf_ops` reads them from the
``.xplane.pb`` with a small protobuf wire-format reader, keyed by the same
full text. :func:`charge` gives each op's self time inside ``bench.window``
(``trace.self_times``, so a ``while`` keeps only its own time) to the
innermost program scope of its path; an op with none, or whose text maps
to differing scopes, is ``unscoped``. The buckets sum to the busy time of
``trace.reduce``.

The metric readers (``metrics/scope_ms.*.py``) report device
self-milliseconds per unit of work completed in the traced window. Each
returns ``None`` untraced, and when no op of the trace carries a program
scope (a program built before the scopes were put in).
"""
from __future__ import annotations

import functools
import gzip
import os
import re
from collections import defaultdict

from . import trace as trace_mod

#: a program scope, as a whole path component
PROGRAM_SCOPE = re.compile(r"^(?:ir|rel|lm|train)\.[A-Za-z_]+$")
#: a path component with its transform wrappers: ``transpose(jvp(X))``
WRAPPED = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")
UNSCOPED = "unscoped"
TF_OP = "tf_op"


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as XSpace's metadata needs it
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def fields(buf):
    """``(field number, value)`` of one message: an int for varints and
    fixed-width values, a ``memoryview`` (no copy) for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value = int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _map_values(entries):
    """The values (field 2) of protobuf map entries."""
    for entry in entries:
        for num, value in fields(entry):
            if num == 2:
                yield value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def tf_ops(raw: bytes) -> dict[int, dict[str, set[str]]]:
    """Per TPU device: each op's full text → the ``tf_op`` paths of the
    event metadata with that text. ``lines`` are skipped uncopied.

    XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4,
    stat_metadata = 5 (maps of id → message); XEventMetadata: name = 2,
    stats = 5; XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
    str_value = 5, ref_value = 7 (the id of a stat metadata whose name is
    the string)."""
    out = {}
    for num, plane in fields(memoryview(raw)):
        if num != 1:
            continue
        name, events, stats = None, [], []
        for pnum, value in fields(plane):
            if pnum == 2:
                name = _text(value)
            elif pnum == 4:
                events.append(value)
            elif pnum == 5:
                stats.append(value)
        m = trace_mod.DEVICE_PLANE.match(name or "")
        if not m:
            continue
        stat_names = {}
        for sm in _map_values(stats):
            d = dict(fields(sm))
            stat_names[d.get(1, 0)] = _text(d.get(2, b""))
        tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
        paths: dict[str, set[str]] = defaultdict(set)
        for em in _map_values(events):
            text, found = None, []
            for enum, value in fields(em):
                if enum == 2:
                    text = _text(value)
                elif enum == 5:
                    st = dict(fields(value))
                    if st.get(1) not in tf_op_ids:
                        continue
                    if 5 in st:
                        found.append(_text(st[5]))
                    elif 7 in st:
                        found.append(stat_names.get(st[7], ""))
            if text is not None:
                paths[text].update(found)
        out[int(m.group(1))] = dict(paths)
    return out


# ---------------------------------------------------------------------------
# charging
# ---------------------------------------------------------------------------

def scope_of(path: str) -> str | None:
    """The innermost program scope among the whole components of a
    ``tf_op`` path (transform wrappers stripped), or ``None``."""
    path = path.rsplit(":", 1)[0] if ":" in path else path
    for comp in reversed(path.split("/")):
        m = WRAPPED.match(comp)
        if m and PROGRAM_SCOPE.match(m.group(1)):
            return m.group(1)
    return None


def label(paths) -> str:
    """The one program scope all of an op text's paths share, else
    ``unscoped``."""
    found = {scope_of(p) for p in paths}
    return found.pop() if len(found) == 1 and None not in found \
        else UNSCOPED


def charge(ops: dict[int, list], paths: dict[int, dict[str, set[str]]],
           lo: float, hi: float) -> dict[str, float]:
    """Nanoseconds of each scope: every op's self time inside
    ``[lo, hi]`` on its innermost program scope, averaged over devices
    (as ``trace.reduce`` averages busy time)."""
    out: dict[str, float] = defaultdict(float)
    for dev, dev_ops in ops.items():
        labels = {t: label(p) for t, p in paths.get(dev, {}).items()}
        named = [(a, b, labels.get(text, UNSCOPED))
                 for a, b, text in dev_ops]
        for scope, ns in trace_mod.self_times(named, lo, hi).items():
            out[scope] += ns / len(ops)
    return dict(out)


# ---------------------------------------------------------------------------
# the run's trace
# ---------------------------------------------------------------------------

def load(path: str) -> tuple[dict[int, list], tuple[float, float],
                             dict[int, dict[str, set[str]]]]:
    """Device ops with their full text, the ``bench.window`` bounds and
    the ``tf_op`` paths of an ``.xplane.pb`` (or its ``.gz``)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    ops, wins = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace_mod.OPS_LINE:
                ops[int(m.group(1))] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events]
            elif not m:
                wins.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events
                            if ev.name == trace_mod.WINDOW_SPAN)
    window = trace_mod.window(trace_mod.Events(ops={}, spans=wins))
    return ops, window, tf_ops(raw)


@functools.lru_cache(maxsize=2)
def _charges_of(path: str, mtime_ns: int, size: int):
    ops, (lo, hi), paths = load(path)
    out = charge(ops, paths, lo, hi)
    return out if set(out) - {UNSCOPED} else None


def charges(path: str) -> dict[str, float] | None:
    """Scope → device nanoseconds in a trace file's window, decoded once
    per file; ``None`` when no op carries a program scope."""
    st = os.stat(path)
    return _charges_of(path, st.st_mtime_ns, st.st_size)


def run_trace() -> str | None:
    """The one ``.xplane.pb`` of this run, still on disk while per-layer
    metrics are read, or ``None``."""
    from .harness import TRACE_DIR

    found = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
    return str(found[0]) if len(found) == 1 else None


def _ms_per(ctx: dict, scopes, units) -> float | None:
    """Device self-ms in ``scopes`` per unit of work, ``units(driver)``
    of them completed in the traced window."""
    if not ctx.get("trace"):
        return None
    n = units(ctx["driver"])
    path = run_trace()
    got = charges(path) if path and n > 0 else None
    if got is None:
        return None
    return sum(got.get(s, 0.0) for s in scopes) * 1e-6 / n


def ms_per_iteration(ctx: dict, scopes) -> float | None:
    """Per training iteration of the MLP drivers: (attempted − failed)
    queries × iterations per query."""
    return _ms_per(ctx, scopes,
                   lambda d: (d.attempted - d.failed) * d.iters)


def ms_per_step(ctx: dict, scopes) -> float | None:
    """Per completed train step."""
    return _ms_per(ctx, scopes, lambda d: d.attempted - d.failed)

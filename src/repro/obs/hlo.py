"""Collectives of a compiled program, read from its HLO text.

:func:`collectives` counts, per kind, the collective operations one run of
a compiled module executes and the bytes of their operands, from
``compiled.as_text()``:

* kinds: ``all-gather``, ``reduce-scatter``, ``all-reduce``,
  ``collective-permute``, ``all-to-all``. An asynchronous pair counts once
  (its ``-start``; a ``-done`` moves nothing). The TPU compiler writes a
  reduce-scatter as a fusion whose computation (``all-reduce-scatter*``)
  holds an all-reduce and a slice: that all-reduce counts as a
  reduce-scatter.
* one logical collective may be printed in several computations (the TPU
  compiler's asynchronous collective fusions repeat it in their start,
  loop and done parts, under one ``channel_id``): it counts once, as often
  as the most frequently run of those computations.
* a computation runs as often as its callers do, times the trip count of
  a ``while`` whose body or condition it is: the loop's
  ``known_trip_count``, else the constant its condition compares the
  counter against (``lax.scan`` counts from 0 by 1), else 1.
"""
from __future__ import annotations

import re
from collections import defaultdict

KINDS = ("all-gather", "reduce-scatter", "all-reduce", "collective-permute",
         "all-to-all")
_OPCODES = {k: k for k in KINDS} | {f"{k}-start": k for k in KINDS}

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_CALLEE = re.compile(r"\b(calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_CALLEES = re.compile(r"\b(?:branch_computations|called_computations)="
                      r"\{([^}]*)\}")
_ARRAY = re.compile(r"\b(pred|[a-z]+\d+(?:[a-z]\d+)*[a-z]*)\[([\d,]*)\]")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CHANNEL = re.compile(r"\bchannel_id=(\d+)")
_CONST = re.compile(r"^[su]\d+\[\]\S* constant\((\d+)\)")


def _bytes(type_text: str) -> int:
    """Bytes of an array type or a tuple of them (``bf16[8,128]{...}``)."""
    out = 0
    for dtype, dims in _ARRAY.findall(type_text):
        bits = 8 if dtype == "pred" else int(re.search(r"\d+", dtype)[0])
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out += n * max(bits // 8, 1)
    return out


def _split(rhs: str) -> tuple[str, str, str]:
    """``type opcode(operands), attrs`` → (type, opcode, the rest)."""
    depth, i = 0, 0
    while i < len(rhs):                  # the type may be a nested tuple
        c = rhs[i]
        depth += c in "({["
        depth -= c in ")}]"
        if c == " " and depth == 0:
            break
        i += 1
    m = re.match(r"\s*([\w\-]+)\((.*)$", rhs[i:])
    return (rhs[:i], m[1], m[2]) if m else (rhs[:i], "", rhs[i:])


def _parse(text: str):
    comps: dict[str, list] = {}
    entry, name = None, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h and not line.startswith(" "):
            name = h[1]
            comps[name] = []
            if line.startswith("ENTRY"):
                entry = name
            continue
        m = _INSTR.match(line) if name else None
        if m:
            comps[name].append((m[1], *_split(m[2])))
    return comps, entry


def _trip(instr_rest: str, cond: list) -> int:
    m = _TRIP.search(instr_rest)
    if m:
        return int(m[1])
    consts = {n: int(c[1]) for n, t, op, rest in cond
              if op == "constant" and (c := _CONST.match(f"{t} {op}({rest}"))}
    for n, t, op, rest in cond:
        if op == "compare" and "direction=LT" in rest:
            args = re.findall(r"%?([\w.\-]+)", rest.split(")", 1)[0])
            for a in args:
                if a in consts:
                    return consts[a]
    return 1


def collectives(text: str) -> dict[str, dict[str, int]]:
    """``{kind: {"count": n, "bytes": b}}`` for one run of the module."""
    comps, entry = _parse(text)
    runs: dict[str, int] = defaultdict(int)

    def visit(comp: str, times: int):
        runs[comp] += times
        for _, _, op, rest in comps.get(comp, ()):
            callees = [(k, c) for k, c in _CALLEE.findall(rest)]
            for group in _CALLEES.findall(rest):
                callees += [("calls", c.strip().lstrip("%"))
                            for c in group.split(",") if c.strip()]
            trip = 1
            if op == "while":
                cond = dict(callees).get("condition")
                trip = _trip(rest, comps.get(cond, []))
            for kind, callee in callees:
                if callee in comps and kind != "to_apply":
                    visit(callee, times * (trip if op == "while" else 1))

    if entry is not None:
        visit(entry, 1)
    seen: dict[str, tuple[str, int, int]] = {}
    for comp, instrs in comps.items():
        types = {n: t for n, t, _, _ in instrs}
        for n, t, op, rest in instrs:
            kind = _OPCODES.get(op)
            if kind is None or not runs.get(comp):
                continue
            if comp.startswith("all-reduce-scatter"):
                kind = "reduce-scatter"
            operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
            nbytes = sum(_bytes(types.get(o, "")) for o in operands)
            ch = _CHANNEL.search(rest)
            key = f"channel {ch[1]}" if ch else f"{comp}/{n}"
            if key not in seen or runs[comp] > seen[key][1]:
                seen[key] = (kind, runs[comp], nbytes)
    out = {k: {"count": 0, "bytes": 0} for k in KINDS}
    for kind, times, nbytes in seen.values():
        out[kind]["count"] += times
        out[kind]["bytes"] += times * nbytes
    return out

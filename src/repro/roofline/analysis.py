"""Three-term roofline from a compiled dry-run artifact (no hardware).

    compute    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory     = HLO_bytes_per_chip / HBM_bw
    collective = wire_bytes_per_chip / link_bw

The peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``;
a kind that is not in the table is an error, never a default.

``compiled.cost_analysis()`` on the SPMD-partitioned executable reports
*per-device* FLOPs/bytes (verified in tests). Collective bytes are not in
cost_analysis:
we parse ``compiled.as_text()`` (post-partitioning HLO) and sum the result
sizes of all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute ops, converted to wire bytes with ring-algorithm factors:

    all-reduce      2·(g−1)/g · bytes      (reduce-scatter + all-gather)
    all-gather      (g−1)/g · result
    reduce-scatter  (g−1)   · result       (operand = g · result)
    all-to-all      (g−1)/g · bytes
    collective-permute  1 · bytes

where g = participants per replica group (parsed from the instruction).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one device kind."""

    flops: float       # bf16 FLOP/s
    hbm_bw: float      # HBM bytes/s
    link_bw: float     # bytes/s per ICI link
    source: str


#: Peaks per ``device_kind`` (the string JAX reports for the device).
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip "
               "interconnect (200 GB/s over 4 ICI links)"),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?((?:[a-z0-9]+\[[0-9,]*\][^ ]*(?:,\s*)?)+)(?:\))?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return 1


@dataclasses.dataclass
class CollectiveStats:
    by_kind: dict
    wire_bytes: float      # per-chip bytes crossing links

    def total_result_bytes(self) -> float:
        return sum(v["bytes"] for v in self.by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    by_kind: dict[str, dict] = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shapes_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(shapes_str)
        g = max(_group_size(line), 1)
        if kind == "all-reduce":
            w = 2.0 * (g - 1) / g * nbytes
        elif kind == "all-gather":
            w = (g - 1) / g * nbytes
        elif kind == "reduce-scatter":
            w = float(g - 1) * nbytes
        elif kind == "all-to-all":
            w = (g - 1) / g * nbytes
        else:  # collective-permute
            w = float(nbytes)
        rec = by_kind.setdefault(kind, {"count": 0, "bytes": 0.0,
                                        "wire": 0.0})
        rec["count"] += 1
        rec["bytes"] += nbytes
        rec["wire"] += w
        wire += w
    return CollectiveStats(by_kind=by_kind, wire_bytes=wire)


@dataclasses.dataclass
class Roofline:
    flops: float                  # per chip
    hbm_bytes: float              # per chip
    wire_bytes: float             # per chip
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    peak_flops: float             # of the chip the terms assume
    model_flops: float = 0.0      # 6·N·D (per chip share)
    collectives: dict | None = None

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved at the modelled step
        time: MODEL_FLOPS / (peak · step_time)."""
        return (self.model_flops / self.peak_flops) / self.step_s \
            if self.step_s else 0.0


def model_flops(cfg, shape, n_chips: int) -> float:
    """MODEL_FLOPS per chip: 6·N·D for training (fwd+bwd), 2·N·D for
    inference, with N = active params (MoE: routed top-k + shared)."""
    n_active = cfg.n_active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                   else (shape.seq_len if shape.kind ==
                                         "prefill" else 1))
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens / n_chips

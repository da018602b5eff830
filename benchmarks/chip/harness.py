"""One run of one cell: check the device, set up, measure, check, print.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n>
        --seconds <s> --trace <0|1> [--rehearse]

The cell's entry in ``BENCHMARK.json`` names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``);
the mix's ``kind`` names the driver (``kinds/<kind>.py``), and the cell's
limits are ``limits/<cell>.json``. Per-layer metrics are
``metrics/<name>.py``, or the reader of the name's first part, each a
``read(ctx)`` that returns a number or ``None``. Nothing here changes when
a cell, a mix or a metric is added.

``--rehearse`` runs the cell at the tiny sizes under each file's
``rehearsal`` key, on any device, and prints no metric and no device.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from . import peaks as peaks_mod  # noqa: E402
from . import trace as trace_mod  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
#: a traced run measures at most this long: the profiler's export of a
#: dispatch-bound cell's 51 s took over three minutes
TRACE_SECONDS = 20.0


class NoChip(RuntimeError):
    """The device is not one the benchmark measures on."""


# ---------------------------------------------------------------------------
# the spec and the files it names
# ---------------------------------------------------------------------------

def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def read_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def merged(doc: dict, rehearse: bool) -> dict:
    """``doc`` with its ``rehearsal`` overrides applied when rehearsing."""
    out = {k: v for k, v in doc.items() if k != "rehearsal"}
    if rehearse:
        out.update(copy.deepcopy(doc.get("rehearsal", {})))
    return out


def resolve(spec: dict, name: str, here: pathlib.Path = HERE,
            rehearse: bool = False) -> dict:
    """Everything one cell needs, found by the names in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = merged(read_json(here.parents[1] / cfg_entry["file"]), rehearse)
    mix = merged(read_json(here / "traffic" / f"{cell['traffic']}.json"),
                 rehearse)
    limits_file = here / "limits" / f"{name}.json"
    limits = read_json(limits_file) if limits_file.exists() else {}
    reports = lambda m: name in m.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if reports(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if m["moves"] in names and reports(m)]
    return {"cell": cell, "config": cfg, "traffic": mix, "limits": limits,
            "end_to_end": e2e, "per_layer": layer}


def load_module(path: pathlib.Path):
    """A module from a file whose name may hold dots (``mfu.lm_train.py``)."""
    modname = "benchmarks.chip._file_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: pathlib.Path = HERE):
    """``metrics/<name>.py``, else the reader of the name's first part
    (``metrics/mfu.py`` for ``mfu.lm_train``)."""
    own = here / "metrics" / f"{name}.py"
    return load_module(own if own.exists() else
                       here / "metrics" / f"{name.split('.')[0]}.py")


def driver_class(kind: str):
    return importlib.import_module(f"benchmarks.chip.kinds.{kind}").Driver


# ---------------------------------------------------------------------------
# device, compile cache, clocks
# ---------------------------------------------------------------------------

def device_info(chips: int) -> tuple[dict, peaks_mod.ChipPeaks]:
    """The device JAX found, and its peaks. Raises :class:`NoChip` unless
    it is a TPU of a known kind with at least ``chips`` devices."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform!r} "
                     f"({dev.device_kind})")
    try:
        pk = peaks_mod.peaks(dev.device_kind)
    except ValueError as e:
        raise NoChip(str(e)) from None
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return ({"platform": dev.platform, "kind": dev.device_kind,
             "count": len(devs)}, pk)


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set,
    else at a fixed directory inside the checkout; every program cached."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts JAX's own compile events (tracing, lowering, compiling) and
    the time they take, from its monitoring hooks."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.events.append((event, duration))

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int) -> tuple[int, float]:
        evs = self.events[mark:]
        return (sum(e == self.EVENTS[2] for e, _ in evs),
                sum(d for e, d in evs if e == self.EVENTS[2]))


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each number the cell's limits name against its limit: correct only
    if there is at least one and each is a number no larger than its
    limit. Readings the limits do not name are not compared."""
    out, ok = {}, bool(limits)
    for name, lim in limits.items():
        value = readings.get(name)
        ok &= (value is not None and math.isfinite(value)
               and value <= lim["limit"])
        out[name] = {"value": value, "limit": lim["limit"]}
    return ok, out


def memory_peak(chips: int) -> int | None:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()[:chips]]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(
        prog="python3 -m benchmarks.chip.run",
        description="Run one cell of BENCHMARK.json on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any device; prints no metric")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the profiler's .xplane.pb into DIR")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    return args


def run(argv, t_start: float) -> dict:
    args = parse(argv)
    spec = load_spec()
    res = resolve(spec, args.workload, rehearse=args.rehearse)
    chips = res["cell"]["chips"]
    device, pk = (None, None) if args.rehearse else device_info(chips)
    if not args.rehearse:
        enable_compile_cache()
    clock = CompileClock()
    drv = driver_class(res["traffic"]["kind"])(
        res["config"], res["traffic"], args.seed)

    drv.setup()
    setup_s = time.time() - t_start
    setup_compiles = clock.since(0)
    traced = bool(args.trace) and not args.rehearse
    mark = clock.mark()
    with (tracing(drv.SPANS) if traced else contextlib.nullcontext()) as tr:
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            t0 = time.perf_counter()
            drv.window(min(args.seconds, TRACE_SECONDS) if traced
                       else args.seconds)
            window_s = time.perf_counter() - t0
    in_window = clock.since(mark)
    mem = memory_peak(chips) if device else None
    e2e = drv.end_to_end(window_s)
    attempted, failed = drv.attempted, drv.failed
    for line in drv.report():
        log(line)
    log(f"setup_s {setup_s:.3f} (compiles {setup_compiles[0]}, "
        f"{setup_compiles[1]:.2f} s); window {window_s:.3f} s, compiles "
        f"inside it {in_window[0]}")

    metrics = {}
    if traced:
        reduced = tr.result
        ctx = {"driver": drv, "trace": reduced, "peaks": pk,
               "config": res["config"]}
        for m in res["per_layer"]:
            value = metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    elif device:
        e2e["setup_s"] = setup_s
        for m in res["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    drv.free()
    gc.collect()
    readings = drv.check()
    correct, check = judge(readings, res["limits"])
    for name, value in readings.items():
        if name not in check:
            log(f"reading {name} {value!r} (not compared)")
    for name, c in check.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")

    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.rehearse:
        out["rehearsal"] = True
    else:
        out["metrics"] = metrics
        out["device"] = dict(device, memory_peak_bytes=mem)
        if traced:
            out["device"].update(busy_s=tr.result["busy_s"],
                                 window_s=tr.result["window_s"])
            out["breakdown"] = tr.result["breakdown"]
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(tr.path, args.keep_trace)
    out["check"] = check
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return out


class tracing:
    """Profile the window; on exit, reduce the trace (``.result``)."""

    def __init__(self, span_names):
        self.span_names = span_names

    def __enter__(self):
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        if exc[0] is not None:
            return False
        found = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        if len(found) != 1:
            raise RuntimeError(f"expected one trace file, found {found}")
        self.path = str(found[0])
        events = trace_mod.load(self.path, self.span_names)
        self.result = trace_mod.reduce(events, *trace_mod.window(events))
        return False


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.time() if t_start is None else t_start
    try:
        out = run(sys.argv[1:] if argv is None else argv, t_start)
    except NoChip as e:
        log(f"benchmarks.chip: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0

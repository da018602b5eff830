"""Readings that set a cell's limits: the program's check on many seeds,
and the same check with the lower-precision control, or a planted fault,
in the program's place. Not part of a benchmark run.

    python3 -m benchmarks.chip.readings --workload <cell> --seeds 1,2,3
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3]
        [--out chiprun_out/readings.jsonl] [--rehearse]

The cells train, so they read without a measured window. Controls:
``high`` (three-pass bfloat16) for the float32 MLP, ``fp8`` for the
bfloat16 LM. Fault: half of each batch left out, the mean taken over
the rest.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from . import harness


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def mlp(drv, mode: str):
    from .references import mlp as ref

    it, lr = drv.iters, drv.cfg["lr"]
    if mode == "control":
        return drv.reference(lambda x, y, w: ref.train_jnp(
            x, y, w, it, lr, "high"))
    half = drv.cfg["rows"] // 2
    scale = drv.cfg["rows"] / half
    return drv.reference(lambda x, y, w: ref.train_jnp(
        x[:half], y[:half], w, it, lr * scale, "f32"))


def readings(drv, kind: str, mode: str) -> dict:
    drv.setup()
    drv.free()
    gc.collect()
    if mode == "program":
        return drv.check()
    if kind == "mlp_train":
        return drv.readings(mlp(drv, mode))
    if kind == "lm_train":
        want = drv.reference("f32")
        got = (drv.reference("fp8") if mode == "control" else
               drv.reference("f32", batch_rows=drv.mix["batch"] // 2))
        return drv.readings(got, want)
    raise ValueError(f"no {mode} readings for {kind}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    res = harness.resolve(harness.load_spec(), args.workload,
                          rehearse=args.rehearse)
    if not args.rehearse:
        try:
            harness.device_info(res["cell"]["chips"])
        except harness.NoChip as e:
            harness.log(f"benchmarks.chip.readings: {e}")
            return 3
        harness.enable_compile_cache()
    kind = res["traffic"]["kind"]
    cls = harness.driver_class(kind)
    runs = ([("program", s) for s in args.seeds]
            + [("control", s) for s in args.control_seeds]
            + [("fault_half_batch", s) for s in args.fault_seeds])
    out = open(args.out, "a") if args.out else None
    for mode, seed in runs:
        t0 = time.time()
        drv = cls(res["config"], res["traffic"], seed)
        row = {"workload": args.workload, "mode": mode, "seed": seed,
               "readings": readings(drv, kind, mode),
               "seconds": time.time() - t0}
        del drv
        gc.collect()
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

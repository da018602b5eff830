"""Record ``data/scopes_v5e.xplane.pb.gz``: the program's named scopes as
one TPU v5e's profiler sees them.

    python3 tests/bench_chip/record_scopes_fixture.py OUT.xplane.pb.gz

Three relational and three dense MLP training queries of 10 iterations at
the paper's widths on 200 rows, and two LM train steps at the rehearsal
sizes of ``granite_3_8b_l2``, run through the benchmark's own drivers
inside one ``bench.window`` annotation and traced; the ``.xplane.pb`` is
written gzipped. Set-up and compiles run before the trace starts.
"""
from __future__ import annotations

import gzip
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from benchmarks.chip import harness, trace  # noqa: E402

SEED = 2**31 + 13
MLP_ROWS = 200
QUERIES = 3
LM_STEPS = 2


def drivers():
    spec = harness.load_spec()
    out = []
    for cell in ("mlp_mnist_h200.rel_train", "mlp_mnist_h200.dense_train"):
        res = harness.resolve(spec, cell)
        cfg = dict(res["config"], rows=MLP_ROWS)
        mix = dict(res["traffic"], ahead_s=0)
        out.append(harness.driver_class(mix["kind"])(cfg, mix, SEED))
    res = harness.resolve(spec, "granite_3_8b_l2.train_4x2048", rehearse=True)
    out.append(harness.driver_class(res["traffic"]["kind"])(
        res["config"], res["traffic"], SEED))
    return out


def main(out_path: str):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    rel, dense, lm = drivers()
    for drv in (rel, dense, lm):
        drv.setup()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for drv in (rel, dense):
            for _ in range(QUERIES):
                with jax.profiler.TraceAnnotation("mlp.query"):
                    jax.block_until_ready(drv.send())
        for _ in range(LM_STEPS):
            lm.train_step()
    jax.profiler.stop_trace()
    found = sorted(pathlib.Path(tmp).glob("**/*.xplane.pb"))
    with open(found[0], "rb") as f, gzip.open(out_path, "wb") as g:
        g.write(f.read())
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out_path}: {os.path.getsize(out_path)} bytes, device "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])

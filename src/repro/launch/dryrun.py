import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes, record memory/cost/roofline.

The two lines above MUST stay first: jax locks the device count on first
init, and the dry-run needs 512 placeholder host devices. (Smoke tests and
benchmarks must NOT import this module — they see 1 device.)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  ... --arch dbrx_132b --shape train_4k --mesh both            # one cell
  ... --set attn_impl=chunked --set remat=dots                 # perf knobs
  ... --out results/dryrun.json
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (ARCH_IDS, SHAPES, get_config,
                                shape_applicable)
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   opt_shardings, param_shardings)
from repro.launch.specs import SDS, batch_specs, cache_specs, params_specs
from repro.nn.model import LM
from repro.optim.optimizers import adamw
from repro.roofline.analysis import (Roofline, model_flops,
                                     parse_collectives, peaks)
from repro.train.trainer import make_train_step


def apply_overrides(cfg, overrides: dict):
    """--set key=value knobs; moe.*/ssm.* update the nested specs."""
    moe_kv = {k[4:]: v for k, v in overrides.items()
              if k.startswith("moe.")}
    ssm_kv = {k[4:]: v for k, v in overrides.items()
              if k.startswith("ssm.")}
    top_kv = {k: v for k, v in overrides.items() if "." not in k}
    if moe_kv and cfg.moe is not None:
        cfg = dataclasses.replace(cfg,
                                  moe=dataclasses.replace(cfg.moe, **moe_kv))
    if ssm_kv and cfg.ssm is not None:
        cfg = dataclasses.replace(cfg,
                                  ssm=dataclasses.replace(cfg.ssm, **ssm_kv))
    if top_kv:
        cfg = dataclasses.replace(cfg, **top_kv)
    return cfg


def ssm_scan_corrections(cfg, shape, n_chips: int) -> tuple[float, float]:
    """Analytic per-chip (flops, bytes) for recurrence steps hidden inside
    lax.scan bodies (counted once by cost_analysis). RWKV-6 time-mix state
    ops: ~5·H·N² FLOPs and 2·H·N²·4 B state traffic per token per layer;
    Mamba-2 inter-chunk recurrence: ~3·H·N·P per chunk per layer. Training
    multiplies by 3 (fwd + bwd recompute + grad accumulation of state)."""
    if shape.kind == "decode":
        return 0.0, 0.0          # decode lowers one explicit step per layer
    tokens = shape.global_batch * shape.seq_len
    mult = 3.0 if shape.kind == "train" else 1.0
    fl = by = 0.0
    if cfg.family == "ssm":
        h = cfg.d_model // cfg.ssm.head_dim
        n = cfg.ssm.head_dim
        fl = 5.0 * h * n * n * tokens * cfg.n_layers * mult
        by = 2.0 * h * n * n * 4 * tokens * cfg.n_layers * mult
    elif cfg.family == "hybrid":
        h = cfg.n_heads_mamba()
        n, pdim = cfg.ssm.d_state, cfg.ssm.head_dim
        chunks = tokens / max(cfg.ssm.chunk, 1)
        fl = 3.0 * h * n * pdim * chunks * cfg.n_layers * mult
        by = 2.0 * h * n * pdim * 4 * chunks * cfg.n_layers * mult
    return fl / n_chips, by / n_chips


def build_lowered(cfg, shape, mesh, fsdp: bool = True):
    """Lower one entry point (train_step / prefill / decode_step) for
    ``cfg`` on ``mesh`` with full production shardings."""
    from repro.launch.mesh import data_axes
    from repro.nn.moe import set_moe_mesh
    set_moe_mesh(mesh, data_axes(mesh))     # impl='shard' engine support
    lm = LM(cfg)
    p_shapes = params_specs(cfg)
    psh = param_shardings(p_shapes, mesh, fsdp=fsdp)
    b_shapes = batch_specs(cfg, shape)
    bsh = batch_shardings(b_shapes, mesh, shape.global_batch)
    with mesh:
        if shape.kind == "train":
            opt = adamw(3e-4,
                        mixed_precision=cfg.param_dtype != "float32")
            o_shapes = jax.eval_shape(opt.init, p_shapes)
            osh = opt_shardings(o_shapes, psh, mesh)
            step = make_train_step(lm.loss_fn, opt)
            return jax.jit(step, in_shardings=(psh, osh, bsh),
                           out_shardings=(psh, osh, None),
                           donate_argnums=(0, 1)).lower(
                               p_shapes, o_shapes, b_shapes)
        if shape.kind == "prefill":
            return jax.jit(lm.prefill, in_shardings=(psh, bsh)).lower(
                p_shapes, b_shapes)
        # decode — serve_step: one new token against a seq_len cache
        c_shapes = cache_specs(cfg, shape)
        csh = cache_shardings(c_shapes, mesh, shape.global_batch,
                              shape.seq_len, cfg)
        return jax.jit(
            lm.decode_step,
            in_shardings=(psh, bsh, csh, None),
            out_shardings=(None, csh),
            donate_argnums=(2,)).lower(
                p_shapes, b_shapes, c_shapes, SDS((), jnp.int32))


#: the production meshes are v5e pods; the roofline terms use its peaks
DEVICE_KIND = "TPU v5 lite"


def _costs(compiled):
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)), colls)


def measure_costs(cfg, shape, mesh, fsdp: bool):
    """Exact-rate cost measurement: XLA's cost analysis counts a lax.scan
    body ONCE (verified in tests/test_roofline.py), so the full scanned
    model under-reports. We compile two UNROLLED shallow variants with the
    real dims — depth L1 and L2 — whose per-layer cost delta is exact, and
    extrapolate affinely: total = c(L1) + (n_units − L1_units)·delta.
    Embedding / LM head / loss land in the base term of both variants."""
    pro = cfg.moe.first_k_dense if cfg.moe else 0
    step = cfg.shared_attn_every if cfg.family == "hybrid" else 1
    l1, l2 = pro + step, pro + 2 * step
    n_units = (cfg.n_layers - pro) // step
    out = []
    for lv in (l1, l2):
        cv = dataclasses.replace(cfg, n_layers=lv, scan_layers=False)
        compiled = build_lowered(cv, shape, mesh, fsdp).compile()
        out.append(_costs(compiled))
    (f1, b1, c1), (f2, b2, c2) = out
    k = n_units - 1
    flops = f1 + k * (f2 - f1)
    hbm = b1 + k * (b2 - b1)
    wire = c1.wire_bytes + k * (c2.wire_bytes - c1.wire_bytes)
    by_kind = {}
    kinds = set(c1.by_kind) | set(c2.by_kind)
    z = {"count": 0, "bytes": 0.0, "wire": 0.0}
    for kd in kinds:
        a, b = c1.by_kind.get(kd, z), c2.by_kind.get(kd, z)
        by_kind[kd] = {m: a[m] + k * (b[m] - a[m])
                       for m in ("count", "bytes", "wire")}
    return flops, hbm, wire, by_kind


def lower_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
               overrides: dict | None = None, fsdp: bool = True):
    """Returns (record dict, compiled) for one (arch × shape × mesh) cell.

    The FULL model (scan-over-layers) is lowered and compiled on the mesh —
    that compile succeeding is the dry-run pass/fail criterion and supplies
    memory_analysis(). FLOP/byte/collective rates come from measure_costs
    (depth-extrapolated, exact); SSM time-scan steps are added analytically
    (ssm_scan_corrections)."""
    cfg = get_config(arch_id)
    if overrides:
        cfg = apply_overrides(cfg, overrides)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skip (full attention)"}, None
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    # the full-model compile (pass/fail + memory_analysis) uses the
    # loop-bounded twins so liveness reflects sequential block reuse;
    # the cost variants below use the unrolled twins for exact FLOPs
    mem_cfg = dataclasses.replace(cfg, flash_impl="scan", ssd_impl="scan")
    compiled = build_lowered(mem_cfg, shape, mesh, fsdp).compile()
    flops, hbm, wire, by_kind = measure_costs(cfg, shape, mesh, fsdp)
    dt = time.time() - t0
    mem = compiled.memory_analysis()
    mf = model_flops(cfg, shape, n_chips)
    xf, xb = ssm_scan_corrections(cfg, shape, n_chips)
    chip = peaks(DEVICE_KIND)
    flops += xf
    hbm += xb
    terms = {"compute": flops / chip.flops, "memory": hbm / chip.hbm_bw,
             "collective": wire / chip.link_bw}
    rl = Roofline(flops=flops, hbm_bytes=hbm, wire_bytes=wire,
                  compute_s=terms["compute"], memory_s=terms["memory"],
                  collective_s=terms["collective"],
                  bottleneck=max(terms, key=terms.get),
                  peak_flops=chip.flops, model_flops=mf,
                  collectives=by_kind)
    record = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok", "compile_s": round(dt, 1),
        "overrides": overrides or {},
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "peak_extra": mem.temp_size_in_bytes,
            "total_live": (mem.argument_size_in_bytes +
                           mem.output_size_in_bytes +
                           mem.temp_size_in_bytes -
                           mem.alias_size_in_bytes),
        },
        "flops_per_device": rl.flops,
        "hbm_bytes_per_device": rl.hbm_bytes,
        "wire_bytes_per_device": rl.wire_bytes,
        "collectives": rl.collectives,
        "terms_s": {"compute": rl.compute_s, "memory": rl.memory_s,
                    "collective": rl.collective_s},
        "bottleneck": rl.bottleneck,
        "model_flops_per_device": mf,
        "useful_flop_ratio": round(rl.useful_ratio, 4),
        "roofline_fraction": round(rl.roofline_fraction, 4),
    }
    return record, compiled


def run_cells(archs, shapes, meshes, overrides=None, out_path=None,
              fsdp=True, verbose=True):
    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
                try:
                    rec, _ = lower_cell(arch, shape, multi_pod=mp,
                                        overrides=overrides, fsdp=fsdp)
                except Exception as e:  # a failure here is a system bug
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": f"FAIL: {type(e).__name__}: {e}"}
                    if verbose:
                        traceback.print_exc()
                records.append(rec)
                if verbose:
                    st = rec["status"]
                    extra = ""
                    if st == "ok":
                        t = rec["terms_s"]
                        extra = (f" [{rec['bottleneck']}] "
                                 f"c={t['compute']:.3g}s m={t['memory']:.3g}s"
                                 f" x={t['collective']:.3g}s "
                                 f"compile={rec['compile_s']}s")
                    print(f"{tag:58s} {st}{extra}", flush=True)
                if out_path:
                    with open(out_path, "w") as f:
                        json.dump(records, f, indent=1)
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=ARCH_IDS)
    ap.add_argument("--shape", nargs="*", default=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--set", dest="sets", action="append", default=[],
                    help="cfg override key=value (e.g. attn_impl=chunked)")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    overrides = {}
    for s in args.sets:
        k, v = s.split("=", 1)
        overrides[k] = (int(v) if v.isdigit() else
                        (float(v) if v.replace(".", "").isdigit() else v))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    recs = run_cells(args.arch, args.shape, meshes, overrides or None,
                     args.out, fsdp=not args.no_fsdp)
    n_ok = sum(r["status"] == "ok" for r in recs)
    n_skip = sum(r["status"].startswith("skip") for r in recs)
    n_fail = len(recs) - n_ok - n_skip
    print(f"\n{n_ok} ok / {n_skip} skip / {n_fail} FAIL of {len(recs)}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())

"""A language model trained by the system's ``Trainer`` on a
``data`` × ``model`` mesh (the mix's ``mesh``, ``launch.mesh.make_mesh``),
driven as ``Trainer.run`` drives it: one batch placed on the mesh per step
with the step index advancing, the step, then a host read of its metrics.

Set-up makes the reference's seeded weights and the optimizer state on
the mesh, in the trainer's shardings, and runs the first three steps
through the compiled step; the window goes on from there. The check follows those
three steps with the plain float32 reference of the configuration
(``references/granite_lm.py``, on its own 1-D mesh of the same chips): each
step's loss, the first gradient as the optimizer holds it, and each leaf's
change over the three steps, as ``lm_train`` compares them.

Readings that set the limits, not part of a benchmark run:

    python3 -m benchmarks.chip.kinds.lm_train_mesh --workload <cell>
        --seeds 1,2 [--modes program,control,fault_half_batch]
        [--program-seeds 3,4] [--out readings.jsonl] [--rehearse]

``control`` is the reference with fp8 products in the program's place,
``fault_half_batch`` the reference on half of each batch (the first half
counted twice, so that it runs the float32 reference's own compiled
step); each seed's float32 reference is computed once for all its
modes. ``--program-seeds`` are further seeds read for the program alone,
after the others.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

from ..references import granite_lm as ref
from . import lm_train
from .common import program_lm
from .lm_train import CHECK_STEPS, norms

MODES = ("program", "control", "fault_half_batch")


def granite_program(cfg: dict):
    """The system's ``LM`` for the file, with granite's multipliers and
    norm epsilon as the file states them."""
    from repro.nn.model import LM

    arch = dataclasses.replace(
        program_lm(cfg).cfg,
        embedding_multiplier=cfg["embedding_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], norm_eps=cfg["rms_norm_eps"])
    return LM(arch)


class Driver(lm_train.Driver):
    """``lm_train``'s driver with the program on the mix's mesh and the
    reference on the same chips."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro import obs
        from repro.launch.mesh import make_mesh

        super().__init__(cfg, mix, seed)
        self.chips = math.prod(mix["mesh"])
        self.mesh = make_mesh(mix["mesh"])
        self.tracer = obs.Tracer()

    # -- the program ----------------------------------------------------
    def setup(self):
        from repro import obs
        from repro.optim import adamw
        from repro.train import Trainer

        opt = self.opt
        optimizer = adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                          eps=opt["eps"], weight_decay=opt["weight_decay"])
        init = partial(ref.init_params, self.cfg)
        with obs.use(self.tracer):
            self.trainer = Trainer(granite_program(self.cfg), optimizer,
                                   self.feed, clip_norm=opt["clip_norm"],
                                   mesh=self.mesh)
            p0 = jax.jit(init, out_shardings=self.trainer.param_sharding)
            self.params = p0(self.wkey)
            self.opt_state = jax.jit(
                optimizer.init,
                out_shardings=self.trainer.opt_sharding)(self.params)
            self.step = 0
            self.losses = [self.train_step()["loss"]]
            m = self.opt_state["m"]
            self.grad = {k: v / (1 - opt["b1"]) for k, v in norms(m).items()}
            self.losses += [self.train_step()["loss"]
                            for _ in range(CHECK_STEPS - 1)]
        p0 = p0(self.wkey)
        self.change = norms(jax.tree.map(jnp.subtract, self.params, p0))
        del p0

    def train_step(self) -> dict:
        with jax.profiler.TraceAnnotation("lm.batch"):
            batch = self.trainer.place_batch(self.feed.batch_at(self.step))
        with jax.profiler.TraceAnnotation("lm.step"):
            self.params, self.opt_state, metrics = self.trainer.step_fn(
                self.params, self.opt_state, batch)
        with jax.profiler.TraceAnnotation("lm.read_metrics"):
            metrics = jax.tree.map(float, metrics)
        self.step += 1
        return metrics

    def end_to_end(self, window_s: float) -> dict:
        out = super().end_to_end(window_s)
        self.facts["chips"] = self.chips
        return out

    # -- the check ------------------------------------------------------
    def reference(self, mode: str, half_batch: bool = False) -> dict:
        """Losses, first clipped gradient and change over the check steps
        by the reference at ``mode`` on the mesh's chips; ``half_batch``
        puts the first half of each batch in place of the second (a
        planted fault: the mean over half the batch)."""
        m = ref.mesh(self.mesh.devices.flatten())
        shape = (self.mix["batch"], self.mix["seq_len"])
        step = ref.make_train_step(self.cfg, self.opt, mode, m, shape)
        p, mo, v = ref.init_state(self.cfg, self.wkey, m)
        losses, grad = [], None
        for i in range(CHECK_STEPS):
            b = self.feed.batch_at(i)
            if half_batch:
                b = jax.tree.map(lambda a: jnp.concatenate(
                    [a[:shape[0] // 2]] * 2), b)
            b = jax.device_put(b, ref.batch_placed(shape, m))
            p, mo, v, loss, g = step(p, mo, v, jnp.int32(i + 1),
                                     b["tokens"], b["labels"])
            losses.append(float(loss))
            if grad is None:
                grad = {k: float(x) for k, x in g.items()}
        del mo, v
        p0 = ref.init_state(self.cfg, self.wkey, m, moments=False)
        change = norms(jax.tree.map(jnp.subtract, p, p0))
        return {"losses": losses, "grad": grad, "change": change}

    def calibrate(self, modes) -> dict:
        """``{mode: readings}``: the program's check, the fp8 control and
        the half-batch fault, against one float32 reference."""
        got = {}
        if "program" in modes:
            self.setup()
            got["program"] = self.program_readings()
            self.free()
            gc.collect()
        if "control" in modes:
            got["control"] = self.reference("fp8")
        if "fault_half_batch" in modes:
            got["fault_half_batch"] = self.reference("f32", half_batch=True)
        want = self.reference("f32")
        return {mode: self.readings(r, want) for mode, r in got.items()}


def main(argv=None) -> int:
    from .. import harness

    ap = argparse.ArgumentParser(description="Readings that set the "
                                 "limits of a meshed LM training cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda t: [int(s) for s in t.split(",") if s])
    ap.add_argument("--modes", default=",".join(MODES),
                    type=lambda t: [m for m in t.split(",") if m])
    ap.add_argument("--program-seeds", default=[],
                    type=lambda t: [int(s) for s in t.split(",") if s])
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    res = harness.resolve(harness.load_spec(), args.workload,
                          rehearse=args.rehearse)
    if not args.rehearse:
        try:
            harness.device_info(res["cell"]["chips"])
        except harness.NoChip as e:
            harness.log(f"lm_train_mesh readings: {e}")
            return 3
        harness.enable_compile_cache()
    runs = ([(seed, args.modes) for seed in args.seeds]
            + [(seed, ["program"]) for seed in args.program_seeds])
    for seed, modes in runs:
        t0 = time.time()
        drv = Driver(res["config"], res["traffic"], seed)
        for mode, r in drv.calibrate(modes).items():
            line = json.dumps({"workload": args.workload, "mode": mode,
                               "seed": seed, "readings": r,
                               "seconds": time.time() - t0})
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
        del drv
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

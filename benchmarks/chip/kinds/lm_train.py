"""A language model trained by the system's ``Trainer`` step, driven as
``Trainer.run`` drives it: one batch per step with the step index
advancing, the step, then a host read of its metrics.

Set-up makes the weights from the seed and runs the first three steps
through the compiled step; the window goes on from there. The check
follows those three steps with the plain float32 reference: each step's
loss, the first gradient as the optimizer holds it after one step (Adam's
first moment over 1 − β₁), and each leaf's change over the three steps."""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops
from ..references import dense_lm as ref
from ..traffic import seed_key, token_batch
from .common import kept, norm_gap, program_lm

CHECK_STEPS = 3


class Feed:
    """``batch_at(step)``, the data interface ``Trainer`` reads."""

    def __init__(self, key, batch: int, seq_len: int, vocab: int):
        self.key = key
        self._make = jax.jit(partial(token_batch, batch=batch,
                                     seq_len=seq_len, vocab=vocab))

    def batch_at(self, step: int) -> dict:
        return self._make(self.key, jnp.int32(step))


def norms(tree) -> dict:
    return {k: float(v) for k, v in jax.jit(ref.leaf_norms)(tree).items()}


class Driver:
    SPANS = ("lm.batch", "lm.step", "lm.read_metrics")

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.opt = mix["optimizer"]
        self.tokens_per_step = mix["batch"] * mix["seq_len"]
        self.feed = Feed(seed_key(seed, 1), mix["batch"], mix["seq_len"],
                         cfg["vocab_size"])
        self.init = jax.jit(partial(ref.init_params, cfg))
        self.wkey = seed_key(seed, 0)
        self.attempted = self.failed = 0

    # -- the program ----------------------------------------------------
    def setup(self):
        from repro.optim import adamw
        from repro.train import Trainer

        opt = self.opt
        lm = program_lm(self.cfg)
        optimizer = adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                          eps=opt["eps"], weight_decay=opt["weight_decay"])
        self.trainer = Trainer(lm, optimizer, self.feed,
                               clip_norm=opt["clip_norm"])
        self.params = self.init(self.wkey)
        self.opt_state = jax.jit(optimizer.init)(self.params)
        self.step = 0
        self.losses = [self.train_step()["loss"]]
        m = self.opt_state["m"]
        self.grad = {k: v / (1 - opt["b1"]) for k, v in norms(m).items()}
        self.losses += [self.train_step()["loss"]
                        for _ in range(CHECK_STEPS - 1)]
        p0 = self.init(self.wkey)
        self.change = norms(jax.tree.map(jnp.subtract, self.params, p0))
        del p0

    def train_step(self) -> dict:
        with jax.profiler.TraceAnnotation("lm.batch"):
            batch = self.feed.batch_at(self.step)
        with jax.profiler.TraceAnnotation("lm.step"):
            self.params, self.opt_state, metrics = self.trainer.step_fn(
                self.params, self.opt_state, batch)
        with jax.profiler.TraceAnnotation("lm.read_metrics"):
            metrics = jax.tree.map(float, metrics)
        self.step += 1
        return metrics

    def window(self, seconds: float):
        start, t0 = self.step, time.perf_counter()
        while True:
            loss = self.train_step()["loss"]
            self.failed += not np.isfinite(loss)
            if time.perf_counter() - t0 >= seconds:
                break
        self.attempted = self.step - start

    def end_to_end(self, window_s: float) -> dict:
        tokens = (self.attempted - self.failed) * self.tokens_per_step
        self.facts = {"window_s": window_s, "model_flops": tokens
                      * flops.lm_train_flops_per_token(self.cfg,
                                                       self.mix["seq_len"])}
        return {self.mix["metric"]: tokens / window_s}

    def report(self):
        return [f"lm_train: {self.attempted} steps of "
                f"{self.mix['batch']}x{self.mix['seq_len']} tokens in the "
                f"window; check-step losses "
                + ", ".join(f"{v:.6f}" for v in self.losses)]

    def free(self):
        del self.params, self.opt_state, self.trainer

    # -- the check ------------------------------------------------------
    def reference(self, mode: str, batch_rows=None) -> dict:
        """Losses, first clipped gradient and change over the check steps
        by the reference at ``mode``; ``batch_rows`` keeps only those rows
        of each batch (a planted fault)."""
        step = ref.make_train_step(self.cfg, self.opt, mode)
        p = self.init(self.wkey)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses, grad = [], None
        for i in range(CHECK_STEPS):
            b = self.feed.batch_at(i)
            if batch_rows is not None:
                b = jax.tree.map(lambda a: a[:batch_rows], b)
            p, m, v, loss, g = step(p, m, v, jnp.int32(i + 1),
                                    b["tokens"], b["labels"])
            losses.append(float(loss))
            if grad is None:
                grad = {k: float(x) for k, x in g.items()}
        del m, v
        p0 = self.init(self.wkey)
        change = norms(jax.tree.map(jnp.subtract, p, p0))
        return {"losses": losses, "grad": grad, "change": change}

    @staticmethod
    def readings(got: dict, want: dict) -> dict:
        return {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                                zip(got["losses"], want["losses"])),
                "grad_gap": norm_gap(got["grad"], want["grad"]),
                "change_gap": norm_gap(got["change"], want["change"],
                                       kept(want["grad"]))}

    def program_readings(self) -> dict:
        return {"losses": self.losses, "grad": self.grad,
                "change": self.change}

    def check(self) -> dict:
        return self.readings(self.program_readings(), self.reference("f32"))

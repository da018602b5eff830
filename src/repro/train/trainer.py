"""Training loop: microbatched grad accumulation, clipping, optimizer,
checkpoint/restart, straggler monitoring.

``make_train_step`` builds the pure step function the dry-run lowers; the
``Trainer`` class wraps it with the operational substrate (fault tolerance,
checkpoint cadence, metrics) for the runnable examples, on one device or,
given a ``mesh``, with its state and batches sharded over the mesh by the
rules of ``launch/sharding.py``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.launch.sharding import (batch_shardings, opt_shardings,
                                   param_shardings)
from repro.obs import tracer_of
from repro.obs.hlo import collectives
from repro.optim.optimizers import Optimizer, clip_by_global_norm


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    grad_accum: int = 1, clip_norm: float = 1.0):
    """loss_fn(params, batch) → (loss, metrics). Returns
    step(params, opt_state, batch) → (params, opt_state, metrics).

    With ``grad_accum > 1`` the global batch is split along axis 0 into
    microbatches accumulated in a ``lax.scan`` — activation memory drops by
    the accumulation factor while keeping the same global batch (a standard
    memory-roofline lever, see §Perf). Clipping and the update run under the
    named scopes ``train.clip`` and ``train.optimizer``.
    """
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params, opt_state, batch):
        if grad_accum == 1:
            (loss, metrics), grads = vg(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)

            def acc(carry, mb):
                (loss, metrics), grads = vg(params, mb)
                g_acc, l_acc = carry
                g_acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                                     g_acc, grads)
                return (g_acc, l_acc + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (grads, loss), metrics = jax.lax.scan(
                acc, (g0, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss / grad_accum
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        with jax.named_scope("train.clip"):
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        with jax.named_scope("train.optimizer"):
            params, opt_state = optimizer.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return params, opt_state, metrics

    return step


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StragglerMonitor:
    """Per-step wall-time tracker. On a real fleet the flag feeds the
    scheduler (preempt/replace the slow host); here the policy is the
    tested artifact: flag any step slower than ``threshold ×`` the running
    median over the trailing window."""

    window: int = 50
    threshold: float = 3.0

    def __post_init__(self):
        self.times: list[float] = []
        self.flagged: list[int] = []

    def record(self, step: int, seconds: float) -> bool:
        baseline = sorted(self.times[-self.window:])
        self.times.append(seconds)
        if len(baseline) >= 5:
            median = baseline[len(baseline) // 2]
            if seconds > self.threshold * median:
                self.flagged.append(step)
                return True
        return False


class MeshStep:
    """The train step jitted for a mesh, compiled ahead of its first call
    for each signature of its inputs. Each compile adds what one step's
    program moves between chips (``obs.hlo.collectives``, per chip) to the
    counters ``train.collectives.<kind>`` and
    ``train.collective_bytes.<kind>`` of the active tracer."""

    def __init__(self, jitted, owner):
        self.jitted, self.owner = jitted, owner
        self._compiled: dict = {}

    def lower(self, *args):
        """The step traced under the mesh (``jax.set_mesh``), so that the
        model lays its activations out on it (``nn.layers.split_tokens``)."""
        with jax.set_mesh(self.owner.mesh):
            return self.jitted.lower(*args)

    def __call__(self, *args):
        sig = tuple((x.shape, x.dtype, getattr(x, "sharding", None))
                    for x in jax.tree.leaves(args))
        fn = self._compiled.get(sig)
        if fn is None:
            fn = self._compiled[sig] = self.lower(*args).compile()
            tr = tracer_of(self.owner)
            for kind, c in collectives(fn.as_text()).items():
                tr.inc(f"train.collectives.{kind}", c["count"])
                tr.inc(f"train.collective_bytes.{kind}", c["bytes"])
        return fn(*args)


class Trainer:
    """Checkpointed, straggler-aware training driver.

    With a ``mesh`` (axes ``data`` and ``model``) parameters and optimizer
    state live on it in the shardings of ``launch/sharding.py``
    (``param_shardings``, ``opt_shardings``: tensor-parallel over
    ``model``, FSDP and ZeRO over ``data``), batches are placed by
    ``batch_shardings``, and ``step_fn`` is a :class:`MeshStep`."""

    def __init__(self, model, optimizer: Optimizer, data,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 50, grad_accum: int = 1,
                 clip_norm: float = 1.0, donate: bool = True, mesh=None):
        self.model = model
        self.optimizer = optimizer
        self.data = data
        self.mesh = mesh
        step = make_train_step(model.loss_fn, optimizer, grad_accum,
                               clip_norm)
        donate_argnums = (0, 1) if donate else ()
        if mesh is None:
            self.step_fn = jax.jit(step, donate_argnums=donate_argnums)
        else:
            p_shapes = jax.eval_shape(
                model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
            self.param_sharding = param_shardings(p_shapes, mesh)
            self.opt_sharding = opt_shardings(
                jax.eval_shape(optimizer.init, p_shapes),
                self.param_sharding, mesh)
            state = (self.param_sharding, self.opt_sharding)
            self.step_fn = MeshStep(jax.jit(
                step, in_shardings=state + (None,),
                out_shardings=state + (None,),
                donate_argnums=donate_argnums), self)
        self.ckpt = (Checkpointer(checkpoint_dir)
                     if checkpoint_dir else None)
        self.checkpoint_every = checkpoint_every
        self.monitor = StragglerMonitor()
        self.history: list[dict] = []

    def init_state(self, key):
        """The model's parameters and the optimizer's state for them. With
        a mesh both are made on it, each leaf in its own sharding and
        never whole on one device, under the span ``train.place``."""
        if self.mesh is None:
            params = self.model.init(key)
            return params, self.optimizer.init(params)

        def make(k):
            params = self.model.init(k)
            return params, self.optimizer.init(params)

        with tracer_of(self).span("train.place"):
            state = jax.jit(make, out_shardings=(
                self.param_sharding, self.opt_sharding))(key)
            return jax.block_until_ready(state)

    def place_batch(self, batch: dict) -> dict:
        """``batch`` on the mesh by ``batch_shardings`` (as it is without
        a mesh)."""
        if self.mesh is None:
            return batch
        rows = jax.tree.leaves(batch)[0].shape[0]
        return jax.device_put(batch, batch_shardings(batch, self.mesh, rows))

    def restore_or_init(self, key):
        """Crash-restart entry point: resume from the latest checkpoint if
        one exists, else initialise fresh. The data pipeline is a pure
        function of the step, so the token stream resumes exactly."""
        params, opt_state = self.init_state(key)
        start = 0
        if self.ckpt and self.ckpt.latest_step() is not None:
            (params, opt_state), start = self.ckpt.restore(
                (params, opt_state), sharding_tree=None if self.mesh is None
                else (self.param_sharding, self.opt_sharding))
        return params, opt_state, start

    def run(self, key, n_steps: int, log_every: int = 10,
            log_fn=print) -> dict:
        """Steps ``start..n_steps``, each under the profiler's
        ``StepTraceAnnotation("train", step_num=step)``, with the spans
        ``train.batch``, ``train.step``, ``train.read_metrics`` and
        ``train.checkpoint`` on the active tracer. Its counter
        ``train.host_syncs`` counts the device→host reads: one
        ``jax.device_get`` of the metrics per step and one snapshot per
        checkpoint."""
        tr = tracer_of(self)
        params, opt_state, start = self.restore_or_init(key)
        for step in range(start, n_steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with tr.span("train.batch", step=step):
                    batch = self.place_batch(self.data.batch_at(step))
                t0 = time.perf_counter()
                with tr.span("train.step", step=step):
                    params, opt_state, metrics = self.step_fn(
                        params, opt_state, batch)
                with tr.span("train.read_metrics", step=step):
                    metrics = jax.tree.map(float, jax.device_get(metrics))
                    tr.inc("train.host_syncs")
                dt = time.perf_counter() - t0
            straggle = self.monitor.record(step, dt)
            rec = dict(metrics, step=step, seconds=dt, straggler=straggle)
            self.history.append(rec)
            if log_every and step % log_every == 0:
                log_fn(f"step {step:5d} loss {metrics['loss']:.4f} "
                       f"({dt * 1e3:.0f} ms){' STRAGGLER' if straggle else ''}")
            if self.ckpt and (step + 1) % self.checkpoint_every == 0:
                with tr.span("train.checkpoint", step=step + 1):
                    self.ckpt.save(step + 1, (params, opt_state))
                    tr.inc("train.host_syncs")
        if self.ckpt:
            with tr.span("train.checkpoint", step=n_steps):
                self.ckpt.save(n_steps, (params, opt_state), blocking=True)
                tr.inc("train.host_syncs")
        return {"params": params, "opt_state": opt_state,
                "history": self.history}

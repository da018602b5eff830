#!/usr/bin/env python
"""Drive the main paths once on a TPU and check what comes out.

    python chip_smoke.py              # phases A, B and C on one chip
    python chip_smoke.py --chips 4    # the sharded training step on a 2x2
                                      # mesh against one chip, nothing else

Phases, all in this one process (a chip belongs to one process at a time):

A  The paper's MLP (Algorithm 1) trained by ``nn2sql.train`` at the
   Fig. 9/10 shapes, 2000×784→200→10 for 5 iterations, with
   ``Engine("dense")`` and ``Engine("relational")``; final weights against
   the float64 NumPy reference of Listing 2 and against each other, once at
   the chip's default matmul precision and once at "highest".
B  granite-3-8b at its published widths, depth cut from 40 layers to 2,
   trained 5 AdamW steps by ``Trainer`` on one repeated batch of 4×2048
   tokens: finite losses and grad norms, a first loss near ln(vocab), and
   a falling loss.
C  ``ServingEngine`` over phase B's weights, one slot, 3 seeded prompts
   decoded greedily; every generated token against the argmax of
   ``LM.forward`` over the same sequence (teacher forcing).

Each phase prints one line; the last line of standard output is the JSON
object ``{"ok": true, "device": {...}}``. Any failed comparison or error
ends the run with a non-zero exit before that line, and so does a run
without a TPU. ``compile_s`` is what JAX's own monitoring events report for
tracing, lowering and compiling during the phase; ``run_s`` is the phase's
wall time less that.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs.base import get_config  # noqa: E402
from repro.core import Engine, nn2sql  # noqa: E402
from repro.data import (TokenPipeline, make_mnist_like,  # noqa: E402
                        one_hot_labels)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.nn.model import LM  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402
from repro.train import Trainer, make_train_step  # noqa: E402

#: float32 at "highest" against float64: the bound the CPU tests use
HIGHEST_BOUND = 1e-4
#: the default-precision gap may be this many times the gap that rounding
#: every matmul operand to bfloat16 once gives in float64 NumPy: that is
#: what the chip's default precision does, and the factor leaves room for
#: where its compiler rounds (a fused transpose, a reordered sum)
DEFAULT_FACTOR = 4.0
#: a served token may differ from the reference's argmax only where the
#: reference's top two logits are this close. Both paths compute in
#: bfloat16, whose spacing is 2^-5 for logits in [4, 8) and 2^-4 in
#: [8, 16); the cached one-token decode and the full-sequence forward
#: round differently through every layer, which moves a logit by a few
#: such steps, and 0.25 is eight of the finer ones.
TIE_MARGIN = 0.25
LR = 3e-4            # launch/train.py's default


class CompileClock:
    """Time JAX spends tracing, lowering and compiling, from its own
    monitoring events. Tracing an outer function traces the inner jitted
    ones too, so the spans nest: the clock counts their union."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_time_span_listener(self._on)

    def _on(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def seconds(self, since: float) -> float:
        total, reach = 0.0, since
        for start, end in sorted(self.spans):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total


class Phase:
    """Times one phase: ``compile_s`` from the clock, ``run_s`` the rest."""

    def __init__(self, clock: CompileClock):
        self.clock = clock

    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *exc):
        self.compile_s = self.clock.seconds(self.t0)
        self.run_s = time.time() - self.t0 - self.compile_s


def check(ok: bool, msg: str):
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def report(name: str, ph: Phase, result: str):
    print(f"{name}: compile_s={ph.compile_s:.2f} run_s={ph.run_s:.2f} "
          f"{result}", flush=True)


def device_memory() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "peak_hbm=not reported"
    return (f"peak_hbm_gib={stats['peak_bytes_in_use'] / 2**30:.2f} of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f}")


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")


# ---------------------------------------------------------------------------
# phase A: the paper's MLP on both JAX engines
# ---------------------------------------------------------------------------

def numpy_train_bf16_operands(x, y, w0, n_iters, lr):
    """``nn2sql.numpy_train`` in float64 with every matmul operand rounded
    to bfloat16 first: the rounding the chip's default precision applies."""
    bf = lambda a: a.astype(np.float32).astype(jnp.bfloat16).astype(
        np.float64)
    dot = lambda a, b: bf(a) @ bf(b)
    w_xh, w_ho = (np.asarray(w0[k], np.float64) for k in ("w_xh", "w_ho"))
    for _ in range(n_iters):
        a_xh = 1.0 / (1.0 + np.exp(-dot(x, w_xh)))
        a_ho = 1.0 / (1.0 + np.exp(-dot(a_xh, w_ho)))
        d_ho = 2.0 * (a_ho - y) * a_ho * (1.0 - a_ho)
        d_xh = dot(d_ho, w_ho.T) * a_xh * (1.0 - a_xh)
        w_ho = w_ho - lr * dot(a_xh.T, d_ho)
        w_xh = w_xh - lr * dot(x.T, d_xh)
    return {"w_xh": w_xh, "w_ho": w_ho}


def max_gap(a: dict, b: dict) -> float:
    return max(float(np.max(np.abs(np.asarray(a[k], np.float64)
                                   - np.asarray(b[k], np.float64))))
               for k in a)


def phase_a(clock, spec=nn2sql.MLPSpec(2000, 784, 200, 10), n_iters=5):
    x, y = make_mnist_like(spec.n_rows, seed=0)
    y_oh = one_hot_labels(y, spec.n_classes)
    g = nn2sql.build_graph(spec)
    w0 = nn2sql.init_weights(spec)
    xn, yn = np.asarray(x, np.float64), np.asarray(y_oh, np.float64)
    ref = nn2sql.numpy_train(xn, yn, spec.n_hidden, n_iters, lr=spec.lr)
    emulated = numpy_train_bf16_operands(xn, yn, w0, n_iters, spec.lr)
    bounds = {
        "highest": (HIGHEST_BOUND, "float32 vs float64, as the CPU tests"),
        "default": (DEFAULT_FACTOR * max_gap(emulated, ref),
                    f"{DEFAULT_FACTOR:g}x the gap of bf16-rounded matmul "
                    f"operands in float64 NumPy"),
    }
    shape = (f"{spec.n_rows}x{spec.n_features}->{spec.n_hidden}->"
             f"{spec.n_classes} x{n_iters} it")
    engines = {}
    for precision in ("highest", "default"):
        bound, why = bounds[precision]
        finals = {}
        for kind in ("dense", "relational"):
            ctx = (jax.default_matmul_precision("highest")
                   if precision == "highest" else contextlib.nullcontext())
            with Phase(clock) as ph, ctx:
                final, _ = nn2sql.train(g, w0, x, y_oh, n_iters,
                                        Engine(kind))
                finals[kind] = jax.tree.map(np.asarray, final)
            gap = max_gap(finals[kind], ref)
            report(f"A.{kind}.{precision}", ph,
                   f"mlp {shape}: max|w-numpy64|={gap:.3e} "
                   f"bound={bound:.3e} ({why})")
            check(gap <= bound, f"A: {kind} at {precision} precision is "
                  f"{gap:.3e} from the reference, over {bound:.3e}")
        engines[precision] = max_gap(finals["dense"], finals["relational"])
    print(f"A.engines: max|dense-relational| highest={engines['highest']:.3e}"
          f" (bound {HIGHEST_BOUND:g}) default={engines['default']:.3e}",
          flush=True)
    check(engines["highest"] <= HIGHEST_BOUND,
          f"A: the engines differ by {engines['highest']:.3e} at highest "
          f"precision")


# ---------------------------------------------------------------------------
# phase B: the LM trainer
# ---------------------------------------------------------------------------

class RepeatedBatch:
    """The pipeline's step-0 batch at every step, so the loss must fall."""

    def __init__(self, pipeline: TokenPipeline):
        self.batch = pipeline.batch_at(0)

    def batch_at(self, step: int) -> dict:
        return self.batch


def granite_cut(n_layers: int = 2):
    """granite-3-8b at published widths with its depth cut."""
    full = get_config("granite_3_8b")
    return full, dataclasses.replace(full, n_layers=n_layers)


def phase_b(clock, cfg, full_layers, batch=4, seq=2048, steps=5):
    lm = LM(cfg)
    data = RepeatedBatch(TokenPipeline(vocab=cfg.vocab, seq_len=seq,
                                       global_batch=batch))
    trainer = Trainer(lm, adamw(LR), data)
    with Phase(clock) as ph:
        out = trainer.run(jax.random.PRNGKey(0), steps, log_every=0)
    hist = out["history"]
    losses = [h["loss"] for h in hist]
    norms = [h["grad_norm"] for h in hist]
    ln_v = math.log(cfg.vocab)
    report("B.train", ph,
           f"{cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
           f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
           f"layers={cfg.n_layers} (cut from {full_layers}) "
           f"batch={batch}x{seq} losses="
           + ",".join(f"{v:.4f}" for v in losses)
           + " grad_norms=" + ",".join(f"{v:.4f}" for v in norms)
           + " step_s=" + ",".join(f"{h['seconds']:.3f}" for h in hist)
           + f" ln(vocab)={ln_v:.3f} {device_memory()}")
    check(all(math.isfinite(v) for v in losses + norms),
          "B: a loss or grad norm is not finite")
    check(abs(losses[0] - ln_v) <= 2.0,
          f"B: first loss {losses[0]:.4f} is not within 2 of ln(vocab)")
    check(losses[-1] < losses[0], "B: the loss did not fall")
    return lm, out["params"]


# ---------------------------------------------------------------------------
# phase C: the server against teacher forcing
# ---------------------------------------------------------------------------

def phase_c(clock, lm, params, n_requests=3, max_new=16, max_len=256,
            prompt_lens=(8, 33), seed=0):
    vocab = lm.cfg.vocab
    rng = np.random.RandomState(seed)
    reqs = [Request(uid, rng.randint(0, vocab, rng.randint(*prompt_lens))
                    .astype(np.int32), max_new_tokens=max_new)
            for uid in range(n_requests)]
    eng = ServingEngine(lm, params, max_len=max_len, batch_slots=1)
    forward = jax.jit(lambda p, t: lm.forward(p, {"tokens": t})[0])
    with Phase(clock) as ph:
        for r in reqs:
            eng.submit(r)
        done = eng.run_to_completion()
        check(len(done) == n_requests, f"C: {len(done)} of {n_requests} "
              f"requests finished")
        n_tok = n_tie = 0
        worst = 0.0
        for r in done:
            check(len(r.generated) == max_new,
                  f"C: request {r.uid} generated {len(r.generated)} tokens")
            seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                       np.int32)])
            logits = np.asarray(forward(params, jnp.asarray(seq[None])),
                                np.float32)[0, len(r.prompt) - 1:]
            for i, tok in enumerate(r.generated):
                n_tok += 1
                want = int(np.argmax(logits[i]))
                if tok == want:
                    continue
                gap = float(logits[i, want] - logits[i, tok])
                worst = max(worst, gap)
                n_tie += 1
                check(gap <= TIE_MARGIN,
                      f"C: request {r.uid} token {i} is {tok}, the "
                      f"reference's argmax is {want}, {gap:.4f} higher")
    report("C.serve", ph,
           f"{n_requests} requests, prompts "
           + ",".join(str(len(r.prompt)) for r in reqs)
           + f" tokens, {max_new} greedy tokens each, 1 slot, "
           f"max_len={max_len}: {n_tok - n_tie}/{n_tok} tokens equal the "
           f"teacher-forced argmax, {n_tie} near-ties (largest logit gap "
           f"{worst:.4f}, margin {TIE_MARGIN:g})")


# ---------------------------------------------------------------------------
# --chips 4: the sharded training step against one chip
# ---------------------------------------------------------------------------

#: loss of the same step on the mesh and on one chip. The logits are
#: bfloat16 (spacing 2^-4 at the loss's scale of 8–16) and the mesh sums
#: partial products in another order; a mean over 8192 tokens of such
#: roundings moves far less than one spacing.
SHARDED_LOSS_TOL = 1e-2
#: relative difference of the gradient norms, a sum of squares over 0.6 B
#: elements whose bfloat16 partials the mesh adds in another order
SHARDED_GNORM_RTOL = 1e-2
#: AdamW's first step moves each element by lr·(sign(g) + wd·p). Where a
#: gradient element is within rounding of zero the two programs may take
#: opposite signs, which moves that element by 2·lr. For gradients spread
#: like a Gaussian that happens to a share of about 0.4·ε of elements, ε
#: being the relative rounding noise of a few bfloat16 spacings (2^-8
#: each), so under 1%; the bound leaves room for gradients more peaked
#: at zero
SHARDED_FLIP_SHARE = 5e-2


def phase_sharded(clock, cfg, batch=4, seq=2048, mesh_shape=(2, 2)):
    from jax.sharding import AxisType

    from repro.launch.sharding import (batch_shardings, opt_shardings,
                                       param_shardings)

    lm = LM(cfg)
    opt = adamw(LR)
    step = make_train_step(lm.loss_fn, opt)
    data = TokenPipeline(vocab=cfg.vocab, seq_len=seq, global_batch=batch)
    mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with Phase(clock) as ph:
        params = jax.jit(lm.init)(jax.random.PRNGKey(0))
        opt_state = jax.jit(opt.init)(params)
        b = data.batch_at(0)
        p_sh = param_shardings(jax.eval_shape(lambda: params), mesh)
        o_sh = opt_shardings(jax.eval_shape(lambda: opt_state), p_sh, mesh)
        b_sh = batch_shardings(jax.eval_shape(lambda: b), mesh, batch)
        # no donation: placing a replicated leaf may reuse the one-chip
        # buffer, which the one-chip step below still reads
        sharded = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None))
        p4, o4, m4 = sharded(jax.device_put(params, p_sh),
                             jax.device_put(opt_state, o_sh),
                             jax.device_put(b, b_sh))
        p4 = jax.tree.map(np.asarray, p4)
        m4 = jax.tree.map(float, m4)
        del o4      # chip 0 needs the room for the one-chip step
        one_chip = jax.jit(step, donate_argnums=(0, 1))
        p1, _, m1 = one_chip(params, opt_state, b)
        p1 = jax.tree.map(np.asarray, p1)
        m1 = jax.tree.map(float, m1)
    n = flips = 0
    worst = 0.0
    for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        d = np.abs(a.astype(np.float64) - c.astype(np.float64))
        worst = max(worst, float(d.max()))
        flips += int(np.sum(d > LR))
        n += d.size
    flip_share = flips / n
    loss_gap = abs(m4["loss"] - m1["loss"])
    gnorm_rel = abs(m4["grad_norm"] - m1["grad_norm"]) / m1["grad_norm"]
    report(f"S.train_{mesh_shape[0]}x{mesh_shape[1]}", ph,
           f"{cfg.name} layers={cfg.n_layers} batch={batch}x{seq} "
           f"loss mesh={m4['loss']:.5f} one_chip={m1['loss']:.5f} "
           f"(tol {SHARDED_LOSS_TOL:g}) grad_norm rel gap={gnorm_rel:.3e} "
           f"(tol {SHARDED_GNORM_RTOL:g}) params max|d|={worst:.3e} "
           f"(2*lr={2 * LR:g}) flipped={flip_share:.3e} "
           f"(tol {SHARDED_FLIP_SHARE:g}) of {n} elements")
    check(loss_gap <= SHARDED_LOSS_TOL,
          f"S: loss gap {loss_gap:.4e}")
    check(gnorm_rel <= SHARDED_GNORM_RTOL,
          f"S: grad-norm relative gap {gnorm_rel:.3e}")
    check(worst <= 2 * LR * (1 + 1e-3), f"S: a parameter moved {worst:.3e}")
    check(flip_share <= SHARDED_FLIP_SHARE,
          f"S: {flip_share:.3e} of parameters took the other sign")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded step on a 2x2 mesh")
    args = ap.parse_args(argv)
    require_tpu()
    check(len(jax.devices()) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(jax.devices())}")
    enable_compile_cache()
    clock = CompileClock()
    full, cfg = granite_cut(2)
    if args.chips == 4:
        phase_sharded(clock, cfg)
    else:
        phase_a(clock)
        lm, params = phase_b(clock, cfg, full.n_layers)
        phase_c(clock, lm, params)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

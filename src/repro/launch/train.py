"""Production training launcher.

On a real fleet each host runs this with its process index; here it runs
the same code path single-host.

    PYTHONPATH=src python -m repro.launch.train --arch yi_6b --reduced \
        --steps 50 --seq 128 --batch 8 [--mesh 2x2]

``--mesh DxM`` trains on a ``data`` × ``model`` mesh of the first D·M
devices, through the same ``Trainer`` with its state sharded
(``launch/sharding.py``).
"""
import argparse

import jax

from repro.configs.base import get_config
from repro.data import TokenPipeline, stub_frontend_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.nn.model import LM
from repro.optim import adamw
from repro.train import Trainer


class StubPipeline:
    """Frontend-stub data source ([audio]/[vlm] archs)."""

    def __init__(self, cfg, seq_len, global_batch):
        self.cfg, self.seq, self.batch = cfg, seq_len, global_batch

    def batch_at(self, step: int):
        return stub_frontend_batch(self.cfg.stub_frontend, self.batch,
                                   self.seq, self.cfg.d_model,
                                   self.cfg.vocab, seed=step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="train on a data x model mesh, e.g. 2x2")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    lm = LM(cfg)
    if cfg.stub_frontend:
        data = StubPipeline(cfg, args.seq, args.batch)
    else:
        data = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                             global_batch=args.batch)
    mesh = (make_mesh([int(n) for n in args.mesh.split("x")])
            if args.mesh else None)
    trainer = Trainer(lm, adamw(args.lr), data,
                      checkpoint_dir=args.ckpt_dir,
                      grad_accum=args.grad_accum, mesh=mesh)
    out = trainer.run(jax.random.PRNGKey(0), args.steps, log_every=10)
    h = out["history"]
    print(f"done: loss {h[0]['loss']:.4f} → {h[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()

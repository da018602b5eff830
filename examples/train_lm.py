"""End-to-end LM training driver (deliverable b).

Trains a reduced-width decoder LM with the full production substrate:
token pipeline → scan-over-layers model → AdamW → grad clip → async
checkpointing → straggler monitoring → crash-safe restart.

    PYTHONPATH=src python examples/train_lm.py                  # ~2M params
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 200
    PYTHONPATH=src python examples/train_lm.py --arch dbrx_132b # reduced MoE

A few hundred steps on the default preset takes minutes on CPU; the 100m
preset is the "train a ~100M model for a few hundred steps" configuration
(expect ~1 s/step on a modern CPU core, faster on real accelerators).
"""
import argparse
import dataclasses

import jax

from repro.configs.base import ArchConfig, get_config
from repro.data import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.nn.model import LM
from repro.optim import adamw
from repro.train import Trainer

PRESETS = {
    "tiny": dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
                 d_head=32, d_ff=512, vocab=2048),
    "20m": dict(n_layers=8, d_model=256, n_heads=8, n_kv_heads=4,
                d_head=32, d_ff=1024, vocab=8192),
    "100m": dict(n_layers=12, d_model=512, n_heads=8, n_kv_heads=4,
                 d_head=64, d_ff=2048, vocab=16384),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=list(PRESETS), default="tiny")
    ap.add_argument("--arch", default=None,
                    help="train a reduced assigned arch instead of a preset")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    enable_compile_cache()
    if args.arch:
        cfg = get_config(args.arch, reduced=True)
    else:
        cfg = ArchConfig(name=f"lm-{args.preset}", family="dense",
                         **PRESETS[args.preset])
    lm = LM(cfg)
    n = cfg.n_params
    print(f"arch={cfg.name} params≈{n / 1e6:.1f}M "
          f"tokens/step={args.batch * args.seq}")
    data = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch)
    trainer = Trainer(lm, adamw(args.lr), data,
                      checkpoint_dir=args.ckpt_dir, checkpoint_every=100,
                      grad_accum=args.grad_accum)
    out = trainer.run(jax.random.PRNGKey(0), args.steps, log_every=10)
    hist = out["history"]
    print(f"\nloss {hist[0]['loss']:.4f} → {hist[-1]['loss']:.4f} over "
          f"{len(hist)} steps; stragglers flagged: "
          f"{sum(h['straggler'] for h in hist)}")


if __name__ == "__main__":
    main()

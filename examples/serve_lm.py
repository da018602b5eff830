"""Batched serving with continuous batching (deliverable b).

Model inference inside the system "avoids data extraction" (paper §6.3.2);
this driver serves a small LM with a continuously-batched decode loop:
requests of different lengths share fixed decode slots, finished sequences
immediately release their slot to the queue.

    PYTHONPATH=src python examples/serve_lm.py --requests 8 --slots 4
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.nn.model import LM
from repro.serving import Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=True)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    eng = ServingEngine(lm, params, max_len=args.max_len,
                        batch_slots=args.slots,
                        temperature=args.temperature)
    rng = np.random.RandomState(0)
    for uid in range(args.requests):
        plen = int(rng.randint(2, 10))
        eng.submit(Request(uid, rng.randint(0, cfg.vocab, plen)
                           .astype(np.int32),
                           max_new_tokens=args.max_new))
    t0 = time.perf_counter()
    done = eng.run_to_completion()
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in done)
    print(f"served {len(done)} requests / {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, {args.slots} slots)")
    for r in sorted(done, key=lambda r: r.uid)[:4]:
        print(f"  req {r.uid}: prompt {list(r.prompt)} → {r.generated}")


if __name__ == "__main__":
    main()

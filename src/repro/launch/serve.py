"""Production serving launcher (reduced configs runnable on CPU).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_8b --reduced
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
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=64)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    eng = ServingEngine(lm, params, max_len=args.max_len,
                        batch_slots=args.slots)
    rng = np.random.RandomState(0)
    for uid in range(args.requests):
        eng.submit(Request(uid,
                           rng.randint(0, cfg.vocab,
                                       int(rng.randint(2, 8)))
                           .astype(np.int32),
                           max_new_tokens=args.max_new))
    t0 = time.perf_counter()
    done = eng.run_to_completion()
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in done)
    print(f"{cfg.name}: {len(done)} requests, {total} tokens, "
          f"{total / dt:.1f} tok/s")


if __name__ == "__main__":
    main()

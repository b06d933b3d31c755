"""Serving launcher: continuous batching with k-relaxed priority admission.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_1_7b --reduced \
      --requests 16 --slots 4 --k 4
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--frontends", type=int, default=4)
    ap.add_argument("--k", type=int, default=4,
                    help="hybrid k-priority publication threshold (rho = frontends*k)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import numpy as np

    from repro.configs import get_config, get_reduced
    from repro.models import materialize, model_p
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if not cfg.supports_decode():
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    params = materialize(jax.random.PRNGKey(args.seed), model_p(cfg))
    eng = ServeEngine(cfg, params, slots=args.slots, max_len=args.max_len,
                      frontends=args.frontends, k=args.k)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        req = Request(
            rid=i,
            tokens=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new=args.max_new,
            priority=float(rng.integers(0, 4)),   # SLA classes 0..3
        )
        eng.submit(req, frontend=i % args.frontends)
    eng.flush_frontends()
    done = eng.run()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s) | admission order: {eng.admission_log}")


if __name__ == "__main__":
    main()

"""Serving driver: continuous batching with ticket-FIFO admission.

CPU-runnable with reduced configs::

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --reduced \
        --requests 12 --lanes 4 --max-new 8
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_checkout_cache
from repro.models.model import init_params
from repro.serve import ServeEngine


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--max-ctx", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_checkout_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step")

    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(cfg, params, lanes=args.lanes, max_ctx=args.max_ctx,
                      temperature=args.temperature, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    reqs = [eng.submit(rng.integers(1, cfg.vocab,
                                    size=int(rng.integers(4, 17))).tolist(),
                       max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    eng.run()
    dt = time.time() - t0
    tokens = sum(len(r.tokens_out) for r in reqs)
    stats = eng.stats()
    print(f"[serve] {len(reqs)} requests, {tokens} tokens in {dt:.2f}s "
          f"({tokens / dt:.1f} tok/s), {stats['steps']} engine steps")
    print(f"[serve] admission: grant_polls={stats['grant_polls']} "
          f"slot_polls={stats['slot_polls']} "
          f"long_term_entries={stats['long_term_entries']}")
    for r in reqs[:4]:
        print(f"  req#{r.ticket}: prompt[:4]={r.prompt[:4]} "
              f"-> out={r.tokens_out}")
    return {"requests": reqs, "stats": stats, "engine": eng}


if __name__ == "__main__":
    main()

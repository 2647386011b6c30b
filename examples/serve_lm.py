"""Serve a small model with batched requests through the jit'd decode engine.

  PYTHONPATH=src python examples/serve_lm.py --arch qwen3-1.7b
"""

import argparse
import time

import jax

from repro import configs
from repro.launch import compile_cache
from repro.models import model as M
from repro.serving.lm_engine import Engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    choices=list(configs.ARCHS))
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()
    compile_cache.configure()

    cfg = configs.get_smoke(args.arch)  # CPU-runnable reduced config
    params = M.init(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params)

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, 12), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    out = engine.generate(jax.random.PRNGKey(2), prompts,
                          max_new_tokens=args.max_new, temperature=0.8)
    jax.block_until_ready(out.tokens)
    dt = time.perf_counter() - t0
    print(f"{args.batch} requests x {args.max_new} tokens in {dt:.2f}s "
          f"({args.batch*args.max_new/dt:.1f} tok/s, includes compile)")
    for i in range(min(3, args.batch)):
        print(f"req{i}: {out.tokens[i].tolist()}")


if __name__ == "__main__":
    main()

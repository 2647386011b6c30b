"""Train an LM with the production Trainer (checkpoint/restart, data resume).

Default is a CPU-quick ~10M-param llama-style config; --full trains the
~100M-param config the brief describes (few hundred steps — use a beefier
host or be patient on CPU).  Kill it mid-run and re-launch with the same
--ckpt to watch fault-tolerant resume.

  PYTHONPATH=src python examples/train_lm.py --steps 60
  PYTHONPATH=src python examples/train_lm.py --full --steps 300
"""

import argparse
import dataclasses

import jax

from repro.configs import get_smoke
from repro.data import tokens
from repro.launch import compile_cache
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.training.train import Trainer, TrainerConfig

TINY = ModelConfig(
    name="llama-tiny-10m", family="dense", num_layers=4, d_model=256,
    n_heads=8, n_kv_heads=4, head_dim=32, d_ff=1024, vocab_size=8192,
)
FULL_100M = ModelConfig(
    name="llama-100m", family="dense", num_layers=12, d_model=768,
    n_heads=12, n_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32_768,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="~100M params")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    compile_cache.configure()

    cfg = FULL_100M if args.full else TINY
    print(f"model={cfg.name} params={cfg.param_count():,}")
    hp = adamw.Hparams(peak_lr=1e-3, warmup_steps=args.steps // 10,
                       total_steps=args.steps)
    data = tokens.for_config(cfg, args.batch, args.seq)
    trainer = Trainer(cfg, hp, data,
                      TrainerConfig(checkpoint_dir=args.ckpt,
                                    checkpoint_every=25),
                      jax.random.PRNGKey(0))
    if trainer.step:
        print(f"resumed from checkpoint at step {trainer.step}")

    def log(step, m):
        if step % 10 == 0:
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"lr {float(m['lr']):.2e}")

    final = trainer.run(args.steps - trainer.step, on_step=log)
    print("final:", final)


if __name__ == "__main__":
    main()

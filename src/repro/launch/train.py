"""Training CLI driver.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 200 --seq-len 256 --batch 8 [--reduced] [--ckpt-dir DIR]

On a real TPU slice this runs under the production mesh
(``make_production_mesh``); on this container it uses the local device.
"""
from __future__ import annotations

import argparse

from repro.config import TrainConfig
from repro.configs import get_config
from repro.data import DataConfig, batches
from repro.launch.compile_cache import enable_compile_cache
from repro.train import Trainer


def main() -> None:
    """CLI entry: train a (reduced) arch on Zipf token data, with
    optional periodic checkpointing via CheckpointManager."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                     optimizer=args.optimizer, grad_accum=args.grad_accum,
                     checkpoint_every=args.checkpoint_every
                     if args.ckpt_dir else 0)
    trainer = Trainer(cfg, tc, ckpt_dir=args.ckpt_dir)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    batch_size=args.batch)
    report = trainer.run(batches(dc), args.steps)
    print(f"steps={report.steps_done} loss {report.losses[0]:.3f} -> "
          f"{report.final_loss:.3f} retries={report.retries} "
          f"stragglers={report.straggler_steps}")


if __name__ == "__main__":
    main()

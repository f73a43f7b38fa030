"""Training launcher.

Smoke scale (this container):
  PYTHONPATH=src python -m repro.launch.train --arch trimkv-paper-4b \
      --smoke --steps 50

Production scale lowers the same train_step through the dry-run path;
on a real TPU slice the only difference is that `.compile()` output is
executed instead of analyzed.
"""
from __future__ import annotations

import argparse

from repro.configs import ARCH_IDS, TrainConfig, get_config, \
    get_smoke_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.train.trainer import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="trimkv-paper-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config runnable on CPU")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--capacity-M", type=int, default=32)
    ap.add_argument("--task", default="mixed",
                    choices=("copy", "arithmetic", "multisession",
                             "procedural", "mixed"))
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    train_cfg = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                            capacity_M=args.capacity_M,
                            total_steps=args.steps)
    tasks = (("copy", "arithmetic", "multisession", "procedural")
             if args.task == "mixed" else (args.task,))
    data_cfg = DataConfig(batch=args.batch, seq_len=args.seq, tasks=tasks)
    _, history = train_loop(cfg, train_cfg, data_cfg, steps=args.steps,
                            ckpt_path=args.ckpt)
    print(f"done: {len(history)} logged steps, "
          f"final loss {history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()

"""End-to-end training driver: train a (reduced) qwen3-family LM for a few
hundred steps with the full production stack — GeoFF-prefetched data
pipeline, pre-warmed compile, async checkpointing, straggler detection, and
a mid-run checkpoint/restart drill.

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--arch qwen3-1.7b]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.configs.registry import smoke_config
from repro.optim import AdamWConfig
from repro.train import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = smoke_config(args.arch).replace(d_model=128, num_heads=4,
                                          head_dim=32, d_ff=512)
    tcfg = TrainerConfig(
        seq_len=args.seq_len, global_batch=args.batch,
        total_steps=args.steps, checkpoint_every=50,
        checkpoint_dir=args.ckpt_dir,
        adamw=AdamWConfig(peak_lr=1e-3, warmup_steps=20,
                          total_steps=args.steps))
    tr = Trainer(cfg, tcfg)

    half = args.steps // 2
    print(f"training {args.arch} (reduced) for {half} steps...")
    tr.run(half)
    print(f"  step {tr.step}: loss={tr.metrics_log[-1]['loss']:.4f}")

    # ---- fault-tolerance drill: 'crash' and restart from the checkpoint ----
    print("simulating failure: dropping the live trainer, restarting from "
          "the latest checkpoint...")
    tr2 = Trainer(cfg, tcfg)
    tr2.run(args.steps - half)
    log = tr2.metrics_log

    first = np.mean([m["loss"] for m in log[:10]])
    last = np.mean([m["loss"] for m in log[-10:]])
    print(f"resumed at step {args.steps - half + tr2.step - len(log)}; "
          f"finished at step {tr2.step}")
    print(f"loss: {first:.4f} -> {last:.4f} "
          f"({'DECREASED' if last < first else 'did not decrease'})")
    print(f"stragglers detected: {len(tr2.stragglers)}")
    print(f"checkpoint stats: {tr2.ckpt.stats}")
    assert last < first, "loss should fall on the synthetic corpus"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

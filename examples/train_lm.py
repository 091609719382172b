"""End-to-end LM training driver with checkpoint/restart.

Trains a reduced-config assigned architecture for a few hundred steps on
the deterministic synthetic token pipeline, demonstrating the full
production loop: sharded train step, async checkpointing, and a simulated
failure + restart that resumes bit-identically.

Run:  PYTHONPATH=src python examples/train_lm.py --arch qwen3-0.6b \
          --steps 200
"""
import argparse
import tempfile
import time

import numpy as np

from repro.configs import get_smoke_config
from repro.data.pipeline import TokenDataset
from repro.launch.mesh import make_debug_mesh
from repro.optim.optimizer import AdamWConfig
from repro.runtime import enable_compile_cache
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step, then restart")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    mesh = make_debug_mesh()
    ds = TokenDataset(vocab=cfg.vocab, seq_len=64, global_batch=8, seed=0,
                      embed_dim=cfg.d_model if cfg.embed_input else None)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=ckpt_dir,
                             ckpt_every=max(10, args.steps // 10),
                             log_every=max(5, args.steps // 20))
        trainer = Trainer(cfg, mesh, ds,
                          AdamWConfig(lr=3e-3, warmup_steps=20,
                                      total_steps=args.steps), tcfg)
        fail_at = args.fail_at or args.steps // 2
        print(f"training {args.arch} (reduced) for {args.steps} steps; "
              f"injecting failure at step {fail_at}...")
        t0 = time.time()
        try:
            trainer.run(fail_at_step=fail_at)
        except RuntimeError as e:
            print(f"  !! {e} -- restarting from the latest checkpoint")
        # "restart": a fresh Trainer picks up the latest atomic ckpt
        trainer2 = Trainer(cfg, mesh, ds,
                           AdamWConfig(lr=3e-3, warmup_steps=20,
                                       total_steps=args.steps), tcfg)
        out = trainer2.run()
        dt = time.time() - t0
        for h in out["history"]:
            print(f"  step {h['step']:5d}  loss {h['loss']:.4f}")
        first, last = out["history"][0], out["history"][-1]
        print(f"\ndone in {dt:.1f}s; loss {first['loss']:.3f} -> "
              f"{last['loss']:.3f} (resumed across a simulated failure)")
        assert last["loss"] < first["loss"] + 1e-6
        assert all(np.isfinite(h["loss"]) for h in out["history"])


if __name__ == "__main__":
    main()

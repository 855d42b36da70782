"""End-to-end training driver: a ~100M-param qwen2-family model for a few
hundred steps on synthetic structured text, with checkpoint/restart and
loss-curve verification.

NOTE on this 1-core CPU container a step takes O(1 min) — use --steps 20
for a demo (checkpoints let you accumulate runs); on real hardware the same
driver runs the full config on the production mesh.  The CI-sized variant
is tests/test_train_substrate.py::test_trainer_loss_decreases_and_restarts.

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses
import shutil

from repro.configs import smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts_mod
from repro.train.trainer import Trainer, TrainerConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--fresh", action="store_true")
    args = ap.parse_args()
    if args.fresh:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    # ~100M params: qwen2 family, scaled-up smoke config
    cfg = dataclasses.replace(
        smoke_config("qwen2-0.5b"),
        n_layers=8, d_model=512, d_ff=2048, n_heads=8, n_kv_heads=4,
        head_dim=64, vocab=32768, logits_chunk=512, q_chunk=256)
    print(f"model: {cfg.n_params() / 1e6:.1f}M params")

    tc = TrainerConfig(
        total_steps=args.steps, ckpt_every=100, ckpt_dir=args.ckpt_dir,
        log_every=10,
        train=ts_mod.TrainConfig(
            microbatches=1,
            adamw=opt_mod.AdamWConfig(lr=1e-3, warmup_steps=30,
                                      total_steps=args.steps)))
    trainer = Trainer(cfg, tc, seq_len=256, global_batch=8)
    trainer.run(resume=not args.fresh)

    losses = [h["loss"] for h in trainer.history]
    if len(losses) >= 20:
        first, last = sum(losses[:10]) / 10, sum(losses[-10:]) / 10
        print(f"loss: {first:.3f} → {last:.3f} "
              f"({'LEARNING ✓' if last < first else 'no improvement ✗'})")


if __name__ == "__main__":
    main()

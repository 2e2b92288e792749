"""End-to-end LM pre-training driver (paper's GPT2 setup, reduced for CPU).

Demonstrates the full production path:
  * layer-parallel MGRIT training with buffer layers (App. B),
  * the adaptive indicator probe + automatic LP -> serial switch (§3.2.3),
  * periodic fault-tolerant checkpointing and resume.

Run:  PYTHONPATH=src python examples/lm_pretrain.py --steps 200
      (add --full for the paper-size 20-layer d=768 nanoGPT config)
"""
import argparse
import dataclasses
import shutil
import sys
import tempfile

sys.path.insert(0, "src")

from repro.configs import registry
from repro.configs.base import OptimizerConfig, ShapeConfig
from repro.configs.reduce import reduce_config
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--full", action="store_true",
                    help="paper-size GPT2 (20L, d=768) instead of reduced")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    rcfg = registry.get_config("gpt2_nanogpt")
    if not args.full:
        rcfg = reduce_config(rcfg, seq=64, batch=8)
        # keep the paper's buffer-layer structure in the reduction
        rcfg = dataclasses.replace(
            rcfg, mgrit=dataclasses.replace(
                rcfg.mgrit, n_open=1, n_close=1, fwd_iters=1, bwd_iters=1,
                check_every=50, enabled=True))
    rcfg = dataclasses.replace(
        rcfg,
        optimizer=OptimizerConfig(name="adamw", lr=3e-3, warmup_steps=20,
                                  total_steps=args.steps),
        shape=rcfg.shape if args.full else ShapeConfig(
            "lm", "train", 64, 8))

    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="lmckpt-")
    trainer = Trainer(rcfg, ckpt_dir=ckpt_dir, seed=0)
    print(f"params: {sum(x.size for x in __import__('jax').tree.leaves(trainer.params)):,}")
    report = trainer.train(args.steps, ckpt_every=max(args.steps // 4, 1),
                           log_every=25)

    print(f"\nsteps/sec: {report.steps_per_sec:.2f} (compile-free steps; "
          f"{report.compiles} compiles)")
    if report.switched_at is not None:
        print(f"adaptive controller switched LP->serial at step "
              f"{report.switched_at} (paper Fig. 4/5 behavior)")
    else:
        print("controller kept layer-parallel mode (indicator < 1)")
    print(f"loss: {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")

    # resume-from-checkpoint demonstration (fault tolerance)
    resumed = Trainer(rcfg, ckpt_dir=ckpt_dir, seed=0)
    print(f"resume check: restarted trainer resumes at step {resumed.step}")
    if not args.ckpt:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()

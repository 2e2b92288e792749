"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek_7b \
      --shape train_4k --steps 100 [--mesh host|single|multi] \
      [--ckpt DIR] [--serial] [--reduced]

``--mesh host`` (default) runs on the real local device(s) — use
``--reduced`` with it on CPU. ``single``/``multi`` build the production
meshes (requires the 512-device XLA flag; intended for real pods — on this
container use launch/dryrun.py instead, which only lowers).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh", choices=["host", "single", "multi"],
                    default="host")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--serial", action="store_true",
                    help="disable layer-parallel (exact serial baseline)")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-friendly)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default="", help="memmap token file")
    args = ap.parse_args(argv)

    if args.mesh == "multi":
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

    from repro.configs import registry
    from repro.configs.reduce import reduce_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_production_mesh
    from repro.train.trainer import Trainer

    enable_compile_cache()
    rcfg = registry.get_config(args.arch, args.shape)
    if args.reduced:
        rcfg = reduce_config(rcfg)
    if args.serial:
        rcfg = dataclasses.replace(
            rcfg, mgrit=dataclasses.replace(rcfg.mgrit, enabled=False))

    mesh = None
    if args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    trainer = Trainer(rcfg, mesh=mesh, ckpt_dir=args.ckpt, seed=args.seed,
                      data_path=args.data)
    report = trainer.train(args.steps, ckpt_every=args.ckpt_every,
                           log_every=10)
    print(f"done: {len(report.losses)} steps, "
          f"final loss {report.losses[-1]:.4f}, "
          f"{report.steps_per_sec:.2f} steps/s, "
          f"switched_at={report.switched_at}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness — one benchmark per paper table/figure.

  bench_convergence    Fig. 3/4   serial vs LP vs switched loss dynamics
  bench_indicator      Fig. 5     convergence-factor indicator
  bench_scaling        Fig. 6/7/8 strong scaling vs N / cf / L / P
  bench_dp_lp          Fig. 9     DP x LP split convexity
  bench_finetune_delta Table 1    fine-tune delta serial vs switched
  bench_buffer         Fig. 12    buffer layers
  bench_kernels        (ours)     Pallas kernels vs oracles
  bench_roofline       (ours)     dry-run roofline aggregation
  bench_serve          (ours)     continuous-batching serve engine
  bench_traffic        (ours)     Poisson-arrival goodput under overload
  bench_spec           (ours)     coarse-propagator speculative decoding

Prints ``name,us_per_call,derived`` CSV; ``--emit-json PATH`` also writes
the rows as JSON for the CI regression gate (benchmarks.check_regression).
A benchmark that raises still gets its ``_meta/<name>`` ERROR row and the
others still run, but the process then exits 1.
Usage: PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]
           [--emit-json BENCH_ci.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

sys.path.insert(0, "src")

from benchmarks.common import CSV  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ALL = ("kernels", "roofline", "perf_report", "scaling", "dp_lp", "serve",
       "traffic", "spec", "convergence", "indicator", "buffer",
       "finetune_delta")

FAST = ("kernels", "roofline", "perf_report", "scaling", "dp_lp", "serve",
        "traffic", "spec")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true",
                    help="skip the training-dynamics benchmarks")
    ap.add_argument("--emit-json", default="",
                    help="also write results to this JSON file")
    args = ap.parse_args(argv)
    enable_compile_cache()

    names = [n for n in ALL if not args.only or n in args.only.split(",")]
    if args.fast:
        names = [n for n in names if n in FAST]
    csv = CSV()
    failed = []
    for name in names:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run(csv)
            csv.add(f"_meta/{name}", (time.time() - t0) * 1e6, "ok")
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            csv.add(f"_meta/{name}", (time.time() - t0) * 1e6,
                    f"ERROR={type(e).__name__}")
    print("name,us_per_call,derived")
    csv.emit()
    if args.emit_json:
        payload = {n: {"us_per_call": us, "derived": derived}
                   for n, us, derived in csv.rows}
        with open(args.emit_json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
    if failed:
        print(f"benchmarks failed: {','.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

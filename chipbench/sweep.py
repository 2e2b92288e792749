#!/usr/bin/env python3
"""Find a serving cell's knee: run its traffic at several fixed rates, one
after another in one process, and print for each what the open-loop
driver saw. The knee is the highest rate at which the queue does not
grow: the queue at the window's close stays near its mean and every
request finishes within the drain limit.

  python3 chipbench/sweep.py --workload qwen3.serve_chat --seed <n> \
      --seconds 30 --rates 0.5,1,1.5,2,3

Each rate is a whole run of the cell (set-up included, from the compile
cache after the first), with the cell's comparison on a short sample.
One JSON line per rate on stdout; the rate chosen for the cell is written
into its traffic file by hand, as a number.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from chipbench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench_run.enable_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        t = time.perf_counter()
        res, run = bench_run.execute(
            args.workload, args.seed, args.seconds, False,
            t_start=t, overrides={"traffic": {"rate_per_s": rate,
                                              "check_tokens": 1}})
        d = run.data
        row = {"rate_per_s": rate, "requests": run.attempted,
               "failed": run.failed, "correct": res["correct"],
               "setup_s": run.setup_s,
               **{k: d.get(k) for k in (
                   "ttft_p50_ms", "ttft_p90_ms", "tpot_p90_ms",
                   "serve_tokens_per_s", "queue_depth_mean", "queue_at_close",
                   "unfinished_at_close", "drain_s", "late_p90_ms",
                   "pages_in_use_mean", "pages_in_use_peak")}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

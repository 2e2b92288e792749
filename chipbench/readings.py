#!/usr/bin/env python3
"""The numbers that decide ``correct``, read over many seeds in one
process, to set a cell's limits (``chipbench/limits/<cell>.json``).

  python3 chipbench/readings.py --workload <cell> --seeds <a,b,...> \
      --seconds <s> [--control 1] [--fault <name>]

Each seed is one run of the cell as ``run.py`` makes it, on the machine's
TPU, with a short window. Every run prints its compared numbers; the last
line is one JSON object: per number, the largest and the least reading
over the seeds, of the program and, with ``--control 1``, of the control
(the program's readings of a control run are sound readings too).
``--fault`` plants one of ``chipbench/faults.py``'s faults first. The
benchmark's measured runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import faults, run
    run.enable_cache()
    if args.fault:
        plant = getattr(faults, args.fault)
        if args.fault == "token_altered":
            cell = run.find_cell(run.load_bench(), args.workload)
            cfg = json.loads((ROOT / run._config_entry(
                run.load_bench(), cell)["file"]).read_text())
            plant(setattr, cfg["program"]["model"]["vocab_size"])
        else:
            plant(setattr)
    readings = {"program": {}, "control": {}}
    for seed in (int(s) for s in args.seeds.split(",")):
        res, r = run.execute(args.workload, seed, args.seconds, False,
                             bool(args.control), t_start=time.perf_counter())
        checks = {k: v["value"] for k, v in res["checks"].items()}
        side = "control" if args.control else "program"
        got = {side: checks}
        if r.program_readings is not None:
            got["program"] = r.program_readings
        for note in r.notes:
            print(f"seed {seed}: {note}", file=sys.stderr)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "reference_s": r.data.get("reference_s"),
                          "setup_s": r.setup_s, **got}), flush=True)
        for s, vals in got.items():
            for k, v in vals.items():
                readings[s].setdefault(k, []).append(v)
    print(json.dumps({s: {k: {"max": max(v), "min": min(v), "n": len(v)}
                          for k, v in vals.items()}
                      for s, vals in readings.items() if vals}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

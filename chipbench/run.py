#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell names a configuration (``chipbench/configs/<config>.json``) and
a traffic mix (``chipbench/traffic/<mix>.json``, whose ``driver`` names
``chipbench/drivers/<driver>.py``); its limits are in
``chipbench/limits/<cell>.json`` and every metric is read by
``chipbench/metrics/<metric>.py``. With ``--trace 0`` the result holds the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from
a profiled segment. Every run checks what the timed path produced against
the plain reference and prints each compared number beside its limit, as
the last lines on stderr and under ``checks`` in the result. The last line
on stdout is the result as one JSON object.

It runs on the machine's TPU and nowhere else: without one, or with fewer
chips than the cell asks for, it exits 2 and prints no result. ``--control
1`` (not part of a measured run) puts the control in the program's place
for the comparison: the reference one precision step down, read against
the same limits, so that ``correct`` comes out false; the program's own
readings of that run are kept under ``program``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def fail(msg: str, code: int = 2):
    print(f"chipbench: {msg}", file=sys.stderr)
    sys.exit(code)


class Run:
    """One run: the cell's files, the devices, and what the driver and the
    metric readers fill in."""

    def __init__(self, bench, cell, seed, seconds, trace, control, devices,
                 peak, t_start, overrides=None):
        self.bench, self.cell = bench, cell
        self.workload = cell["name"]
        self.config = json.loads(
            (ROOT / _config_entry(bench, cell)["file"]).read_text())
        self.traffic = json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
        self.limits = json.loads(
            (BENCH / "limits" / f"{cell['name']}.json").read_text())
        for key, value in (overrides or {}).items():
            _merge(getattr(self, key), value)
        self.seed, self.seconds = seed, seconds
        self.trace, self.control = trace, control
        self.devices, self.chips, self.peak = devices, cell["chips"], peak
        self.t_start = t_start
        self.setup_s = None
        self.attempted = self.failed = 0
        self.data = {}
        self.checks = []
        self.notes = []
        self.trace_data = None
        self.program_readings = None
        self.memory_peak_bytes = None

    def setup_done(self):
        self.setup_s = time.perf_counter() - self.t_start

    def check(self, name, value, limit):
        self.checks.append((name, float(value), float(limit)))

    def compare(self, readings, control=None):
        """Check each reading against the cell's limit. In a control run the
        control's readings stand in the program's place, and the program's
        are kept beside them."""
        if self.control:
            self.program_readings, readings = readings, control
        for name, value in readings.items():
            self.check(name, value, self.limits[name])

    def note(self, text):
        self.notes.append(text)

    def read_memory(self):
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        self.memory_peak_bytes = max((p for p in peaks if p is not None),
                                     default=None)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim in
                                         self.checks)


def _merge(dst: dict, src: dict):
    """Deep-merge ``src`` into ``dst`` (tests shrink a cell this way)."""
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v


def _config_entry(bench, cell):
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            return c
    raise KeyError(f"configuration {cell['config']!r} not in BENCHMARK.json")


def load_bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench, name):
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"workload {name!r} not in BENCHMARK.json")


def metrics_for(bench, cell, trace: bool):
    """The cell's end-to-end metrics (``--trace 0``) or per-layer ones."""
    names = [m["name"] for m in bench["end_to_end"]
             if "workloads" not in m or cell["name"] in m["workloads"]]
    if not trace:
        return [m for m in bench["end_to_end"] if m["name"] in names]
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def reader(m):
    """The reader of the metric entry ``m``, whose ``META`` has to agree
    with the entry."""
    from chipbench import common
    mod = common.load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "chipbench_metric_" + m["name"].replace(
                                 ".", "_"))
    for key in ("layer", "unit", "source", "moves"):
        if key in mod.META and mod.META[key] != m.get(key):
            raise RuntimeError(f"metric {m['name']}: {key} "
                               f"{mod.META[key]!r} in its reader, "
                               f"{m.get(key)!r} in BENCHMARK.json")
    return mod


def read_metrics(run, entries):
    out = {}
    for m in entries:
        value = reader(m).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def tpu_devices(chips: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no devices: {str(e).splitlines()[0]}")
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's devices are {devs[0].platform!r} "
             f"({devs[0].device_kind}) x{len(devs)}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips; JAX has {len(devs)} "
             f"({devs[0].device_kind})")
    return devs[:chips]


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept (set-up then loads what the first run compiled)."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)      # JAX writes nothing into a missing one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def execute(workload, seed, seconds, trace, control=False, devices=None,
            t_start=None, overrides=None, peak=None):
    """Run one cell on ``devices`` (the TPU's unless given) and return
    (result dict, Run). ``overrides`` deep-merges into the cell's
    ``config``, ``traffic`` and ``limits``, and ``peak`` stands in for the
    peaks table (the tests' small sizes on the CPU)."""
    from chipbench import common
    bench = load_bench()
    cell = find_cell(bench, workload)
    if devices is None:
        devices = tpu_devices(cell["chips"])
    kind = devices[0].device_kind
    if peak is None:
        peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
        if kind not in peaks:
            fail(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
        peak = peaks[kind]
    run = Run(bench, cell, seed, seconds, trace, control, devices, peak,
              T_START if t_start is None else t_start, overrides)
    driver = common.load_module(
        BENCH / "drivers" / f"{run.traffic['driver']}.py",
        "chipbench_driver_" + run.traffic["driver"])
    driver.run(run)
    gc.collect()
    entries = metrics_for(bench, cell, trace)
    metrics = read_metrics(run, entries)
    missing = [m["name"] for m in entries if m["name"] not in metrics]
    if missing:
        raise RuntimeError(f"nothing to read for {missing}")
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        t = run.trace_data
        if t is None:
            raise RuntimeError("the traced run profiled no segment")
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.window_s()
        result["breakdown"] = {"device_ops": t.top_ops(10),
                               "idle_gaps": t.idle_gaps(10)}
    if run.program_readings is not None:
        result["program"] = run.program_readings
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in run.checks}
    return result, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a non-negative whole number")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is not beside the benchmark: no {ROOT}/src/repro")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    enable_cache()
    result, run = execute(args.workload, args.seed, args.seconds,
                          bool(args.trace), bool(args.control))
    for text in run.notes:
        print(f"chipbench: {text}", file=sys.stderr)
    d = result["device"]
    print(f"chipbench: {run.workload} on {d['platform']} {d['kind']} "
          f"x{d['count']}: correct={result['correct']}", file=sys.stderr)
    if run.program_readings is not None:
        print(f"chipbench: control run; the program read "
              f"{json.dumps(run.program_readings)}", file=sys.stderr)
    for name, v, lim in run.checks:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

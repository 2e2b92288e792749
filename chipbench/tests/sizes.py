"""Small sizes of the benchmark's cells for the CPU tests: the same
drivers, program paths and comparisons, at widths a test run can hold."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TRAIN = {
    "config": {"program": {
        "model": {"n_layers": 8, "vocab_size": 8192, "max_seq_len": 128},
        "mgrit": {"pad_to": 4}}},
    "traffic": {"global_batch": 2, "seq_len": 128, "trace_steps": 2},
}

SERVE = {
    "config": {
        "program": {"model": {"n_layers": 3, "d_ff": 1024,
                              "vocab_size": 32768}},
        "deployment_settings": {"max_batch": 4, "page_size": 8,
                                "max_len": 128}},
    "traffic": {"rate_per_s": 4.0,
                "prompt_len": {"median": 24, "min": 8, "max": 64},
                "output_len": {"median": 8, "min": 4, "max": 32},
                "trace_seconds": 1, "check_tokens": 40,
                "drain_limit_s": 30},
}

# The control's float8 error grows with depth: at 3 layers its widest gap
# stays under the cell's limit, at 12 (of the configuration's 28) and over
# every served token it is well over it, as on the chip at full size (a
# widest gap over a few requests' tokens swings from run to run).
SERVE_DEEP = copy.deepcopy(SERVE)
SERVE_DEEP["config"]["program"]["model"]["n_layers"] = 12
SERVE_DEEP["traffic"]["check_tokens"] = 1000

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def execute(workload, sizes, seed=2 ** 31 + 7, seconds=5.0, devices=None,
            **kw):
    """One run of a cell at the given small sizes on the CPU."""
    import jax
    from chipbench import run
    return run.execute(workload, seed, seconds, False,
                       devices=devices or jax.devices()[:1],
                       overrides=sizes, peak=PEAK, **kw)

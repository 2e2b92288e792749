"""The command refuses to measure anything but a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import sizes

ARGS = ["--workload", "gpt2.train_lp", "--seed", str(2 ** 31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(sizes.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr and "cpu" in out.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(sizes.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(sizes.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert Path(tmp_path / "src").exists() is False

"""Pieces shared by the drivers: compile counting, the comparison that
decides ``correct``, a profiler segment, and loading by name."""
from __future__ import annotations

import importlib.util
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent


def load_module(path: Path, name: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reference(name: str):
    return load_module(BENCH / "references" / f"{name}.py",
                       f"chipbench_ref_{name}")


class CompileCounter:
    """Counts JAX lowerings (one per jit cache miss) from ``start()`` on."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False

        def listen(name, *_a, **_k):
            if self.armed and name == self.EVENT:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)

    def start(self):
        self.count, self.armed = 0, True

    def stop(self) -> int:
        self.armed = False
        return self.count


def rel_gap(got: float, want: float, floor: float) -> float:
    return abs(got - want) / max(abs(want), floor)


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   keys: List[str]) -> (float, str):
    """Worst leaf: |got - want| over the larger of the reference's norm of
    that leaf and of the median leaf."""
    med = float(np.median([want[k] for k in keys]))
    gaps = {k: rel_gap(got[k], want[k], med) for k in keys}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def counted_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's."""
    med = float(np.median(list(ref_grad.values())))
    return sorted(k for k, v in ref_grad.items() if v >= 1e-3 * med)


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile, linear between closest ranks (numpy's)."""
    v = [x for x in values if x is not None and math.isfinite(x)]
    return float(np.percentile(v, q)) if v else None


class Profile:
    """A ``jax.profiler`` trace of one segment into a fresh directory under
    ``TMPDIR``; ``stop()`` reduces it and removes the directory."""

    def __init__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()

    def stop(self, devices, window_span: str):
        """The reduced trace. A trace that cannot be read raises: the run
        then fails rather than leave its per-layer metrics out."""
        import jax
        from chipbench import trace as trace_mod
        jax.profiler.stop_trace()
        try:
            paths = list(Path(self.dir).rglob("*.trace.json.gz"))
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return trace_mod.load(str(paths[0]), [d.id for d in devices],
                                  window_span)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


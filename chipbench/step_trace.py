"""Readings of the trainer's own scopes and spans in a profiled segment.

The program names the phases of its training step with ``jax.named_scope``
(``trunk_forward``, ``trunk_adjoint``, ``mgrit_coarse``,
``trunk_param_grads``, ``optimizer_update``) and each step on the host with
``jax.profiler.TraceAnnotation`` spans (``train.step`` around
``train.input``, ``train.h2d``, ``train.dispatch``, ``train.wait``, ...),
which land in the same trace as the device's ops, on one clock.

The program declares these names in ``repro.obs.profile`` (``TRAIN_SCOPES``,
``TRAIN_SPANS``). Where it declares them, a scope or span that finds
nothing in the trace reads None, and the traced run then fails ("nothing to
read"). A program from before it declared them has no such scope or span:
it spends 0.0 ms under them, which is what it reads, since ``run.py`` asks
a number of every reader a cell lists.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple


def program_names_its_step() -> bool:
    """Whether the program being measured declares the scopes and spans of
    its training step (``repro.obs.profile.TRAIN_SCOPES``)."""
    try:
        from repro.obs import profile
    except ImportError:
        return False
    return hasattr(profile, "TRAIN_SCOPES")


def scope_ms_per_step(run, scope: str):
    """Device ms per step of the ops under ``scope``, summed over chips."""
    t = run.trace_data
    if t is None:
        return None
    if not program_names_its_step():
        return 0.0
    spent = t.scope_s(scope)
    if spent <= 0:
        return None
    return 1e3 * spent / run.data["trace_steps"]


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        elif e > s:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def idle_under_s(t, names) -> float:
    """Device-idle time in the window (the holes in each device's busy
    union) that falls under a host span named in ``names``, mean over the
    devices."""
    lo, hi = t.window
    under = _union((max(s, lo), min(e, hi)) for s, e, name in t.spans
                   if name in names)
    total = 0
    for d in t.ops:
        edges = [lo]
        for s, e in t.busy_intervals(d):
            edges += [s, e]
        edges.append(hi)
        for a, b in zip(edges[::2], edges[1::2], strict=True):
            for s, e in under:
                total += max(0, min(b, e) - max(a, s))
    return total * 1e-9 / t.n_devices


def idle_under_ms_per_step(run, names):
    t = run.trace_data
    if t is None:
        return None
    if not program_names_its_step():
        return 0.0
    if not any(name in names for _, _, name in t.spans):
        return None
    return 1e3 * idle_under_s(t, names) / run.data["trace_steps"]

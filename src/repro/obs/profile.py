"""Compile-event counters and opt-in ``jax.profiler`` span hooks.

**Compile counters** promote the technique ``tests/test_serve_trace.py``
proves in tests into production: a jitted function's *Python body* runs
once per XLA trace, so wrapping the pre-jit callable with
:func:`count_traces` counts compilations exactly — zero cost on cached
calls beyond one dict increment at trace time. Every jitted serve
callable (backend step/verify, draft prefill/wave, dense decode) wraps
itself into its backend's ``compile_counts`` dict, and the trainer's
steps and probe into ``Trainer.compile_counts``;
:func:`compiles_per_callable` is the derived gauge the registry exposes
(``engine.compiles_per_callable``) — a recompile leak shows up as this
number creeping above the expected O(log max_len) bucket count.

**The attention path counter** :data:`ATTENTION_PATHS` counts, at trace
time, which core each attention call took (fused kernel, dense or
chunked), so a run can show that every layer of a step took the kernel.

**Profiler spans** are opt-in for serving (``REPRO_PROFILE=1`` or an
explicit flag) and always on in the trainer (an explicit ``True``):
:func:`span_factory` returns a ``name -> context manager`` callable that
is a shared no-op ``nullcontext`` when disabled (nothing allocated per
call) and ``jax.profiler.TraceAnnotation`` when enabled, so the jitted
prefill/decode/verify dispatches show up named in a ``jax.profiler``
/ TensorBoard / Perfetto device trace.

The module itself imports neither jax nor numpy (jax loads lazily
inside the enabled-spans path only), keeping the obs package importable
in the dependency-free lint job.
"""
from __future__ import annotations

import contextlib
import os
from typing import Callable, Dict

_NULL = contextlib.nullcontext()

# What the training step names (docs/observability.md, "Training"): the
# device scopes of its phases, and the host spans of each step in the
# order they open (``train.probe`` and ``train.ckpt`` only when they run).
TRAIN_SCOPES = ("trunk_forward", "trunk_adjoint", "mgrit_coarse",
                "trunk_param_grads", "optimizer_update", "mgrit_probe")
TRAIN_SPANS = ("train.step", "train.input", "train.h2d", "train.probe",
               "train.dispatch", "train.wait", "train.ckpt")


# Attention calls by the core they took (``models.attention._attention_path``),
# counted when a call is traced: each traced call site once (a layer under
# vmap or scan once), however often the program runs. Read a difference
# around a trace.
ATTENTION_PATHS: Dict[str, int] = {"fused": 0, "dense": 0, "chunked": 0}


def count_attention(path: str) -> None:
    ATTENTION_PATHS[path] += 1


def count_traces(name: str, fn: Callable, counts: Dict[str, int]) \
        -> Callable:
    """Wrap a pre-jit callable so each XLA trace of it increments
    ``counts[name]`` (the body only runs when jit traces). The wrapper
    keeps ``fn``'s name, so the jitted program is named as without it."""
    counts.setdefault(name, 0)

    def traced(*args):
        counts[name] = counts.get(name, 0) + 1
        return fn(*args)
    traced.__name__ = traced.__qualname__ = getattr(fn, "__name__", name)
    return traced


def compiles_per_callable(counts: Dict[str, int]) -> float:
    """Mean traces per registered jitted callable (0 before any jit)."""
    if not counts:
        return 0.0
    return sum(counts.values()) / len(counts)


def spans_enabled(flag=None) -> bool:
    """Profiler spans are opt-in: an explicit flag wins, else the
    ``REPRO_PROFILE=1`` environment switch."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_PROFILE", "0") == "1"


def span_factory(enabled: bool) -> Callable:
    """``name -> context manager`` for annotating host dispatch regions.
    Disabled: one shared reusable nullcontext (no per-call allocation).
    Enabled: ``jax.profiler.TraceAnnotation`` (imported lazily here —
    the only jax touch in this package)."""
    if not enabled:
        return lambda name: _NULL
    import jax

    return lambda name: jax.profiler.TraceAnnotation(name)

"""Faults planted underneath the timed path, by name: each breaks the
program the way a wrong optimisation could, and ``correct`` has to come
out false. The tests plant them at small sizes; ``readings.py --fault``
plants one at a cell's own size on the chip.

Training: ``state_unchanged`` (a step that returns its state as it came),
``half_batch`` (half of the batch left out, the mean over the rest).
Serving: ``token_altered`` (a decoded token changed where it is
produced), ``cache_unchanged`` (a serve step that returns the cache as it
came).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _train_step(setattr_, wrap):
    from repro.train.trainer import Trainer
    orig = Trainer._step_fn

    def step_fn(self, mode):
        return wrap(orig(self, mode))
    setattr_(Trainer, "_step_fn", step_fn)


def _serve_call(setattr_, wrap, name):
    from repro.serve.cache import CacheBackend
    orig = getattr(CacheBackend, name)

    def call(self, state, slots, tokens, *a):
        return wrap(lambda *x: orig(self, *x), state, slots, tokens, *a)
    setattr_(CacheBackend, name, call)


def state_unchanged(setattr_):
    def wrap(fn):
        def step(p, o, b):
            copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
            return p, o, fn(copy(p), copy(o), b)[2]
        return step
    _train_step(setattr_, wrap)


def half_batch(setattr_):
    def wrap(fn):
        return lambda p, o, b: fn(p, o, jax.tree.map(
            lambda a: a[: a.shape[0] // 2], b))
    _train_step(setattr_, wrap)


def token_altered(setattr_, vocab):
    def wrap(orig, state, slots, tokens):
        state, nxt = orig(state, slots, tokens)
        return state, (nxt + 1) % vocab
    _serve_call(setattr_, wrap, "step")


def cache_unchanged(setattr_):
    """Planted in the compiled serve step itself, before the engine is
    built: the step hands back the pools it was given, so it keeps no
    copy beside them."""
    from repro.launch import steps
    orig = steps.make_paged_serve_fn

    def make(*a, **kw):
        fn = orig(*a, **kw)

        def step(params, state, *rest):
            nxt, _ = fn(params, state, *rest)
            return nxt, state
        return step
    setattr_(steps, "make_paged_serve_fn", make)

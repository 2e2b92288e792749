"""Optimizers (AdamW / SGD+momentum) with schedules and global-norm clipping.

Pure pytree implementation (no optax dependency). Optimizer state inherits
the parameter sharding, so FSDP-sharded params get ZeRO-sharded moments for
free. ``moment_dtype`` lets very large models (grok-1) keep m/v in bf16 to
fit the single-pod HBM budget (see EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import OptimizerConfig


def lr_at(cfg: OptimizerConfig, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "cosine":
        t = jnp.clip((step - cfg.warmup_steps)
                     / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                     0.0, 1.0)
        decay = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
        decay = 0.1 + 0.9 * decay
    elif cfg.schedule == "linear":
        t = jnp.clip((step - cfg.warmup_steps)
                     / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                     0.0, 1.0)
        decay = 1.0 - 0.9 * t
    else:
        decay = 1.0
    return cfg.lr * warm * decay


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(
        g.dtype), grads), gn


def init_opt_state(cfg: OptimizerConfig, params, moment_dtype=None):
    if moment_dtype is None:
        moment_dtype = jnp.dtype(cfg.moment_dtype)
    zeros = lambda p: jnp.zeros(p.shape, moment_dtype)
    state = {"step": jnp.zeros((), jnp.int32)}
    if cfg.name == "adamw" or cfg.name == "adam":
        state["m"] = jax.tree.map(zeros, params)
        state["v"] = jax.tree.map(zeros, params)
    elif cfg.name == "sgd":
        state["m"] = jax.tree.map(zeros, params)
    else:
        raise ValueError(cfg.name)
    # mixed precision: bf16 stored params keep an fp32 master copy here
    if any(p.dtype == jnp.bfloat16 for p in jax.tree.leaves(params)):
        state["master"] = jax.tree.map(
            lambda p: p.astype(jnp.float32), params)
    return state


def _freeze_structural(params, grads):
    """Zero the gradients of structural (non-trainable) leaves: the 0/1
    layer gates of the padded ParallelNet. They must neither update nor
    weight-decay."""
    def one(path, g):
        keys = [getattr(k, "key", None) for k in path]
        if keys and keys[-1] == "gate":
            return jnp.zeros_like(g)
        return g
    frozen = jax.tree_util.tree_map_with_path(one, grads)

    def mask(path, p, new_p):
        keys = [getattr(k, "key", None) for k in path]
        return p if (keys and keys[-1] == "gate") else new_p
    return frozen, mask


@jax.named_scope("optimizer_update")
def apply_updates(cfg: OptimizerConfig, params, grads, state
                  ) -> Tuple[Any, Any, Any]:
    """Returns (new_params, new_state, metrics)."""
    grads, _mask = _freeze_structural(params, grads)
    grads, gn = clip_by_global_norm(grads, cfg.grad_clip)
    step = state["step"] + 1
    lr = lr_at(cfg, step)
    mdt = jax.tree.leaves(state["m"])[0].dtype
    stored = params
    if "master" in state:
        params = state["master"]     # update in fp32, cast back at the end

    if cfg.name in ("adamw", "adam"):
        b1, b2 = cfg.beta1, cfg.beta2
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m2 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v2 = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
            u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + cfg.eps)
            if cfg.name == "adamw":
                u = u + cfg.weight_decay * p.astype(jnp.float32)
            p2 = p.astype(jnp.float32) - lr * u
            return p2.astype(p.dtype), m2.astype(mdt), v2.astype(mdt)

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        new_params = jax.tree.map(lambda t: t[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda t: t[2], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_state = {"step": step, "m": new_m, "v": new_v}
    else:  # sgd + momentum
        def upd(p, g, m):
            m2 = 0.9 * m.astype(jnp.float32) + g.astype(jnp.float32)
            p2 = p.astype(jnp.float32) - lr * m2
            return p2.astype(p.dtype), m2.astype(mdt)

        out = jax.tree.map(upd, params, grads, state["m"])
        new_params = jax.tree.map(lambda t: t[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_state = {"step": step, "m": new_m}

    # structural leaves (layer gates) pass through untouched (no decay)
    new_params = jax.tree_util.tree_map_with_path(
        lambda path, p, np_: _mask(path, p, np_), params, new_params)

    if "master" in state:
        new_state["master"] = new_params
        new_params = jax.tree.map(
            lambda p, s: p.astype(s.dtype), new_params, stored)

    return new_params, new_state, {"grad_norm": gn, "lr": lr}

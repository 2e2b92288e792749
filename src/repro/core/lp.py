"""LayerParallelNet: the paper's ParallelNet as a composable JAX module.

``lp_forward`` evaluates the neural-ODE trunk Z_{n+1} = Z_n + h*gate_n*F_n(Z_n)
with either an exact serial solve (fwd_iters=0) or `fwd_iters` MGRIT V-cycles
(inexact, layer-parallel). Its custom VJP runs the *adjoint* equation
(paper Eq. 4 right) through the same MGRIT solver with an independent
`bwd_iters` count — reproducing the paper's inexact biased gradients with
serial-forward/parallel-backward combinations (Table 3's dashes).

Everything inside the trunk is stacked over the layer (time) axis, so the
logical "layers" axis shards the solve over the mesh's "model" axis.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MGRITConfig, ModelConfig
from repro.core import mgrit
from repro.models.blocks import block_F

Extra = Dict[str, Any]  # differentiable per-call inputs: rope cos/sin, xa


@dataclasses.dataclass(frozen=True)
class LPStatic:
    cfg: ModelConfig
    mgrit: MGRITConfig
    kind: str               # block kind: attn_mlp | attn_moe | encdec_dec | mamba1 | mamba2
    causal: bool = True
    use_pallas: bool = False
    znames: Tuple[Optional[str], ...] = ("batch", None, None)

    def spec(self, iters: int) -> mgrit.MGRITSpec:
        return mgrit.MGRITSpec(cf=self.mgrit.cf, levels=self.mgrit.levels,
                               iters=iters, h=self.mgrit.h, shard=True,
                               shard_levels=self.mgrit.shard_levels,
                               znames=self.znames)


def eval_F(static: LPStatic, params, z, extra: Extra):
    """The ODE right-hand side F(t_n, Z) of paper Eq. 1/2."""
    f, _ = block_F(params, z, static.cfg, kind=static.kind,
                   causal=static.causal, positions=None,
                   rope=extra.get("rope"), xa=extra.get("xa"),
                   use_pallas=static.use_pallas)
    return f


def make_fwd_step(static: LPStatic, extra: Extra) -> mgrit.StepFn:
    """Phi(z) = z + h * gate * F(z). `slot` = {"params", "gate"}."""
    def step(slot, z, h):
        f = eval_F(static, slot["params"], z, extra)
        return z + (jnp.asarray(h, z.dtype) * slot["gate"].astype(z.dtype)) * f
    return step


def make_adj_step(static: LPStatic, extra: Extra) -> mgrit.StepFn:
    """Adjoint propagator Psi(lam) = lam + h*gate*(dF/dZ)^T lam, evaluated at
    the stored forward state. `slot` = {"params", "gate", "z"}."""
    def step(slot, lam, h):
        _, vjp_fn = jax.vjp(
            lambda z: eval_F(static, slot["params"], z, extra), slot["z"])
        (dz,) = vjp_fn(lam)
        return lam + (jnp.asarray(h, lam.dtype)
                      * slot["gate"].astype(lam.dtype)) * dz
    return step


# ---------------------------------------------------------------------------
# Solves
# ---------------------------------------------------------------------------


@jax.named_scope("trunk_forward")
def _forward_solve(static: LPStatic, stacked, z0, extra, iters: int):
    step = make_fwd_step(static, extra)
    if iters <= 0:
        states, zT = mgrit.serial_solve(step, stacked, z0, static.mgrit.h)
        norms = jnp.zeros((1,), jnp.float32)
    else:
        states, zT, norms = mgrit.mgrit_solve(step, stacked, z0,
                                              static.spec(iters))
    return states, zT, norms


def _adjoint_solve(static: LPStatic, stacked, states, lamN, extra,
                   iters: int):
    """Solve the adjoint backward from lam_N. Returns (rev_lam, lam0, norms)
    with rev_lam[n] = lambda_{n+1} (the multiplier hitting layer n's output).

    With V-cycles (``iters > 0``) the solve is named ``trunk_adjoint`` in the
    trace; the serial sweep at ``iters=0`` is the plain backward, unnamed."""
    rev = lambda a: jnp.flip(a, axis=0)
    scope = jax.named_scope("trunk_adjoint") if iters > 0 \
        else contextlib.nullcontext()
    with scope:
        adj_stacked = {
            "params": jax.tree.map(rev, stacked["params"]),
            "gate": rev(stacked["gate"]),
            "z": rev(states),
        }
        step = make_adj_step(static, extra)
        if iters <= 0:
            mu_states, mu_T = mgrit.serial_solve(step, adj_stacked, lamN,
                                                 static.mgrit.h)
            norms = jnp.zeros((1,), jnp.float32)
        else:
            mu_states, mu_T, norms = mgrit.mgrit_solve(
                step, adj_stacked, lamN, static.spec(iters))
        # mu_states[m] = lambda_{N-m}; layer n consumes lambda_{n+1} = mu[N-1-n]
        rev_lam = rev(mu_states)
    return rev_lam, mu_T, norms


@jax.named_scope("trunk_param_grads")
def _param_grads(static: LPStatic, stacked, states, rev_lam, extra):
    """Per-layer gradients g_theta_n = h*gate_n*(dF/dtheta_n)^T lambda_{n+1}
    and the summed extra-input cotangent — fully layer-parallel (vmap)."""
    h = static.mgrit.h

    def one(p, gate, z, lam_next):
        def f(pp, ee):
            return eval_F(static, pp, z, ee)
        _, vjp_fn = jax.vjp(f, p, extra)
        ct = (jnp.asarray(h, lam_next.dtype) * gate.astype(lam_next.dtype)) \
            * lam_next
        dp, de = vjp_fn(ct)
        return dp, de

    dps, des = jax.vmap(one)(stacked["params"], stacked["gate"], states,
                             rev_lam)
    d_extra = jax.tree.map(lambda a: jnp.sum(a, axis=0), des)
    return dps, d_extra


# ---------------------------------------------------------------------------
# custom_vjp binding
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def lp_forward(static: LPStatic, stacked, z0, extra: Extra):
    """Returns (zT, fwd_residual_norms). Gradient is the MGRIT adjoint."""
    _, zT, norms = _forward_solve(static, stacked, z0, extra,
                                  static.mgrit.fwd_iters)
    return zT, norms


def _lp_fwd(static, stacked, z0, extra):
    states, zT, norms = _forward_solve(static, stacked, z0, extra,
                                       static.mgrit.fwd_iters)
    return (zT, norms), (stacked, states, extra)


def _lp_bwd(static, res, cts):
    stacked, states, extra = res
    ct_zT, _ct_norms = cts
    # the adjoint runs in the trunk's compute dtype (lambda ~ z)
    ct_zT = ct_zT.astype(states.dtype)
    rev_lam, lam0, _ = _adjoint_solve(static, stacked, states, ct_zT, extra,
                                      static.mgrit.bwd_iters)
    dps, d_extra = _param_grads(static, stacked, states, rev_lam, extra)
    d_stacked = {"params": dps,
                 "gate": jnp.zeros_like(stacked["gate"]),
                 }
    return d_stacked, lam0, d_extra


lp_forward.defvjp(_lp_fwd, _lp_bwd)


# ---------------------------------------------------------------------------
# Diagnostics for the adaptive controller (paper 3.2.3, Fig. 5)
# ---------------------------------------------------------------------------


def lp_diagnose(static: LPStatic, stacked, z0, extra, seed_ct,
                fwd_iters: int, bwd_iters: int):
    """Run forward + adjoint MGRIT with explicit iteration counts and return
    both residual-norm sequences (the controller doubles the counts to
    estimate the convergence factor of the final iteration)."""
    states, zT, fwd_norms = _forward_solve(static, stacked, z0, extra,
                                           max(fwd_iters, 1))
    lamN = seed_ct(zT)
    _, _, bwd_norms = _adjoint_solve(static, stacked, states, lamN, extra,
                                     max(bwd_iters, 1))
    return fwd_norms, bwd_norms


# ---------------------------------------------------------------------------
# Stacked-layer utilities (padding, gates, buffers)
# ---------------------------------------------------------------------------


def pad_depth(n_real: int, pad_to: int) -> int:
    if pad_to <= 0:
        return n_real
    return ((n_real + pad_to - 1) // pad_to) * pad_to


def make_gates(n_real: int, n_padded: int, dtype=jnp.float32):
    g = jnp.arange(n_padded) < n_real
    return g.astype(dtype)


def stack_init(init_fn, key, n: int):
    """vmap an init function over n layer keys -> stacked params (n, ...)."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)

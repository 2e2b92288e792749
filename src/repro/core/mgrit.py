"""MGRIT (multigrid-reduction-in-time) over the transformer layer dimension.

This is the paper's core algorithm (Fig. 2 / Appendix A), adapted from
MPI+GPU (TorchBraid) to JAX GSPMD:

  * the fine time grid of N layers is chunked into J = N/c_f coarse
    intervals; the J axis is the logical "layers" axis, sharded over the
    physical "model" mesh axis (the paper's layer distribution over ranks);
  * F-relaxation = vmap over J of a (c_f-1)-step lax.scan  -> fully parallel;
  * C-relaxation's cross-chunk shift lowers to collective-permute
    (the MPI halo exchange);
  * the FAS coarse solve gathers coarse points to replicated (the serial
    coarse solve of the paper) and either scans exactly (coarsest level) or
    recurses (L > 2).

The solver is generic over the stepping function, so the *same* code runs
the forward solve (nonlinear Phi) and the adjoint solve (linearized
transpose propagator) — see :mod:`repro.core.adjoint`.

Notation maps to the paper: ``step_fn`` is Phi, ``cf`` is c_f, ``levels`` is
L, one call to :func:`_vcycle` is one MGRIT V-cycle iteration.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import logical_constraint

# step_fn(stacked_n: pytree_slice, z, h: float) -> z_next
StepFn = Callable[[Any, Any, float], Any]


@dataclasses.dataclass(frozen=True)
class MGRITSpec:
    cf: int = 4
    levels: int = 2
    iters: int = 1
    h: float = 1.0
    # constrain the level-0 chunk axis to the "layers" logical axis
    shard: bool = True
    # levels [0, shard_levels) keep the chunk axis sharded; deeper levels
    # replicate (the paper's serial coarse solve). Non-divisible chunk
    # counts fall back to replication automatically.
    shard_levels: int = 1
    # names of the state's own axes, e.g. ("batch", None, None) for (B,S,D)
    znames: Tuple[Optional[str], ...] = ("batch", None, None)


def _tree_idx(tree, idx):
    return jax.tree.map(lambda a: a[idx], tree)


def _chunk(tree, J: int, cf: int):
    return jax.tree.map(lambda a: a.reshape((J, cf) + a.shape[1:]), tree)


def _constrain(x, spec: MGRITSpec, lead: Tuple[Optional[str], ...]):
    if not spec.shard:
        return x
    return logical_constraint(x, lead + spec.znames)


# ---------------------------------------------------------------------------
# Relaxation sweeps
# ---------------------------------------------------------------------------


def _f_relax(step_fn: StepFn, chunked, Zc, g, spec: MGRITSpec, h: float):
    """F-relaxation: propagate c_f - 1 steps from every coarse point.

    Zc: (J, *state) current coarse-point values.
    g:  None or (J, cf, *state) FAS rhs (g[j, i] is added producing point
        j*cf + i + 1).
    Returns U: (J, cf, *state) with U[j, i] = Z_{j*cf+i}.
    """
    cf = spec.cf

    def chunk_fn(z0j, p_chunk, g_chunk):
        def stp(z, xs):
            p_i, g_i = xs
            z2 = step_fn(p_i, z, h)
            if g_i is not None:
                z2 = z2 + g_i
            return z2, z2

        if cf == 1:
            return z0j[None]
        xs = (_tree_idx(p_chunk, slice(0, cf - 1)),
              g_chunk[: cf - 1] if g_chunk is not None else None)
        if g_chunk is None:
            # avoid scanning a None: wrap with zero-free variant
            def stp0(z, p_i):
                z2 = step_fn(p_i, z, h)
                return z2, z2
            _, ys = jax.lax.scan(stp0, z0j, _tree_idx(p_chunk, slice(0, cf - 1)))
        else:
            _, ys = jax.lax.scan(lambda z, xs: stp(z, xs), z0j, xs)
        return jnp.concatenate([z0j[None], ys], axis=0)

    U = jax.vmap(chunk_fn)(Zc, chunked, g)
    return _constrain(U, spec, ("layers", None))


def _c_step(step_fn: StepFn, chunked, U, g, spec: MGRITSpec, h: float):
    """Propagate the last fine point of every chunk across the boundary:
    W[j] = Phi(U[j, cf-1]) (+ g[j, cf-1]) = candidate value for Z_{(j+1)cf}."""
    cf = spec.cf
    p_last = _tree_idx(chunked, (slice(None), cf - 1))
    u_last = U[:, cf - 1]
    W = jax.vmap(lambda p, u: step_fn(p, u, h))(p_last, u_last)
    if g is not None:
        W = W + g[:, cf - 1]
    return _constrain(W, spec, ("layers",))


def _shift(z0, W, spec: MGRITSpec):
    """New coarse points after C-relaxation: [z0, W[0], ..., W[J-2]];
    the slice across the sharded J axis lowers to collective-permute."""
    Zc = jnp.concatenate([z0[None], W[:-1]], axis=0)
    return _constrain(Zc, spec, ("layers",))


# ---------------------------------------------------------------------------
# Exact serial solves (coarsest level / reference / buffer layers)
# ---------------------------------------------------------------------------


def serial_solve(step_fn: StepFn, stacked, z0, h: float, g=None,
                 remat: bool = False):
    """Exact forward substitution Z_{n+1} = Phi(Z_n) + g_n (a lax.scan).

    Returns (states, zT): states[n] = Z_n for n = 0..N-1 and zT = Z_N.
    """
    body = step_fn
    if remat:
        body = jax.checkpoint(step_fn, static_argnums=(2,))

    def stp(z, xs):
        if g is None:
            p = xs
            z2 = body(p, z, h)
        else:
            p, g_n = xs
            z2 = body(p, z, h) + g_n
        return z2, z

    xs = stacked if g is None else (stacked, g)
    zT, states = jax.lax.scan(stp, z0, xs)
    return states, zT


# ---------------------------------------------------------------------------
# Level restriction
# ---------------------------------------------------------------------------


def coarse_restrict(stacked, cf: int):
    """Level restriction R: the coarse propagator's stacked arguments are
    the fine arguments at every ``cf``-th layer (paper Fig. 2 — coarse
    point j reuses the fine weights of layer ``j*cf``; the ODE step is
    rescaled by the caller: ``h_c = h * cf`` inside the V-cycle, a gate /
    residual scale at serve time). This is the single owner of the
    coarse-grid restriction, shared by the MGRIT solver below and the
    serve engine's coarse-propagator draft model
    (``repro.serve.spec`` via ``transformer.coarse_draft_params``).

    Unlike the solver (which requires ``N % cf == 0``), the restriction
    itself accepts any depth: the last coarse layer of a ragged stack
    stands in for ``N - (J-1)*cf < cf`` fine layers.
    """
    return jax.tree.map(lambda a: a[::cf], stacked)


# ---------------------------------------------------------------------------
# The V-cycle
# ---------------------------------------------------------------------------


def _vcycle(step_fn: StepFn, stacked, z0, states, zT, g, spec: MGRITSpec,
            level: int, h: float, final_frelax: bool = True):
    """One FAS MGRIT V-cycle at `level`.

    stacked: pytree (N_l, ...); states: (N_l, *state) current values
    (states[n] = Z_n, n < N_l); zT: Z_{N_l}; g: None or (N_l, *state).
    Returns (states, zT, resnorm) improved.

    ``final_frelax=False`` skips the trailing interpolation F-relaxation:
    it is bit-identical to the FIRST sweep of the next V-cycle (F-points
    are recomputed from unchanged C-points), so consecutive cycles only
    need it once (§Perf beyond-paper optimization; saves one relaxation
    sweep per extra iteration).
    """
    N = jax.tree.leaves(stacked)[0].shape[0]
    cf = spec.cf
    assert N % cf == 0, f"level {level}: N={N} not divisible by cf={cf}"
    J = N // cf
    lspec = spec if level < spec.shard_levels else \
        dataclasses.replace(spec, shard=False)

    chunked = _chunk(stacked, J, cf)
    gc_fine = None if g is None else g.reshape((J, cf) + g.shape[1:])
    Zc = states.reshape((J, cf) + states.shape[1:])[:, 0]
    Zc = _constrain(Zc, lspec, ("layers",))

    # ---- FCF relaxation (paper Alg. 1) ----
    U = _f_relax(step_fn, chunked, Zc, gc_fine, lspec, h)          # F
    W = _c_step(step_fn, chunked, U, gc_fine, lspec, h)            # C
    Zc = _shift(z0, W, lspec)
    zT = W[-1]
    U = _f_relax(step_fn, chunked, Zc, gc_fine, lspec, h)          # F
    # propagated C-values of the relaxed iterate (for residual + FAS rhs)
    W = _c_step(step_fn, chunked, U, gc_fine, lspec, h)

    # ---- residual at C-points:  r_{(j+1)cf} = W[j] - Z_{(j+1)cf} ----
    u0 = jnp.concatenate([Zc, zT[None]], axis=0)                   # (J+1, ...)
    r = W - u0[1:]
    resnorm = jnp.sqrt(jnp.sum(jnp.square(r.astype(jnp.float32))))

    # ---- coarse grid (FAS): u_{j+1} = Phi_c(u_j) + g_c[j] ----
    # named ``mgrit_coarse`` in the trace: the serial part of the solve
    with jax.named_scope("mgrit_coarse"):
        coarse = coarse_restrict(stacked, cf)
        h_c = h * cf
        # replicate the coarse problem (the paper's serial coarse solve)
        u0_rep = logical_constraint(u0, (None,) + spec.znames) \
            if lspec.shard else u0
        phi_c_u0 = jax.vmap(lambda p, u: step_fn(p, u, h_c))(coarse,
                                                             u0_rep[:-1])
        g_c = W - phi_c_u0                                         # (J, ...)
        if lspec.shard:
            g_c = logical_constraint(g_c, (None,) + spec.znames)

        if level + 1 >= spec.levels - 1 or J % cf != 0:
            # exact coarsest solve: serial forward substitution
            cs, czT = serial_solve(step_fn, coarse, z0, h_c, g=g_c)
        else:
            cs0 = u0_rep[:-1]
            cs, czT, _ = _vcycle(step_fn, coarse, z0, cs0, u0_rep[-1], g_c,
                                 spec, level + 1, h_c)
        u_new = jnp.concatenate([cs, czT[None]], axis=0)

    # ---- correct C-points and final F-relax (interpolation) ----
    e = u_new - u0_rep
    if lspec.shard:
        e = _constrain(e[:-1], lspec, ("layers",))
        Zc = Zc + e
        zT = zT + u_new[-1] - u0_rep[-1]
    else:
        Zc = Zc + e[:-1]
        zT = zT + e[-1]
    if final_frelax:
        U = _f_relax(step_fn, chunked, Zc, gc_fine, lspec, h)
        states = U.reshape((N,) + U.shape[2:])
    else:
        # write back corrected C-points only; stale F-points are overwritten
        # by the next cycle's opening F-relaxation anyway
        U = U.at[:, 0].set(Zc)
        states = U.reshape((N,) + U.shape[2:])
    return states, zT, resnorm


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def mgrit_solve(step_fn: StepFn, stacked, z0, spec: MGRITSpec,
                init_states=None, init_zT=None):
    """Run `spec.iters` MGRIT V-cycles for the evolution
    ``Z_{n+1} = step_fn(stacked[n], Z_n, h)``.

    Returns (states (N, *state) with states[n] = Z_n, zT, resnorms (iters,)).

    Initialization (when init_states is None) is the coarse-grid
    propagation (nested iteration / FMG init): serial coarse traversal with
    Phi_c, then an F-relaxation fills fine points.
    """
    N = jax.tree.leaves(stacked)[0].shape[0]
    cf = spec.cf
    J = N // cf
    chunked = _chunk(stacked, J, cf)

    if init_states is None:
        with jax.named_scope("mgrit_coarse"):
            coarse = coarse_restrict(stacked, cf)
            cs, czT = serial_solve(step_fn, coarse, z0, spec.h * cf)
            Zc0 = _constrain(cs, spec, ("layers",))
        U = _f_relax(step_fn, chunked, Zc0, None, spec, spec.h)
        states = U.reshape((N,) + U.shape[2:])
        zT = czT
    else:
        states, zT = init_states, init_zT

    norms = []
    n_iters = max(spec.iters, 1)
    for i in range(n_iters):
        states, zT, rn = _vcycle(step_fn, stacked, z0, states, zT, None,
                                 spec, 0, spec.h,
                                 final_frelax=(i == n_iters - 1))
        norms.append(rn)
    return states, zT, jnp.stack(norms)

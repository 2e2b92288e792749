"""Sort-free top-k/top-p logit masking (fused sampling epilogue).

``repro.launch.steps.apply_top_k_top_p`` drives both filters off one full
descending argsort per row — an O(V log V) sort plus three gather/scatter
round-trips over the vocab axis, all to find two scalar thresholds. This
module computes those thresholds directly by binary search over the
*sortable-integer* encoding of float32:

    u = bitcast(x, int32);  u ^= (0x7FFFFFFF if u < 0 else 0)

is strictly monotone in x for finite floats, so signed comparisons on
``u`` order logits without sorting. 32 fixed iterations then find

  * tau_k — the k-th largest logit (largest threshold keeping >= k values),
  * tau_p — the smallest logit whose strictly-greater survivor mass is
    still < p (the nucleus boundary; the argmax satisfies it vacuously),

and the row mask is just ``u >= max(tau_k, tau_p)``: O(V) streaming
passes, no sort, no scatter. Gumbel noise stays *outside* the kernel —
the sampler's key schedule (``fold_in(PRNGKey(seed), counter)``) is
request-reproducibility contract surface and must not change.

Tie semantics caveat (distinct logits are unaffected): threshold masking
keeps *every* logit tied with the k-th value, where the sort path keeps
only the ties that argsort happened to rank first. Equal logits do not
occur with real model outputs, matching the documented contract of
``apply_top_k_top_p``. The p-boundary comparison accumulates survivor
mass in vocab order rather than sorted order, so a row whose cumulative
mass hits p within one float ulp of the boundary could flip one
borderline token — deterministic for a given input, and temperature-0
slots never enter this path at all.

``topk_topp_mask_ref`` (vectorized jnp, no sort) is the oracle and the
CPU production path; ``topk_topp_mask_pallas`` runs one grid step per
batch slot for TPU/interpret, on the same math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -1e30
_SEARCH_BITS = 32
_I32_MIN = -(2 ** 31)
_I32_MAX = 2 ** 31 - 1


def _sortable_i32(x):
    """Monotone int32 encoding of float32 (finite values): non-negative
    floats keep their bits, negative ones flip their magnitude bits."""
    i = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _row_reduce(fn, a):
    """Reduce every axis but the leading (row) one, keeping dims: (B, V)
    in the jnp path, (1, Vp/128, 128) inside the kernel."""
    for axis in range(a.ndim - 1, 0, -1):
        a = fn(a, axis=axis, keepdims=True)
    return a


def _search_kth(u, k_eff):
    """Largest threshold tau with count(u >= tau) >= k_eff, per row.
    u: int32 keys; k_eff: float32 in [1, V], shaped like a row reduction.
    Counts are float32 sums, exact below 2**24."""

    def step(_, lh):
        lo, hi = lh
        # ceil((lo + hi) / 2) without int32 overflow
        mid = (lo >> 1) + (hi >> 1) + ((lo | hi) & 1)
        cnt = _row_reduce(jnp.sum, jnp.where(u >= mid, 1.0, 0.0))
        ok = cnt >= k_eff
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo = jnp.full(k_eff.shape, _I32_MIN, jnp.int32)
    hi = jnp.full(k_eff.shape, _I32_MAX, jnp.int32)
    lo, _ = jax.lax.fori_loop(0, _SEARCH_BITS, step, (lo, hi))
    return lo


def _search_nucleus(u, e, p_z):
    """Smallest threshold tau with mass(u > tau) < p_z, per row.
    e: unnormalized survivor weights; p_z = p * sum(e) per row."""

    def step(_, lh):
        lo, hi = lh
        # floor((lo + hi) / 2) without int32 overflow
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
        mass = _row_reduce(jnp.sum, jnp.where(u > mid, e, 0.0))
        ok = mass < p_z
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    lo = jnp.full(p_z.shape, _I32_MIN, jnp.int32)
    hi = jnp.full(p_z.shape, _I32_MAX, jnp.int32)
    _, hi = jax.lax.fori_loop(0, _SEARCH_BITS, step, (lo, hi))
    return hi


def _mask_rows(lf, top_ks, top_ps, V):
    """Shared mask math for ref and kernel paths. lf: float32 rows of V
    logits, (B, V) or (1, Vp/128, 128); top_ks/top_ps shaped like a row
    reduction of lf."""
    u = _sortable_i32(lf)
    k_eff = jnp.clip(jnp.where(top_ks <= 0, V, top_ks), 1, V)
    tau_k = _search_kth(u, k_eff.astype(jnp.float32))
    keep_k = u >= tau_k
    masked_k = jnp.where(keep_k, lf, _MASKED)
    m = _row_reduce(jnp.max, masked_k)
    e = jnp.exp(masked_k - m)                 # exact 0 for masked entries
    p_z = top_ps.astype(jnp.float32) * _row_reduce(jnp.sum, e)
    tau_p = _search_nucleus(u, e, p_z)
    keep = keep_k & (u >= tau_p)
    return jnp.where(keep, lf, _MASKED)


def topk_topp_mask_ref(logits, top_ks, top_ps):
    """Mask all but each row's top-k/top-p survivors to ``_MASKED``.
    logits: (B, V); top_ks: (B,) int32 (<= 0 disables); top_ps: (B,)
    float in (0, 1]. Survivor logits pass through bit-unchanged."""
    return _mask_rows(logits.astype(jnp.float32), top_ks[:, None],
                      top_ps[:, None], logits.shape[-1])


def _sampling_kernel(ks_ref, ps_ref, x_ref, o_ref, *, V: int):
    b = pl.program_id(0)
    lf = x_ref[...]                                   # (1, Vp/128, 128)
    one = (1,) * lf.ndim
    o_ref[...] = _mask_rows(lf, jnp.full(one, ks_ref[b], jnp.int32),
                            jnp.full(one, ps_ref[b], jnp.float32), V)


def topk_topp_mask_pallas(logits, top_ks, top_ps, *,
                          interpret: bool = False):
    """Pallas twin of :func:`topk_topp_mask_ref`: one grid step per slot,
    the whole logit row resident in VMEM, both threshold searches and the
    final mask fused into a single pass with no HBM sort. The row is laid
    out as (Vp/128, 128) — dense (8, 128) tiles — with V padded up to Vp,
    a multiple of 128, by ``_MASKED`` entries: they rank below every real
    logit, so they never survive top-k and carry no top-p mass."""
    B, V = logits.shape
    Vp = -(-V // 128) * 128
    logits = jnp.pad(logits.astype(jnp.float32), ((0, 0), (0, Vp - V)),
                     constant_values=_MASKED)
    row = (Vp // 128, 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1,) + row, lambda b, ks, ps: (b, 0, 0))],
        out_specs=pl.BlockSpec((1,) + row, lambda b, ks, ps: (b, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_sampling_kernel, V=V),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,) + row, jnp.float32),
        interpret=interpret,
    )(top_ks.astype(jnp.int32), top_ps.astype(jnp.float32),
      logits.reshape((B,) + row))
    return out.reshape(B, Vp)[:, :V]

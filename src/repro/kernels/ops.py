"""Jit'd public wrappers for the Pallas kernels.

Every kernel entry point resolves its execution mode HERE — single owner,
no per-call-site flag to forget (the old per-kernel ``interpret: bool =
False`` defaults silently picked compiled Mosaic on CPU unless each
caller remembered to pass the flag; now an unspecified mode always asks
:func:`_interpret_default` / :func:`kernel_mode`).

Two tiers of dispatch:

* The training-side kernels (``flash_attention``/``rmsnorm``/``ssm_scan``)
  keep their boolean contract: compiled on TPU, interpret on CPU.
* The paged-decode kernels (``paged_attention``/``paged_ssm_update``/
  ``topk_topp_mask``) are three-way — mode "pallas" (compiled Mosaic, the
  TPU default), "interpret" (the same Pallas body executed per grid cell
  on CPU: the conformance-test contract, far too slow to serve with), or
  "ref" (a jnp implementation of identical math, the CPU default — XLA
  serves it fast, and the kernel files document that ref and kernel are
  oracle-checked against each other in ``tests/test_kernels_paged.py``).
  ``REPRO_KERNEL_MODE`` overrides the default for debugging on the CPU.
  On a TPU only "pallas" is accepted: a run there that silently served
  through the jnp arm would not be measuring the kernels.
"""
from __future__ import annotations

import functools
import os

import jax

from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.kernels import paged_ssm as ps
from repro.kernels import rmsnorm as rn
from repro.kernels import sampling as sp
from repro.kernels import ssm_scan as ss

_MODES = ("pallas", "interpret", "ref")


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def kernel_mode(mode: str | None = None) -> str:
    """Resolve the paged-kernel execution mode (single owner).

    Explicit argument wins, then the ``REPRO_KERNEL_MODE`` env var, then
    the platform default: compiled Pallas on TPU, jnp ref on CPU. Asking
    for "ref" or "interpret" on a TPU raises.
    """
    if mode is None:
        mode = os.environ.get("REPRO_KERNEL_MODE") or (
            "ref" if _interpret_default() else "pallas")
    if mode not in _MODES:
        raise ValueError(f"kernel mode {mode!r} not in {_MODES}")
    if mode != "pallas" and jax.default_backend() == "tpu":
        raise ValueError(f"kernel mode {mode!r} on a TPU: the paged "
                         "kernels run compiled there (mode 'pallas')")
    return mode


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, interpret=None):
    """q: (B, S, H, hd); k/v: (B, S, Hkv, hd) — model layout."""
    interpret = _interpret_default() if interpret is None else interpret
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = fa.flash_attention_bhsd(qt, kt, vt, causal=causal,
                                interpret=interpret)
    return o.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rmsnorm(x, w, *, interpret=None):
    """x: (..., D) any leading dims."""
    interpret = _interpret_default() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y = rn.rmsnorm_2d(x2, w, interpret=interpret)
    return y.reshape(shape)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(dt, x, A, B, C, D, *, chunk: int = 64, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return ss.ssm_scan(dt, x, A, B, C, D, chunk=chunk, interpret=interpret)


# ---------------------------------------------------------------------------
# Paged-decode kernels (serving hot path)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode",))
def paged_attention(q, pk, pv, page_table, lengths, *, mode=None):
    """Paged flash-decode attention in model layout.

    q: (B, S, H, hd) new-token queries (post-rope); pk/pv: (n_pages,
    page_size, Hkv, hd) pools (new k/v already scattered in);
    page_table: (B, P) — pass the table sliced to the live page bucket,
    that slice is the fused path's speed lever; lengths: (B,). Returns
    (B, S, H, hd), bitwise-matching the gathered-view ``dot_attention``
    path at every unpadded position.
    """
    mode = kernel_mode(mode)
    if mode == "ref":
        return pa.paged_attention_ref(q, pk, pv, page_table, lengths)
    return pa.paged_flash_attention(q, pk, pv, page_table, lengths,
                                    interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("order", "mode"))
def paged_ssm_update(dt, x, Bm, Cm, A, h_pool, read_page, live, phys_w,
                     t_w, n_new, *, order: str, mode=None):
    """Paged SSM recurrence + compact snapshot commit, rows layout.

    dt/x: (B, S, R); Bm/Cm: (B, S, ds); A: (R, ds); h_pool: (N, R, ds)
    float32. read_page/live/n_new: (B,); phys_w/t_w: (B, W) — the compact
    write plan from ``repro.models.ssm.compact_snapshot_steps``. ``order``
    selects the mamba1 ("dbx") vs mamba2 ("dxb") product grouping.
    Returns (y (B, S, R) float32, updated h_pool).
    """
    mode = kernel_mode(mode)
    if mode == "ref":
        return ps.paged_ssm_update_ref(dt, x, Bm, Cm, A, h_pool, read_page,
                                       live, phys_w, t_w, n_new, order=order)
    return ps.paged_ssm_update_pallas(dt, x, Bm, Cm, A, h_pool, read_page,
                                      live, phys_w, t_w, n_new, order=order,
                                      interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("mode",))
def topk_topp_mask(logits, top_ks, top_ps, *, mode=None):
    """Sort-free top-k/top-p masking: survivors keep their logits
    bit-unchanged, the rest drop to -1e30. logits: (B, V); top_ks: (B,)
    int32 (<= 0 disables); top_ps: (B,) float in (0, 1]."""
    mode = kernel_mode(mode)
    if mode == "ref":
        return sp.topk_topp_mask_ref(logits, top_ks, top_ps)
    return sp.topk_topp_mask_pallas(logits, top_ks, top_ps,
                                    interpret=(mode == "interpret"))

"""Paged SSM decode/chunked-prefill Pallas kernel (TPU target).

Serving keeps SSM state as snapshot *pages* (``repro.models.ssm``): page p
of a slot holds the recurrent state after exactly (p+1)*page_size tokens.
The gathered-view decode path re-runs a full ``lax.scan`` and then
scatters a snapshot for **every** (slot, table-column) pair — B*P pages of
pool traffic per layer per step, almost all of it rewriting scratch
page 0. This kernel walks the snapshot schedule in-kernel instead: grid
(B, W) with W = the at-most ``ceil((S + page_size - 1)/page_size)`` pages
a call of S tokens can finalize; window w of slot b advances the
recurrence from local step ``t_w[b, w-1]+1`` through ``t_w[b, w]`` with
the running state h carried in VMEM scratch, then writes h — which at the
end of window w *is* the snapshot after step ``t_w[b, w]`` — straight
into physical page ``phys_w[b, w]`` of the pool via an aliased,
scalar-prefetch-indexed output block. The initial state is read in-kernel
from ``read_page[b]`` the same way. ``kernels/ssm_scan.py`` is the serial
(non-paged) chunked reference for the recurrence itself.

Rows layout: both mamba versions are expressed as R independent rows over
a shared (B, ds) B/C stream — mamba1 maps rows to the di channels
(A: (di, ds), term order (dt⊙B)⊙x, ``order="dbx"``), mamba2 flattens
(heads, headdim) to rows with per-head dt/A tiled across headdim (term
order (dt⊙x)⊙B, ``order="dxb"``). The orders are NOT interchangeable —
float multiplication is not associative-bitwise, and the fused path must
reproduce the gathered scan's exact product order.

``paged_ssm_update_ref`` is the jnp oracle and the CPU production path
(mode="ref" in :mod:`repro.kernels.ops`): the same masked scan the
gathered path runs, plus the *compact* snapshot scatter (W pages per slot
instead of P). Pools may differ from the gathered path only at scratch
page 0, which is never read back as real state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MAX_ROW_TILE = 512


def max_write_pages(seq_len: int, page_size: int) -> int:
    """Most snapshot pages S consecutive tokens can finalize, over every
    possible start offset within a page: ceil((page_size-1 + S)/page_size)."""
    return (seq_len + page_size - 2) // page_size + 1


def _row_tile(R: int) -> int:
    """Rows per grid step: rows are independent recurrences, and a
    (rows, ds) f32 block pads ds up to 128 lanes in VMEM, so R = 8192
    whole would take 4 MiB per block. Tiles stay multiples of 128 (the
    lane width of the (B, S, R) dt/x/y blocks); an R that 128 does not
    divide runs whole."""
    tr = R
    while tr > _MAX_ROW_TILE and tr % 256 == 0:
        tr //= 2
    return tr


def _paged_ssm_kernel(rp_ref, pw_ref, tw_ref, nn_ref, lv_ref,
                      dt_ref, x_ref, b_ref, c_ref, a_ref, hin_ref,
                      y_ref, hout_ref, h_scr, *, order: str):
    b = pl.program_id(0)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)
        h0 = hin_ref[0].astype(jnp.float32)
        h_scr[...] = jnp.where(lv_ref[b] > 0, h0, jnp.zeros_like(h0))

    # window w advances the recurrence through local steps (t0 .. t_w[b,w]];
    # trailing windows past the slot's last written page are empty ranges
    t0 = jnp.where(w == 0, 0, tw_ref[b, jnp.maximum(w - 1, 0)] + 1)
    t1 = tw_ref[b, w]
    A = a_ref[...]
    n_new = nn_ref[b]

    def body(t, h):
        act = t < n_new                      # idle slots: state frozen
        dt_t = dt_ref[0, t]                  # (R,)
        x_t = x_ref[0, t]                    # (R,)
        b_t = b_ref[0, t]                    # (ds,)
        c_t = c_ref[0, t]                    # (ds,)
        dA = jnp.exp(dt_t[:, None] * A)
        if order == "dbx":
            term = dt_t[:, None] * b_t[None, :] * x_t[:, None]
        else:
            term = (dt_t * x_t)[:, None] * b_t[None, :]
        h2 = dA * h + term
        h = jnp.where(act, h2, h)
        y_ref[0, t] = jnp.where(act, jnp.sum(h * c_t[None, :], axis=1),
                                y_ref[0, t])
        return h

    h = jax.lax.fori_loop(t0, t1 + 1, body, h_scr[...])
    h_scr[...] = h
    # end of window w == snapshot after step t_w[b, w]; unwritten windows
    # route to scratch page 0 (phys_w == 0), which is never read as state
    hout_ref[0] = h


def paged_ssm_update_pallas(dt, x, Bm, Cm, A, h_pool, read_page, live,
                            phys_w, t_w, n_new, *, order: str,
                            interpret: bool = False):
    """Rows-layout paged SSM update. dt/x: (B, S, R) f32; Bm/Cm: (B, S, ds)
    f32; A: (R, ds) f32; h_pool: (N, R, ds) f32. read_page/live/n_new: (B,);
    phys_w/t_w: (B, W) from the caller's compact snapshot plan. Returns
    (y (B, S, R) f32, new h_pool — the input buffer, donated/aliased).
    """
    assert order in ("dbx", "dxb"), order
    B, S, R = dt.shape
    ds = Bm.shape[-1]
    N = h_pool.shape[0]
    W = phys_w.shape[1]
    tr = _row_tile(R)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, R // tr, W),
        in_specs=[
            pl.BlockSpec((1, S, tr), lambda b, r, w, *_: (b, 0, r)),  # dt
            pl.BlockSpec((1, S, tr), lambda b, r, w, *_: (b, 0, r)),  # x
            pl.BlockSpec((1, S, ds), lambda b, r, w, *_: (b, 0, 0)),  # Bm
            pl.BlockSpec((1, S, ds), lambda b, r, w, *_: (b, 0, 0)),  # Cm
            pl.BlockSpec((tr, ds), lambda b, r, w, *_: (r, 0)),       # A
            pl.BlockSpec((1, tr, ds),
                         lambda b, r, w, rp, pw, tw, nn, lv: (rp[b], r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, tr), lambda b, r, w, *_: (b, 0, r)),  # y
            pl.BlockSpec((1, tr, ds),
                         lambda b, r, w, rp, pw, tw, nn, lv:
                         (pw[b, w], r, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((tr, ds), jnp.float32)],
    )
    y, new_pool = pl.pallas_call(
        functools.partial(_paged_ssm_kernel, order=order),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, S, R), jnp.float32),
                   jax.ShapeDtypeStruct((N, R, ds), h_pool.dtype)],
        # operand 10 = h_pool (after the 5 scalar-prefetch operands)
        input_output_aliases={10: 1},
        interpret=interpret,
    )(read_page.astype(jnp.int32), phys_w.astype(jnp.int32),
      t_w.astype(jnp.int32), n_new.astype(jnp.int32),
      live.astype(jnp.int32), dt, x, Bm, Cm, A, h_pool)
    return y, new_pool


def paged_ssm_update_ref(dt, x, Bm, Cm, A, h_pool, read_page, live,
                         phys_w, t_w, n_new, *, order: str):
    """jnp oracle / CPU production path, same contract as the kernel.

    The scan body is copied from ``repro.models.ssm.mamba{1,2}_paged_apply``
    operation-for-operation (including the ``order`` product grouping and
    the frozen-state ``where``) so fused ref-mode decode stays bitwise
    equal to the gathered-view path; only the commit differs — a compact
    (B, W) scatter instead of the (B, P) full-table one. Outputs at steps
    >= n_new[b] reproduce the gathered scan's values too (frozen-state
    readout), so even padded positions match bitwise.
    """
    assert order in ("dbx", "dxb"), order
    B, S, R = dt.shape
    h0 = h_pool[read_page]
    h0 = jnp.where(live[:, None, None], h0, jnp.zeros_like(h0))
    valid = jnp.arange(S)[None, :] < n_new[:, None]

    def step(h, inp):
        dt_t, x_t, b_t, c_t, v_t = inp
        dA = jnp.exp(dt_t[:, :, None] * A[None])
        if order == "dbx":
            term = dt_t[:, :, None] * b_t[:, None, :] * x_t[:, :, None]
        else:
            term = (dt_t * x_t)[:, :, None] * b_t[:, None, :]
        h2 = dA * h + term
        h = jnp.where(v_t[:, None, None], h2, h)
        y = jnp.einsum("brs,bs->br", h, c_t)
        return h, (h, y)

    xs = (dt.transpose(1, 0, 2), x.transpose(1, 0, 2),
          Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2), valid.T)
    _, (hs, ys) = jax.lax.scan(step, h0, xs)
    y = ys.transpose(1, 0, 2)                                  # (B, S, R)
    hs_b = jnp.swapaxes(hs, 0, 1)                              # (B, S, R, ds)
    snaps = hs_b[jnp.arange(B)[:, None], t_w]                  # (B, W, R, ds)
    new_pool = h_pool.at[phys_w.reshape(-1)].set(
        snaps.reshape((-1,) + snaps.shape[2:]).astype(h_pool.dtype))
    return y, new_pool

"""Fused flash-attention Pallas kernels (TPU target), forward and backward.

The attention core of self-attention without a KV cache: the (S, S)
score square is never written to HBM. The grid's last dimension walks
key blocks (forward, dQ) or query blocks (dK/dV) sequentially with the
running state in VMEM scratch; the other dimensions are independent.

Inside every kernel keys lie on sublanes and queries on lanes: a score
tile is ``s^T = k q^T`` of shape (k_block, q_block). The per-query
softmax statistics (running max and sum, log-sum-exp, the backward's
``D``) are then lane-dense rows (1, q_block), reduced over sublanes and
broadcast down them, and every product is a plain or ``A B^T`` matmul,
with no transpose inside a kernel:

    forward  s^T = k q^T    p^T = exp(s^T - m)    o^T += v^T p^T
    dK, dV   p^T = exp(s^T - lse)   dV += p^T dO   dP^T = v dO^T
             dS^T = p^T (dP^T - D)  dK += dS^T q
    dQ       the same p^T and dS^T; dQ^T += k^T dS^T

The MXU gets the inputs' own dtype (the model's bf16) with float32
accumulation; the softmax scale multiplies the float32 scores; the
statistics stay float32; p is cast to v's dtype for its products, as
:func:`repro.models.attention.dot_attention` does. The forward writes
``o^T`` and the float32 log-sum-exp, the residual from which both
backward kernels recompute ``p``. ``D = rowsum(dO * O)`` comes from a
jnp pre-pass. Causal blocks wholly above the diagonal are skipped: no
compute (``pl.when``) and no DMA (the index maps clamp to the last block
that is needed, so the pipeline sees an unchanged block and fetches
nothing). The mask is applied only on blocks that cross the diagonal.

GQA: query head h reads KV head h // g; the dK/dV kernel walks the g
query heads of its KV head inside each step. Under ``jax.vmap`` (the
layer-parallel solver maps a layer over the trunk's layers) each
``pallas_call`` gains a leading grid dimension, so the kernels batch.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query and key blocks, largest first: on a v5e a larger tile wins even
# where it gives up the causal skip (at batch 4, 12 heads of 64 and 1024
# tokens one 1024 block beat 512 x 512 blocks by 8%, PERF.md section 5)
BLOCKS = (1024, 512, 256, 128)
# scoped VMEM a kernel may use: a 1024 x 1024 float32 score tile is 4 MiB,
# and the dK/dV kernel holds several beside its operands (a v5e core has
# 128 MiB; the compiler's default limit is 16 MiB)
VMEM_LIMIT = 64 * 1024 * 1024
# s^T = k q^T: contract the head dim of both operands (A B^T)
_NT = (((1,), (1,)), ((), ()))
_F32 = jnp.float32


def block_size(n: int) -> Optional[int]:
    """The query or key block for a sequence of ``n``: the largest of
    ``BLOCKS`` that divides it, the whole sequence when it is shorter
    than the smallest, else None (the fused path does not take it)."""
    if n < BLOCKS[-1]:
        return n
    return next((b for b in BLOCKS if n % b == 0), None)


def _params(n_grid: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n_grid - 1) + ("arbitrary",),
        vmem_limit_bytes=VMEM_LIMIT)


def _causal_steps(step, causal: bool, qi, kj, q_block: int, k_block: int):
    """Run ``step(masked)`` on query block ``qi`` and key block ``kj`` if
    some key of the block is at or before some query (``live``), masked if
    some key is after some query (``diag``)."""
    if not causal:
        step(False)
        return
    live = kj * k_block <= qi * q_block + q_block - 1
    diag = kj * k_block + k_block - 1 > qi * q_block
    pl.when(jnp.logical_and(live, diag))(lambda: step(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(diag)))(
        lambda: step(False))


def _keep(qi, kj, q_block: int, k_block: int):
    """(k_block, q_block) causal mask of a transposed score tile."""
    shape = (k_block, q_block)
    kpos = kj * k_block + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return qpos >= kpos


def _scores_t(q, k, scale: float, keep):
    """s^T = scale * k q^T in float32, masked where ``keep`` is given."""
    st = jax.lax.dot_general(k, q, _NT, preferred_element_type=_F32) * scale
    return st if keep is None else jnp.where(keep, st, NEG_INF)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, vt_ref, ot_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, causal: bool, q_block: int, k_block: int, n_k: int,
                scale: float):
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        keep = _keep(qi, kj, q_block, k_block) if masked else None
        st = _scores_t(q_ref[...], k_ref[...], scale, keep)   # (kb, qb)
        m_prev = m_ref[...]                                    # (1, qb)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pt, axis=0, keepdims=True)
        m_ref[...] = m_new
        vt = vt_ref[...]                                       # (hd, kb)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            vt, pt.astype(vt.dtype), preferred_element_type=_F32)

    _causal_steps(step, causal, qi, kj, q_block, k_block)

    @pl.when(kj == n_k - 1)
    def _finish():
        # every query keeps key 0 (block 0 is never skipped), so l >= 1
        l = l_ref[...]
        ot_ref[...] = (acc_ref[...] / l).astype(ot_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _key_maps(causal: bool, q_block: int, k_block: int, g: int):
    """Index maps of k (B, Hkv, S, hd) and of k^T (B, Hkv, hd, S) blocks on
    a grid over (b, h, query block i, key block j). Under the causal mask
    the key block is clamped to ``last``, the last one query block i
    needs, so a skipped step repeats the index and nothing is fetched.
    (Index maps stay plain arithmetic: the clamp is a min without a call,
    and ``last = j`` leaves j as it is.)"""
    def k_map(b, h, i, j):
        last = (i * q_block + q_block - 1) // k_block if causal else j
        return b, h // g, j - (j > last) * (j - last), 0

    def kt_map(b, h, i, j):
        last = (i * q_block + q_block - 1) // k_block if causal else j
        return b, h // g, 0, j - (j > last) * (j - last)

    return k_map, kt_map


def _query_maps(causal: bool, q_block: int, k_block: int):
    """Index maps of the query-side blocks (q, dO: all g heads of a KV
    head; the log-sum-exp and D rows) on a grid over (b, KV head h, key
    block j, query block i). Under the causal mask the query block is
    clamped up to ``first``, the first one that sees key block j, so the
    skipped steps before it fetch that block once, ahead of its use."""
    def qo_map(b, h, j, i):
        first = (j * k_block) // q_block if causal else i
        return b, h, i + (first > i) * (first - i), 0

    def row_map(b, h, j, i):
        first = (j * k_block) // q_block if causal else i
        return b, h, 0, i + (first > i) * (first - i)

    return qo_map, row_map


def _head_maps():
    """Index maps of a head's own (B, H, S, hd) and (B, H, ., S) blocks on a
    grid over (b, h, query block i, key block j)."""
    def qo_map(b, h, i, j):
        return b, h, i, 0

    def row_map(b, h, i, j):
        return b, h, 0, i

    return qo_map, row_map


def _forward(q, k, v, causal, q_block, k_block, interpret):
    """(o^T (B, H, hd, Sq), lse (B, H, 1, Sq) float32)."""
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    n_q, n_k = Sq // q_block, Sk // k_block
    k_map, kt_map = _key_maps(causal, q_block, k_block, H // Hkv)
    qo_map, row_map = _head_maps()
    kernel = functools.partial(_fwd_kernel, causal=causal, q_block=q_block,
                               k_block=k_block, n_k=n_k,
                               scale=1.0 / math.sqrt(hd))
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[pl.BlockSpec((None, None, q_block, hd), qo_map),
                  pl.BlockSpec((None, None, k_block, hd), k_map),
                  pl.BlockSpec((None, None, hd, k_block), kt_map)],
        out_specs=[pl.BlockSpec((None, None, hd, q_block), row_map),
                   pl.BlockSpec((None, None, 1, q_block), row_map)],
        out_shape=[jax.ShapeDtypeStruct((B, H, hd, Sq), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Sq), _F32)],
        scratch_shapes=[
            pltpu.VMEM((1, q_block), _F32),       # running max
            pltpu.VMEM((1, q_block), _F32),       # running sum
            pltpu.VMEM((hd, q_block), _F32),      # o^T accumulator
        ],
        compiler_params=_params(4),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v.swapaxes(2, 3))


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _probs_t(q, k, lse, scale: float, keep):
    """p^T recomputed from the forward's log-sum-exp row."""
    return jnp.exp(_scores_t(q, k, scale, keep) - lse)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, causal: bool, q_block: int, k_block: int,
                n_q: int, scale: float, groups: int):
    kj, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        keep = _keep(qi, kj, q_block, k_block) if masked else None
        k, v = k_ref[...], v_ref[...]                          # (kb, hd)
        for h in range(groups):        # the query heads of this KV head
            q, do = q_ref[h], do_ref[h]                        # (qb, hd)
            pt = _probs_t(q, k, lse_ref[h], scale, keep)       # (kb, qb)
            dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=_F32)
            dpt = jax.lax.dot_general(v, do, _NT,
                                      preferred_element_type=_F32)
            dst = pt * (dpt - d_ref[h])
            dk_acc[...] += jnp.dot(dst.astype(q.dtype), q,
                                   preferred_element_type=_F32)

    _causal_steps(step, causal, qi, kj, q_block, k_block)

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, d_ref, dqt_ref,
               dq_acc, *, causal: bool, q_block: int, k_block: int, n_k: int,
               scale: float):
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(masked: bool):
        keep = _keep(qi, kj, q_block, k_block) if masked else None
        do = do_ref[...]
        pt = _probs_t(q_ref[...], k_ref[...], lse_ref[...], scale, keep)
        dpt = jax.lax.dot_general(v_ref[...], do, _NT,
                                  preferred_element_type=_F32)
        dst = pt * (dpt - d_ref[...])
        kt = kt_ref[...]                                       # (hd, kb)
        dq_acc[...] += jnp.dot(kt, dst.astype(kt.dtype),
                               preferred_element_type=_F32)

    _causal_steps(step, causal, qi, kj, q_block, k_block)

    @pl.when(kj == n_k - 1)
    def _finish():
        dqt_ref[...] = (dq_acc[...] * scale).astype(dqt_ref.dtype)


def _backward(q, k, v, o, lse, do, causal, q_block, k_block, interpret):
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    n_q, n_k = Sq // q_block, Sk // k_block
    scale = 1.0 / math.sqrt(hd)
    d = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1)[:, :, None, :]

    # dK, dV: a grid over KV heads and key blocks; the query blocks (of
    # all g heads at once) are the sequential dimension
    qo_map, row_map = _query_maps(causal, q_block, k_block)
    qo = pl.BlockSpec((None, g, q_block, hd), qo_map)
    row = pl.BlockSpec((None, g, 1, q_block), row_map)
    kv = pl.BlockSpec((None, None, k_block, hd), lambda b, h, j, i:
                      (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, q_block=q_block,
                          k_block=k_block, n_q=n_q, scale=scale, groups=g),
        grid=(B, Hkv, n_k, n_q),
        in_specs=[qo, kv, kv, qo, row, row],
        out_specs=[kv, kv],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((k_block, hd), _F32),
                        pltpu.VMEM((k_block, hd), _F32)],
        compiler_params=_params(4),
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, d)

    # dQ: the forward's grid, key blocks sequential
    k_map, kt_map = _key_maps(causal, q_block, k_block, g)
    qo_map, row_map = _head_maps()
    qo = pl.BlockSpec((None, None, q_block, hd), qo_map)
    row = pl.BlockSpec((None, None, 1, q_block), row_map)
    kv = pl.BlockSpec((None, None, k_block, hd), k_map)
    dqt = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, q_block=q_block,
                          k_block=k_block, n_k=n_k, scale=scale),
        grid=(B, H, n_q, n_k),
        in_specs=[qo, kv, pl.BlockSpec((None, None, hd, k_block), kt_map),
                  kv, qo, row, row],
        out_specs=pl.BlockSpec((None, None, hd, q_block), row_map),
        out_shape=jax.ShapeDtypeStruct((B, H, hd, Sq), q.dtype),
        scratch_shapes=[pltpu.VMEM((hd, q_block), _F32)],
        compiler_params=_params(4),
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, k.swapaxes(2, 3), v, do, lse, d)
    return dqt.swapaxes(2, 3), dk, dv


# ---------------------------------------------------------------------------
# Differentiable entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, q_block, k_block, interpret):
    ot, _ = _forward(q, k, v, causal, q_block, k_block, interpret)
    return ot.swapaxes(2, 3)


def _flash_fwd(q, k, v, causal, q_block, k_block, interpret):
    ot, lse = _forward(q, k, v, causal, q_block, k_block, interpret)
    o = ot.swapaxes(2, 3)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, q_block, k_block, interpret, res, do):
    # the caller's scope again: the backward is traced outside it, and the
    # profile reader finds the attention core by this name
    with jax.named_scope("attn_core"):
        return _backward(*res, do, causal, q_block, k_block, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         q_block: Optional[int] = None,
                         k_block: Optional[int] = None, interpret=None):
    """q: (B, H, Sq, hd); k/v: (B, Hkv, Sk, hd). Returns (B, H, Sq, hd).

    Differentiable: the backward runs the dK/dV and dQ kernels. Blocks
    default to :func:`block_size` of each length. ``interpret=None``
    defers to the single mode owner in :mod:`repro.kernels.ops`
    (interpret on CPU, compiled on TPU).
    """
    if interpret is None:
        from repro.kernels import ops
        interpret = ops._interpret_default()
    Sq, Sk = q.shape[2], k.shape[2]
    q_block = q_block or block_size(Sq)
    k_block = k_block or block_size(Sk)
    if not (q_block and k_block and Sq % q_block == 0
            and Sk % k_block == 0):
        raise ValueError(f"no flash blocks for lengths {Sq}, {Sk}: "
                         f"q_block={q_block}, k_block={k_block}")
    return _flash(q, k, v, causal, q_block, k_block, bool(interpret))

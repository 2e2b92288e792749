"""Flash-decode paged-attention Pallas kernel (TPU target).

Decode-side twin of :mod:`repro.kernels.flash_attention`: instead of a
contiguous (B, H, Sk, hd) K/V tensor, keys live in the serve engine's page
pool (n_pages, page_size, Hkv, hd) and each batch slot owns a row of the
page table. The kernel walks that row **in-kernel** — the page table and
per-slot lengths are scalar-prefetch operands, so the BlockSpec index maps
resolve logical page p of slot b to physical page ``page_table[b, p]``
while the grid runs. One grid block per (slot, query-row tile, page): the
page's K and V for all kv heads arrive as one (page_size*Hkv, hd) block,
every (token, head) query row of the tile scores it in one dot, and a
mask keeps query head h on kv head ``h // group`` (GQA). The online-
softmax state (m, l, acc) lives in VMEM scratch across the sequential
page dimension, exactly like the prefill kernel's k-block dimension.
Blocks span whole trailing dimensions, as Mosaic's (8, 128) tiling
requires of a kv-head axis of 8 or fewer.

Dead pages (beyond ``lengths[b] + S - 1``) are skipped with ``pl.when`` —
the page walk does the work the gathered-dense-view path spends on a
(B, P*page_size, Hkv, hd) gather plus a full-width masked softmax.

``paged_attention_ref`` is the jnp oracle AND the CPU production path
(:mod:`repro.kernels.ops` mode="ref"): it reproduces the gathered-view
math bit-for-bit — same gather construction, same einsum contractions,
same mask constant — so fused serving at temperature 0 emits exactly the
tokens the gathered path emits. Its speed lever is the caller slicing the
page table to the live page count (``repro.serve.cache`` buckets it to a
power of two) rather than gathering the table's full width.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_ROW_TILE = 512          # query rows (token x head) per grid step, at most


def _paged_flash_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, page_size: int,
                        n_pages: int, s_q: int, n_heads: int, n_kv: int,
                        scale: float):
    b = pl.program_id(0)
    tile = pl.program_id(1)       # query-row tile
    p = pl.program_id(2)          # logical page index (sequential)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a page is live iff its first key position can be attended by the
    # last query row (absolute position lengths[b] + s_q - 1)
    live = (p * page_size) <= (len_ref[b] + s_q - 1)

    @pl.when(live)
    def _page():
        q = q_ref[0].astype(jnp.float32) * scale       # (tq, hd)
        k = k_ref[0].astype(jnp.float32)               # (ps*Hkv, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        shape = s.shape                                # (tq, ps*Hkv)
        row = tile * shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # query row r = (token r // H, head r % H); key column c =
        # (page slot c // Hkv, kv head c % Hkv). A row scores only its
        # own GQA kv head, and one mask covers causality AND staleness:
        # key slots past a query's absolute position are either future
        # tokens or garbage beyond the slot's written length
        qpos = len_ref[b] + row // n_heads
        kpos = p * page_size + col // n_kv
        own = (row % n_heads) // (n_heads // n_kv) == col % n_kv
        s = jnp.where(own & (kpos <= qpos), s, NEG_INF)
        m_prev = m_ref[...]                            # (tq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=1,
                                                 keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_flash_attention(q, pk, pv, page_table, lengths, *,
                          interpret: bool = False):
    """q: (B, S, H, hd) model layout; pk/pv: (n_pages, page_size, Hkv,
    hd); page_table: (B, P) int32; lengths: (B,) int32 — q row i of slot
    b sits at absolute position ``lengths[b] + i``. Returns (B, S, H,
    hd).

    One grid step per (slot, query-row tile, page): the page's keys for
    every kv head load once, as a (page_size*Hkv, hd) block, and every
    query head of the tile scores them in one dot, with a mask keeping
    each head on its own kv head. Query rows are (token, head) pairs,
    tiled at ``_ROW_TILE`` so that a long prefill chunk stays inside the
    kernel's scoped VMEM. Both reshapes below are free (row-major merges of
    adjacent axes), and every block spans whole trailing dimensions,
    which is what Mosaic's (8, 128) tiling asks of a block whose
    dimensions are not multiples of the tile. S is tiny (1 in
    steady-state decode, k+1 in speculative verify, the prompt bucket in
    chunked prefill); the page walk supplies the K extent.
    """
    B, S, H, hd = q.shape
    n_pages, page_size, Hkv = pk.shape[:3]
    P = page_table.shape[1]
    rows, cols = S * H, page_size * Hkv
    tq = rows
    while tq > _ROW_TILE and tq % 16 == 0:
        tq //= 2
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_paged_flash_kernel, page_size=page_size,
                               n_pages=P, s_q=S, n_heads=H, n_kv=Hkv,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, rows // tq, P),
        in_specs=[
            pl.BlockSpec((1, tq, hd), lambda b, i, p, pt, ln: (b, i, 0)),
            pl.BlockSpec((1, cols, hd),
                         lambda b, i, p, pt, ln: (pt[b, p], 0, 0)),
            pl.BlockSpec((1, cols, hd),
                         lambda b, i, p, pt, ln: (pt[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, hd),
                               lambda b, i, p, pt, ln: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),         # m
            pltpu.VMEM((tq, 1), jnp.float32),         # l
            pltpu.VMEM((tq, hd), jnp.float32),        # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(B, rows, hd), pk.reshape(n_pages, cols, hd),
      pv.reshape(n_pages, cols, hd))
    return out.reshape(B, S, H, hd)


def _dot_attention_paged(q, kd, vd, lengths, *, scale=None):
    """Dense GQA attention with per-slot causal offsets — a verbatim twin
    of ``repro.models.attention.dot_attention(..., q_offset=lengths)``
    (same contractions, same mask constant, same dtype casts) so the ref
    path stays bitwise-identical to the gathered-view model path. Kept
    here rather than imported: kernels/ must not depend on models/."""
    B, Sq, H, hd = q.shape
    Hkv = kd.shape[2]
    g = H // Hkv
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(B, Sq, Hkv, g, hd)
    logits = jnp.einsum("bqhgk,bshk->bhgqs", qg.astype(jnp.float32),
                        kd.astype(jnp.float32)) * scale
    Sk = kd.shape[1]
    qoff = jnp.asarray(lengths)
    qpos = qoff[..., None] + jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    mask = qpos[..., :, None] >= kpos
    mask = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqs,bshk->bqhgk", probs.astype(vd.dtype), vd)
    return out.reshape(B, Sq, H, hd)


def paged_attention_ref(q, pk, pv, page_table, lengths):
    """jnp oracle in model layout — q: (B, S, H, hd); pk/pv page pools.

    This IS the gathered-view computation over however many table columns
    the caller passes: slicing the table to the live-page bucket is what
    makes it the fast CPU path, and because masked key slots contribute
    exactly-zero probability mass, truncating dead pages leaves the
    surviving logits (and the temperature-0 argmax) unchanged.
    """
    B = q.shape[0]
    n_pages, page_size = pk.shape[0], pk.shape[1]
    pk_flat = pk.reshape(n_pages * page_size, *pk.shape[2:])
    pv_flat = pv.reshape(n_pages * page_size, *pv.shape[2:])
    gather = (page_table[:, :, None] * page_size
              + jnp.arange(page_size)[None, None, :]).reshape(B, -1)
    return _dot_attention_paged(q, pk_flat[gather], pv_flat[gather], lengths)

"""Grouped-query attention with RoPE, qk-norm, KV cache, and cross-attention.

Layout conventions:
  activations        (B, S, D)          logical ("batch","seq","embed")
  q after projection (B, S, H, hd)      logical ("batch","seq","heads","head_dim")
  kv cache           (B, S_max, Hkv, hd) logical ("batch","kv_seq","kv_heads","head_dim")

The dense attention math lives in ``dot_attention``. Self-attention
without a cache takes the fused Pallas flash-attention kernels
(:mod:`repro.kernels.flash_attention`, forward and backward) where
:func:`repro.kernels.ops.flash_attention_mode` says they run: on a TPU
with one device, or with ``use_pallas=True`` (interpret mode on the CPU);
see ``_attention_path``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import flash_attention as fa
from repro.kernels import ops as kops
from repro.models.layers import apply_rope, dense_init, preln_output_scale, rms_norm, rope_freqs
from repro.obs import profile
from repro.parallel.sharding import logical_constraint


def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    pdt = cfg.param_dtype
    ks = jax.random.split(key, 6)
    oscale = 0.02 * preln_output_scale(cfg.n_layers)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), pdt),
        "wk": dense_init(ks[1], (d, hkv, hd), pdt),
        "wv": dense_init(ks[2], (d, hkv, hd), pdt, scale=oscale / 0.02 * 0.02),
        "wo": dense_init(ks[3], (h, hd, d), pdt, scale=oscale),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.dtype(pdt))
        p["k_norm"] = jnp.ones((hd,), jnp.dtype(pdt))
    return p


def _project_qkv(params, x, xa, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    src = x if xa is None else xa
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    return q, k, v


def dot_attention(q, k, v, *, causal: bool, q_offset=0,
                  scale: Optional[float] = None):
    """Reference dense GQA attention.

    q: (B, Sq, H, hd); k, v: (B, Sk, Hkv, hd). ``q_offset`` is the absolute
    position of q[.., 0] for causal masking against a longer k (KV cache) —
    a scalar, or a (B,) array when each batch slot has its own position
    (paged serving, no left-padding).
    """
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(B, Sq, Hkv, g, hd)
    logits = jnp.einsum("bqhgk,bshk->bhgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        Sk = k.shape[1]
        qoff = jnp.asarray(q_offset)
        qpos = qoff[..., None] + jnp.arange(Sq)       # (Sq,) or (B, Sq)
        kpos = jnp.arange(Sk)
        mask = qpos[..., :, None] >= kpos             # (.., Sq, Sk)
        mask = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqs,bshk->bqhgk", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, hd)


def chunked_attention(q, k, v, *, causal: bool, q_block: int = 512,
                      k_block: int = 512):
    """Flash-style online-softmax attention in pure jnp (double blocked
    scan). Memory is O(Sq*Ck + Sk) per head instead of O(Sq*Sk) — this is
    the lowering-safe path for 32k prefill and the oracle for the Pallas
    kernel."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = hd ** -0.5
    q_block = min(q_block, Sq)
    k_block = min(k_block, Sk)
    nq, nk = Sq // q_block, Sk // k_block
    # fold gqa groups: (B, Hkv, g, Sq, hd)
    qh = q.reshape(B, Sq, Hkv, g, hd).transpose(0, 2, 3, 1, 4) * scale
    kh = k.transpose(0, 2, 1, 3)                     # (B, Hkv, Sk, hd)
    vh = v.transpose(0, 2, 1, 3)

    qs = qh.reshape(B, Hkv, g, nq, q_block, hd).transpose(3, 0, 1, 2, 4, 5)
    ks = kh.reshape(B, Hkv, nk, k_block, hd).transpose(2, 0, 1, 3, 4)
    vs = vh.reshape(B, Hkv, nk, k_block, hd).transpose(2, 0, 1, 3, 4)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx                          # (B,Hkv,g,qb,hd)
        qpos = iq * q_block + jnp.arange(q_block)

        def k_step(carry, kv_idx):
            m, l, acc = carry
            kj, vj, jk = kv_idx
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qi.astype(jnp.float32),
                           kj.astype(jnp.float32))
            if causal:
                kpos = jk * k_block + jnp.arange(k_block)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vj.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, g, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, q_block, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), (ks, vs, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(v.dtype)

    _, outs = jax.lax.scan(q_step, None, (qs, jnp.arange(nq)))
    # outs: (nq, B, Hkv, g, qb, hd) -> (B, Sq, H, hd)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hkv, g, Sq, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)


ATTN_CHUNK_THRESHOLD = 8192


def _attention_path(q, k, cfg: ModelConfig, *, cache, xa,
                    fuse: bool) -> str:
    """Which attention core a call takes: ``fused`` (the flash-attention
    kernels, forward and backward) for self-attention without a cache over
    a length the kernel blocks divide, where ``fuse`` (the kernels run
    here: :func:`repro.kernels.ops.flash_attention_mode`); else
    ``chunked`` from ``cfg.attn_chunk`` tokens, else ``dense``."""
    S, hd = q.shape[1], q.shape[3]
    if (fuse and cache is None and xa is None and S == k.shape[1]
            and hd <= 128 and fa.block_size(S) is not None):
        return "fused"
    if (cache is None and S >= (cfg.attn_chunk or ATTN_CHUNK_THRESHOLD)
            and S == k.shape[1] and S % 512 == 0):
        return "chunked"
    return "dense"


def attention_apply(params, x, cfg: ModelConfig, *, causal: bool,
                    rope=None, positions=None, xa=None, cache=None,
                    use_pallas: bool = False):
    """Self/cross attention.

    x: (B, S, D). rope: precomputed (cos, sin) — shared across layers and a
    differentiable "extra" input for the layer-parallel custom VJP. xa:
    encoder output for cross-attention (no rope, no cache rotation). cache:
    dict(k, v, index) for autoregressive decode — the new k/v are scattered
    at ``index`` and attention runs over the full cache.
    Returns (out, new_cache).
    """
    dt = jnp.dtype(cfg.dtype)
    x = x.astype(dt)
    q, k, v = _project_qkv(params, x, xa, cfg)
    q = logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
    k = logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))

    if xa is None and rope is None and positions is not None:
        rope = rope_freqs(cfg.resolved_head_dim, cfg.rope_theta, positions)
    if xa is None and rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_cache = None
    q_offset = 0
    if cache is not None:
        idx = cache["index"]
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        ck = logical_constraint(ck, ("batch", "kv_seq", "kv_heads", "head_dim"))
        cv = logical_constraint(cv, ("batch", "kv_seq", "kv_heads", "head_dim"))
        new_cache = {"k": ck, "v": cv, "index": idx + x.shape[1]}
        k, v = ck, cv
        q_offset = idx

    mode = kops.flash_attention_mode(use_pallas)
    path = _attention_path(q, k, cfg, cache=cache, xa=xa,
                           fuse=mode is not None)
    profile.count_attention(path)
    with jax.named_scope("attn_core"):
        if path == "fused":
            out = kops.flash_attention(q, k, v, causal=causal,
                                       interpret=mode == "interpret")
        elif path == "chunked":
            out = chunked_attention(q, k, v, causal=causal and xa is None)
        else:
            out = dot_attention(q, k, v, causal=causal and xa is None,
                                q_offset=q_offset)
    out = logical_constraint(out, ("batch", "seq", "heads", "head_dim"))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    y = logical_constraint(y, ("batch", "seq", "embed"))
    return y, new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int):
    """Stacked-over-layers KV cache: (L, B, S, Hkv, hd)."""
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((n_layers, batch, max_len, cfg.n_kv_heads, hd), dt),
        "v": jnp.zeros((n_layers, batch, max_len, cfg.n_kv_heads, hd), dt),
        "index": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Paged KV cache (serving)
# ---------------------------------------------------------------------------
#
# Instead of one dense (B, max_len) cache per batch slot, K/V live in a pool
# of fixed-size pages shared by all sequences. A per-slot page table maps
# logical page p of slot b to a physical page id; finished sequences return
# their pages to the free list immediately (repro.serve.kv_pages). Page 0 is
# a scratch page that absorbs writes from padded prompt positions and
# unoccupied slots, so the jitted step needs no data-dependent shapes.


def init_paged_kv_cache(cfg: ModelConfig, n_layers: int, n_pages: int,
                        page_size: int):
    """Page pool stacked over layers: (L, n_pages, page_size, Hkv, hd)."""
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    shape = (n_layers, n_pages, page_size, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_attention_apply(params, x, cfg: ModelConfig, *, rope, pk, pv,
                          page_table, lengths, n_new, fused: bool = False):
    """Self-attention reading/writing one layer's page pool.

    x: (B, S, D) new-token activations. Slot b contributes ``n_new[b] <= S``
    real tokens at absolute positions ``lengths[b] .. lengths[b]+n_new[b]-1``
    — every slot has its own coordinate system starting at 0, so there is no
    left-padding and ``n_new == 0`` marks an unoccupied slot (occupancy
    mask). rope: (cos, sin) of shape (B, S, hd/2) for those positions.
    pk/pv: (n_pages, page_size, Hkv, hd). page_table: (B, P) int32.
    Returns (y, new_pk, new_pv).

    Prefix-sharing contract: several slots may map the same physical page
    (read-only). The caller must guarantee every page overlapping a slot's
    write range [lengths[b], lengths[b]+n_new[b]) is private to that slot
    (allocator refcount 1) — copy-on-write forks
    (``repro.serve.cache.copy_state_page``) happen host-side before the
    step is launched.

    Partial-page ingest safety (token-granular sharing,
    ``CacheBackend.fork_partial``): a slot may start with ``lengths[b]``
    mid-page, its current page a whole-page copy of a donor whose rows
    past ``lengths[b] % page_size`` are stale. That is safe here by
    construction — K/V rows at positions ``>= lengths[b]`` are
    scatter-written before any read of them, and the causal window
    ``pos < lengths[b] + n_new[b]`` (masked per query) never exposes a
    row this call did not either inherit as valid or just write.

    ``fused=True`` routes the attention core through the flash-decode
    paged kernel (:func:`repro.kernels.ops.paged_attention`) — the page
    table is walked in-kernel (or, in ref mode on CPU, gathered at
    whatever width the caller sliced the table to) instead of always
    materializing the full (B, P*page_size, Hkv, hd) dense view. The
    scatter-write of new K/V and all mesh constraints are identical in
    both branches, and unpadded outputs are bitwise-equal.
    """
    dt = jnp.dtype(cfg.dtype)
    x = x.astype(dt)
    q, k, v = _project_qkv(params, x, None, cfg)
    cos, sin = rope
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
    k = logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))

    B, S = x.shape[:2]
    n_pages, page_size = pk.shape[0], pk.shape[1]
    P = page_table.shape[1]
    pos = lengths[:, None] + jnp.arange(S)[None, :]               # (B, S)
    valid = jnp.arange(S)[None, :] < n_new[:, None]               # (B, S)
    slot = jnp.clip(pos // page_size, 0, P - 1)
    phys = jnp.take_along_axis(page_table, slot, axis=1)          # (B, S)
    # invalid writes (prompt padding / idle slots) all land in scratch page 0
    flat = jnp.where(valid, phys * page_size + pos % page_size, 0)
    flat = flat.reshape(-1)
    pk_flat = pk.reshape(n_pages * page_size, *pk.shape[2:])
    pv_flat = pv.reshape(n_pages * page_size, *pv.shape[2:])
    pk_flat = pk_flat.at[flat].set(k.astype(pk.dtype).reshape(
        B * S, *k.shape[2:]))
    pv_flat = pv_flat.at[flat].set(v.astype(pv.dtype).reshape(
        B * S, *v.shape[2:]))

    if fused:
        with jax.named_scope("paged_attn_core_fused"):
            out = kops.paged_attention(q, pk_flat.reshape(pk.shape),
                                       pv_flat.reshape(pv.shape),
                                       page_table, lengths)
    else:
        # per-slot dense view in logical order: (B, P*page_size, Hkv, hd)
        gather = (page_table[:, :, None] * page_size
                  + jnp.arange(page_size)[None, None, :]).reshape(B, -1)
        kd = logical_constraint(pk_flat[gather],
                                ("batch", "kv_seq", "kv_heads", "head_dim"))
        vd = logical_constraint(pv_flat[gather],
                                ("batch", "kv_seq", "kv_heads", "head_dim"))

        # keys gathered in logical order sit at absolute positions
        # 0..cap-1; garbage beyond a slot's written length always has
        # kpos > qpos and masks out under the per-slot causal offset
        with jax.named_scope("paged_attn_core"):
            out = dot_attention(q, kd, vd, causal=True, q_offset=lengths)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    y = logical_constraint(y, ("batch", "seq", "embed"))
    # pools keep their mesh placement across steps (pages over serving
    # DP, kv heads over TP) instead of decaying to replicated
    new_pk = logical_constraint(pk_flat.reshape(pk.shape),
                                ("pages", None, "kv_heads", "head_dim"))
    new_pv = logical_constraint(pv_flat.reshape(pv.shape),
                                ("pages", None, "kv_heads", "head_dim"))
    return y, new_pk, new_pv


# -- fused ref-mode decode: pre-gathered views + deferred pool commit -------
#
# Shipping the stacked (L, N, page_size, Hkv, hd) pools through the layer
# scan as xs/ys costs two full-pool copies per step (scan input slicing +
# output stacking), no matter how few pages a step touches — on CPU that
# dominates steady-state decode. The fused ref path therefore never moves
# the pools through the scan: it gathers each slot's live pages ONCE into
# per-layer dense views (small: the caller's sliced table width), scans the
# layers over those views carrying only the (B, S) new K/V rows out, and
# publishes every layer's rows with ONE donated in-place scatter afterwards
# (paged_kv_commit). Consumed outputs and committed pages are bitwise-equal
# to the in-scan write path: the views hold exactly what a post-write
# gather would (write pages are private by the prefix-sharing contract, and
# scratch-page rows only surface at masked positions), and the commit uses
# the same flat-index formula as the per-layer writes.


def paged_view_gather(pool, page_table):
    """Per-slot dense views of a stacked page pool: (L, N, page_size, H,
    hd) + (B, P) -> (L, B, P*page_size, H, hd), rows in logical order."""
    L, n_pages, page_size = pool.shape[:3]
    B = page_table.shape[0]
    idx = (page_table[:, :, None] * page_size
           + jnp.arange(page_size)[None, None, :]).reshape(B, -1)
    return pool.reshape(L, n_pages * page_size, *pool.shape[3:])[:, idx]


def paged_view_attention_apply(params, x, cfg: ModelConfig, *, rope, kd, vd,
                               lengths, n_new):
    """One layer's self-attention over pre-gathered K/V views — the
    deferred-write twin of :func:`paged_attention_apply`'s fused branch.
    kd/vd: (B, cap, Hkv, hd) views from :func:`paged_view_gather`. The new
    tokens' K/V are inserted at their logical rows (writes beyond ``cap``
    or ``n_new`` drop), the attention core is the same causal-offset dot
    as the gathered path, and the pool write is left to
    :func:`paged_kv_commit`. Returns (y, k_new, v_new)."""
    dt = jnp.dtype(cfg.dtype)
    x = x.astype(dt)
    q, k, v = _project_qkv(params, x, None, cfg)
    cos, sin = rope
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
    k = logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))
    B, S = x.shape[:2]
    cap = kd.shape[1]
    pos = lengths[:, None] + jnp.arange(S)[None, :]
    valid = jnp.arange(S)[None, :] < n_new[:, None]
    row = jnp.where(valid, pos, cap)          # out-of-bounds rows drop
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
    kd = kd.at[bidx, row].set(k.astype(kd.dtype))
    vd = vd.at[bidx, row].set(v.astype(vd.dtype))
    kd = logical_constraint(kd, ("batch", "kv_seq", "kv_heads", "head_dim"))
    vd = logical_constraint(vd, ("batch", "kv_seq", "kv_heads", "head_dim"))
    with jax.named_scope("paged_attn_core_fused_view"):
        out = dot_attention(q, kd, vd, causal=True, q_offset=lengths)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    y = logical_constraint(y, ("batch", "seq", "embed"))
    return y, k, v


def paged_kv_commit(pages, k_rows, v_rows, page_table, lengths, n_new):
    """Publish every layer's new K/V rows into the stacked page pools with
    one scatter per pool (in-place when the state is donated). k_rows /
    v_rows: (L, B, S, Hkv, hd) from the layer scan. Uses the same
    flat-index formula as :func:`paged_attention_apply` — invalid rows
    (padding / idle slots) land in scratch page 0."""
    pk, pv = pages["k"], pages["v"]
    L, n_pages, page_size = pk.shape[:3]
    B, S = k_rows.shape[1:3]
    P = page_table.shape[1]
    pos = lengths[:, None] + jnp.arange(S)[None, :]
    valid = jnp.arange(S)[None, :] < n_new[:, None]
    slot = jnp.clip(pos // page_size, 0, P - 1)
    phys = jnp.take_along_axis(page_table, slot, axis=1)
    flat = jnp.where(valid, phys * page_size + pos % page_size, 0)
    flat = flat.reshape(-1)
    rows = n_pages * page_size
    axes = (None, "pages", None, "kv_heads", "head_dim")

    def commit(pool, vals):
        new = pool.reshape(L, rows, *pool.shape[3:]).at[:, flat].set(
            vals.astype(pool.dtype).reshape(L, B * S, *pool.shape[3:]))
        return logical_constraint(new.reshape(pool.shape), axes)

    return {"k": commit(pk, k_rows), "v": commit(pv, v_rows)}

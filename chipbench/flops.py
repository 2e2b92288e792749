"""Operations and bytes that the work requires, counted from shapes.

Convention, for every metric of the benchmark: a matrix product of
(m, k) by (k, n) is 2*m*k*n operations; causal attention counts only the
keys at or before each query (a query at position p attends p + 1 keys);
training counts the forward pass once and the backward pass twice, and
nothing else: neither MGRIT's extra relaxation sweeps nor recomputation
nor the work of identity (gate 0) padding layers. Element-wise work,
norms and softmax are not counted. Bytes count the operands that must
cross HBM once: bf16 weights, activations in and out.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2


def _dims(m: Dict) -> Tuple[int, int, int, int, int, int, int, int]:
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], hd,
            m["d_ff"], m["vocab_size"], 3 if m["act"] == "silu" else 2)


def mlp_flops_per_token(m: Dict) -> float:
    """One layer's MLP, forward, per token."""
    _, d, _, _, _, ff, _, nmat = _dims(m)
    return 2.0 * d * ff * nmat


def proj_flops_per_token(m: Dict) -> float:
    """One layer's q, k, v and o projections, forward, per token."""
    _, d, H, Hkv, hd, _, _, _ = _dims(m)
    return 2.0 * d * hd * (H + 2 * Hkv) + 2.0 * H * hd * d


def attn_core_flops(m: Dict, n_keys: float) -> float:
    """One layer's scores and weighted values for one query that attends
    ``n_keys`` keys, forward."""
    _, _, H, _, hd, _, _, _ = _dims(m)
    return 4.0 * H * hd * n_keys


def head_flops(m: Dict) -> float:
    _, d, _, _, _, _, V, _ = _dims(m)
    return 2.0 * d * V


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Forward and backward of one token of a (B, seq) causal LM batch."""
    L = m["n_layers"]
    mean_keys = (seq + 1) / 2.0
    fwd = L * (mlp_flops_per_token(m) + proj_flops_per_token(m)
               + attn_core_flops(m, mean_keys)) + head_flops(m)
    return 3.0 * fwd


def train_mlp_step(m: Dict, tokens: int) -> Tuple[float, float]:
    """(operations, bytes) that every layer's MLP requires in one training
    step of ``tokens`` tokens: forward and backward."""
    L, d, _, _, _, ff, _, nmat = _dims(m)
    flops = 3.0 * L * tokens * mlp_flops_per_token(m)
    byts = 3.0 * L * (BF16 * nmat * d * ff + 2 * BF16 * tokens * d)
    return flops, byts


def train_attn_core_step(m: Dict, batch: int, seq: int) -> Tuple[float,
                                                                 float]:
    """(operations, bytes) that every layer's attention core (scores,
    softmax, weighted values; no projections) requires in one training
    step: forward and backward, causal."""
    L, _, H, Hkv, hd, _, _, _ = _dims(m)
    flops = 3.0 * L * batch * sum_keys(seq) * attn_core_flops(m, 1)
    byts = 3.0 * L * batch * seq * (2 * H + 2 * Hkv) * hd * BF16
    return flops, byts


def sum_keys(n: int, start: int = 0) -> float:
    """Keys attended by queries at positions start .. start + n - 1."""
    return n * start + n * (n + 1) / 2.0


def paged_attn_call(m: Dict, lengths: Iterable[int], n_new: Iterable[int],
                    kv_bytes: int = BF16) -> Tuple[float, float]:
    """(operations, bytes) of one layer's paged-attention call: for every
    slot with new tokens, its q rows against the live keys (cached plus
    new, causal), reading the live tokens' K and V once and q and the
    output once."""
    _, _, H, Hkv, hd, _, _, _ = _dims(m)
    flops = byts = 0.0
    for length, n in zip(lengths, n_new):
        if n <= 0:
            continue
        flops += attn_core_flops(m, 1) * sum_keys(n, length)
        byts += (length + n) * Hkv * hd * 2 * kv_bytes + 2 * n * H * hd * BF16
    return flops, byts


def roofline_s(flops: float, byts: float, peak: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = byts / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def serve_call_flops(m: Dict, lengths: Iterable[int],
                     n_new: Iterable[int]) -> float:
    """Forward operations that one serve-step call (prefill or decode)
    required: every slot's new tokens through every layer, their causal
    attention over cached plus new keys, and one row of logits per slot
    that had new tokens."""
    L = m["n_layers"]
    dense = mlp_flops_per_token(m) + proj_flops_per_token(m)
    total = 0.0
    for length, n in zip(lengths, n_new):
        if n > 0:
            total += (L * (dense * n + attn_core_flops(m, 1)
                           * sum_keys(n, length)) + head_flops(m))
    return total

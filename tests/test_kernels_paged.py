"""Fused paged-decode kernels: mode conformance + fused-vs-gathered serve
equality (ISSUE 6 acceptance).

Two tiers. The kernel tier pins each paged kernel's Pallas body
(``mode="interpret"`` — the CPU stand-in for compiled Mosaic, same body
per grid cell) against the jnp ref implementation that CPU serving
actually runs, so the two dispatch arms of ``kernels.ops`` cannot drift.
The serve tier runs real ``ServeEngine`` pairs per backend family: the
fused engine must emit bitwise the gathered engine's tokens at
temperature 0, reproduce sampled streams under shared seeds, and leave
speculative decoding unchanged (the draft wave inherits the fused step,
the verify wave stays full-width by design).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.models import transformer
from repro.models.ssm import compact_snapshot_steps, paged_read_plan
from repro.serve.engine import Request, ServeEngine
from serve_oracle import engine_outputs
from test_serve_backends import FAMILY_MODELS, MAX_LEN, family_rcfg, \
    family_setup


def rand(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.5).astype(dtype)


# ---------------------------------------------------------------------------
# Kernel tier: paged attention
# ---------------------------------------------------------------------------


def _attn_case(key, B, S, H, Hkv, hd, page_size, pages_per_slot):
    """Random pool + disjoint per-slot page tables + mixed lengths."""
    ks = jax.random.split(key, 3)
    n_pages = 1 + B * pages_per_slot                   # page 0 = scratch
    q = rand(ks[0], (B, S, H, hd))
    pk = rand(ks[1], (n_pages, page_size, Hkv, hd))
    pv = rand(ks[2], (n_pages, page_size, Hkv, hd))
    table = (1 + np.arange(B * pages_per_slot)).reshape(B, pages_per_slot)
    cap = pages_per_slot * page_size
    lengths = np.minimum(np.arange(B) * 3 + 1, cap - S).astype(np.int32)
    return q, pk, pv, jnp.asarray(table, jnp.int32), jnp.asarray(lengths)


@pytest.mark.parametrize("B,S,H,Hkv,hd", [
    (2, 1, 2, 2, 16),      # plain decode step
    (2, 4, 4, 2, 16),      # chunked prefill, GQA
    (3, 2, 4, 1, 32),      # MQA
])
def test_paged_attention_interpret_matches_ref(B, S, H, Hkv, hd):
    q, pk, pv, table, lengths = _attn_case(
        jax.random.PRNGKey(3), B, S, H, Hkv, hd, page_size=4,
        pages_per_slot=4)
    ref = kops.paged_attention(q, pk, pv, table, lengths, mode="ref")
    out = kops.paged_attention(q, pk, pv, table, lengths, mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_row_tiles_match_ref():
    """A prefill chunk of S*H = 1024 query rows runs as two row tiles of
    512, each with its own online-softmax state, over 8 kv heads."""
    q, pk, pv, table, lengths = _attn_case(
        jax.random.PRNGKey(10), 2, 64, 16, 8, 16, page_size=4,
        pages_per_slot=20)
    ref = kops.paged_attention(q, pk, pv, table, lengths, mode="ref")
    out = kops.paged_attention(q, pk, pv, table, lengths, mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_ignores_garbage_beyond_length():
    """Pool rows past each slot's causal frontier carry exactly-zero
    probability mass: poisoning them with huge values must not move a
    single output bit (this is what makes page-table truncation and
    uninitialized pool rows safe)."""
    q, pk, pv, table, lengths = _attn_case(
        jax.random.PRNGKey(4), 2, 1, 2, 2, 16, page_size=4,
        pages_per_slot=4)
    clean = kops.paged_attention(q, pk, pv, table, lengths, mode="ref")
    page_size = pk.shape[1]
    rows = (np.asarray(table)[:, :, None] * page_size
            + np.arange(page_size)).reshape(2, -1)   # physical row of pos j
    cap = rows.shape[1]
    dead = np.arange(cap)[None, :] > np.asarray(lengths)[:, None]  # > qpos
    pk_flat = np.array(pk).reshape(-1, *pk.shape[2:])
    pv_flat = np.array(pv).reshape(-1, *pv.shape[2:])
    for b in range(2):
        pk_flat[rows[b][dead[b]]] = 1e30
        pv_flat[rows[b][dead[b]]] = 1e30
    poisoned = kops.paged_attention(
        q, jnp.asarray(pk_flat).reshape(pk.shape),
        jnp.asarray(pv_flat).reshape(pv.shape), table, lengths, mode="ref")
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))


def test_paged_attention_truncated_table_preserves_output():
    """Slicing the page table to the live-page bucket (the fused path's
    speed lever, serve/cache.CacheBackend._table_view) is exact: dropping
    columns no slot has reached leaves outputs bit-identical."""
    q, pk, pv, table, lengths = _attn_case(
        jax.random.PRNGKey(5), 2, 1, 2, 2, 16, page_size=4,
        pages_per_slot=4)
    full = kops.paged_attention(q, pk, pv, table, lengths, mode="ref")
    cut = kops.paged_attention(q, pk, pv, table[:, :2], lengths, mode="ref")
    np.testing.assert_array_equal(np.asarray(cut), np.asarray(full))


# ---------------------------------------------------------------------------
# Kernel tier: paged SSM update
# ---------------------------------------------------------------------------


def _ssm_case(key, B, S, R, ds, page_size, pages_per_slot, lengths, n_new):
    ks = jax.random.split(key, 6)
    n_pages = 1 + B * pages_per_slot
    dt = jax.nn.softplus(rand(ks[0], (B, S, R))) * 0.2
    x = rand(ks[1], (B, S, R))
    Bm, Cm = rand(ks[2], (B, S, ds)), rand(ks[3], (B, S, ds))
    A = -jnp.exp(rand(ks[4], (R, ds)))
    h_pool = rand(ks[5], (n_pages, R, ds))
    table = jnp.asarray(
        (1 + np.arange(B * pages_per_slot)).reshape(B, pages_per_slot),
        jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    n_new = jnp.asarray(n_new, jnp.int32)
    t_w, phys_w = compact_snapshot_steps(table, lengths, n_new, page_size, S)
    read_page, live = paged_read_plan(table, lengths, page_size)
    return (dt, x, Bm, Cm, A, h_pool, read_page, live, phys_w, t_w, n_new)


@pytest.mark.parametrize("order", ["dbx", "dxb"])
@pytest.mark.parametrize("S,lengths,n_new", [
    (1, [3, 0], [1, 1]),     # decode step; slot 0 crosses a page boundary
    (4, [2, 5], [4, 0]),     # chunked prefill + an idle slot
    (1, [0, 7], [1, 1]),     # empty slot (no live read page)
])
def test_paged_ssm_update_interpret_matches_ref(order, S, lengths, n_new):
    args = _ssm_case(jax.random.PRNGKey(6), 2, S, R=8, ds=4, page_size=4,
                     pages_per_slot=3, lengths=lengths, n_new=n_new)
    y_ref, pool_ref = kops.paged_ssm_update(*args, order=order, mode="ref")
    y_int, pool_int = kops.paged_ssm_update(*args, order=order,
                                            mode="interpret")
    # outputs at padded positions (>= n_new) are unspecified — the serve
    # step reads position n_new-1 only, so conformance covers valid rows
    valid = (np.arange(S)[None, :] < np.asarray(n_new)[:, None])[..., None]
    np.testing.assert_allclose(np.asarray(y_int) * valid,
                               np.asarray(y_ref) * valid,
                               rtol=1e-5, atol=1e-6)
    # pools must agree except scratch page 0, where idle slots' discarded
    # snapshots land in unspecified duplicate-scatter order
    np.testing.assert_allclose(np.asarray(pool_int)[1:],
                               np.asarray(pool_ref)[1:],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("order", ["dbx", "dxb"])
def test_paged_ssm_update_row_tiles_match_ref(order):
    """R = 1024 rows run as two tiles of 512 (rows are independent
    recurrences); a slot crossing a page boundary mid-chunk."""
    S, lengths, n_new = 4, [3, 5], [4, 2]
    args = _ssm_case(jax.random.PRNGKey(11), 2, S, R=1024, ds=4,
                     page_size=4, pages_per_slot=3, lengths=lengths,
                     n_new=n_new)
    y_ref, pool_ref = kops.paged_ssm_update(*args, order=order, mode="ref")
    y_int, pool_int = kops.paged_ssm_update(*args, order=order,
                                            mode="interpret")
    valid = (np.arange(S)[None, :] < np.asarray(n_new)[:, None])[..., None]
    np.testing.assert_allclose(np.asarray(y_int) * valid,
                               np.asarray(y_ref) * valid,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pool_int)[1:],
                               np.asarray(pool_ref)[1:],
                               rtol=1e-5, atol=1e-6)


def test_paged_ssm_update_touches_only_planned_pages():
    """The compact write plan is what it claims: pages outside phys_w
    (and scratch) come back bit-identical — idle slots' state survives."""
    args = _ssm_case(jax.random.PRNGKey(7), 2, 1, R=8, ds=4, page_size=4,
                     pages_per_slot=3, lengths=[3, 6], n_new=[1, 0])
    h_pool, phys_w = args[5], args[8]
    _, new_pool = kops.paged_ssm_update(*args, order="dbx", mode="ref")
    planned = set(np.asarray(phys_w).reshape(-1).tolist()) | {0}
    for page in range(h_pool.shape[0]):
        if page not in planned:
            np.testing.assert_array_equal(np.asarray(new_pool[page]),
                                          np.asarray(h_pool[page]))


# ---------------------------------------------------------------------------
# Kernel tier: sort-free sampling mask
# ---------------------------------------------------------------------------


def _sampling_case(key, B=4, V=128):
    logits = rand(key, (B, V)) * 3.0
    top_ks = jnp.asarray([0, 5, 1, V], jnp.int32)[:B]
    top_ps = jnp.asarray([1.0, 0.9, 0.5, 0.73], jnp.float32)[:B]
    return logits, top_ks, top_ps


def test_topk_topp_mask_matches_sort_based_masking():
    """The binary-search mask must reproduce the sort-based
    launch.steps.apply_top_k_top_p bit-for-bit: same survivor set, same
    untouched survivor logits, same -1e30 fill — this equality is why the
    serve sampler can swap implementations without changing any stream."""
    from repro.launch.steps import apply_top_k_top_p
    logits, top_ks, top_ps = _sampling_case(jax.random.PRNGKey(8))
    got = kops.topk_topp_mask(logits, top_ks, top_ps, mode="ref")
    want = apply_top_k_top_p(logits, top_ks, top_ps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("V", [128, 200])     # 200: padded to 256 lanes
def test_topk_topp_mask_interpret_matches_ref(V):
    logits, top_ks, top_ps = _sampling_case(jax.random.PRNGKey(9), V=V)
    ref = kops.topk_topp_mask(logits, top_ks, top_ps, mode="ref")
    out = kops.topk_topp_mask(logits, top_ks, top_ps, mode="interpret")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# Serve tier: fused engine == gathered engine, per backend family
# ---------------------------------------------------------------------------

GREEDY_REQS = [(np.array([5, 9, 3, 7, 2, 11], np.int32), 8),
               (np.array([1, 2, 3], np.int32), 6),
               (np.array([4], np.int32), 5)]
SAMPLED_REQS = [
    (np.array([5, 9, 3, 7, 2], np.int32), 7,
     dict(temperature=1.1, top_k=16, top_p=0.9, seed=7)),
    (np.array([4, 2, 9], np.int32), 6,
     dict(temperature=0.8, top_k=0, top_p=0.7, seed=123)),
    (np.array([8], np.int32), 6, dict(temperature=1.5, seed=1)),
]


@pytest.mark.parametrize("name", sorted(FAMILY_MODELS))
def test_fused_greedy_bitwise_equals_gathered(name):
    """Acceptance criterion: temperature-0 fused decode is token-for-token
    the gathered-view engine on every backend family — mixed prompt
    lengths, continuous batching, page-boundary crossings included."""
    rcfg, params, _ = family_setup(name)
    kw = dict(max_len=MAX_LEN, max_batch=2, page_size=4)
    _, ref = engine_outputs(rcfg, params, GREEDY_REQS, fused=False, **kw)
    _, got = engine_outputs(rcfg, params, GREEDY_REQS, **kw)
    for a, b in zip(ref, got, strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", sorted(FAMILY_MODELS))
def test_fused_sampled_stream_equals_gathered(name):
    """Sampled requests share the (seed, tokens_emitted) key schedule, so
    the fused sampler epilogue must reproduce the gathered engine's
    streams exactly — masking is bitwise, Gumbel keys are unchanged."""
    rcfg, params, _ = family_setup(name)
    kw = dict(max_len=MAX_LEN, max_batch=2, page_size=4)
    _, ref = engine_outputs(rcfg, params, SAMPLED_REQS, fused=False, **kw)
    _, got = engine_outputs(rcfg, params, SAMPLED_REQS, **kw)
    for a, b in zip(ref, got, strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", ["decoder", "ssm_mamba2", "hybrid"])
def test_spec_decode_unchanged_by_fused_step(name):
    """Spec decode over the fused engine: the draft wave runs the fused
    step, the verify wave stays full-width/unfused by design — greedy
    output must still equal the plain fused engine's bitwise."""
    from repro.serve.spec import SpecConfig
    rcfg, params, _ = family_setup(name)
    kw = dict(max_len=MAX_LEN, max_batch=2, page_size=4)
    _, ref = engine_outputs(rcfg, params, GREEDY_REQS, **kw)
    eng, got = engine_outputs(rcfg, params, GREEDY_REQS,
                              spec=SpecConfig(cf=2, k=3), **kw)
    for a, b in zip(ref, got, strict=True):
        np.testing.assert_array_equal(a, b)
    assert eng.stats["tokens_drafted"] > 0


def test_table_view_slices_to_live_page_bucket():
    """Host-side speed lever: _table_view hands the jitted step a
    power-of-two page-table slice covering every live slot, so shallow
    batches never pay full-capacity attention width (and the trace count
    stays <= log2(P)+1)."""
    rcfg, params, _ = family_setup("decoder")
    eng = ServeEngine(rcfg, params, max_len=MAX_LEN, max_batch=2,
                      page_size=4)
    backend = eng.scheduler.backend
    table = np.zeros((2, 8), np.int32)      # capacity: 8 pages of 4

    def width(lengths, n_new):
        from repro.serve.cache import SlotBatch
        slots = SlotBatch.greedy(
            2, table, lengths=np.asarray(lengths, np.int32),
            n_new=np.asarray(n_new, np.int32))
        return backend._table_view(slots).shape[1]

    assert width([0, 0], [1, 1]) == 1       # first token: 1 page
    assert width([4, 2], [1, 1]) == 2       # deepest slot on page 2
    assert width([9, 1], [1, 1]) == 4       # 10 rows -> 3 pages -> pow2 4
    assert width([26, 0], [1, 1]) == 8      # near capacity: full table
    assert width([31, 0], [1, 0]) == 8      # never beyond capacity

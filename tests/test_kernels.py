"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping
shapes/dtypes per the assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rmsnorm import rmsnorm_2d
from repro.kernels.ssm_scan import ssm_scan


def rand(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.5).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,Sq,Sk,hd,causal", [
    (1, 4, 4, 128, 128, 64, True),
    (2, 4, 2, 128, 128, 32, True),     # GQA
    (1, 8, 1, 256, 256, 64, True),     # MQA
    (1, 2, 2, 128, 256, 64, False),    # cross-ish, non-causal
    (2, 2, 2, 384, 384, 128, True),    # 3 q blocks, hd=128
])
def test_flash_attention_matches_ref(dtype, B, H, Hkv, Sq, Sk, hd, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, H, Sq, hd), dtype)
    k = rand(ks[1], (B, Hkv, Sk, hd), dtype)
    v = rand(ks[2], (B, Hkv, Sk, hd), dtype)
    out = flash_attention_bhsd(q, k, v, causal=causal, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("R,D,rb", [(256, 512, 128), (512, 128, 256),
                                    (64, 2048, 64)])
def test_rmsnorm_matches_ref(dtype, R, D, rb):
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = rand(ks[0], (R, D), dtype)
    w = jnp.ones((D,), jnp.float32) + rand(ks[1], (D,), jnp.float32) * 0.1
    out = rmsnorm_2d(x, w, row_block=rb, interpret=True)
    expect = ref.rmsnorm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Bb,S,di,ds,chunk", [
    (2, 128, 64, 16, 32),
    (1, 64, 128, 8, 64),
    (2, 256, 32, 16, 128),
])
def test_ssm_scan_matches_ref(dtype, Bb, S, di, ds, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    dt = jax.nn.softplus(rand(ks[0], (Bb, S, di), jnp.float32)) * 0.2
    x = rand(ks[1], (Bb, S, di), dtype)
    A = -jnp.exp(rand(ks[2], (di, ds), jnp.float32))
    B = rand(ks[3], (Bb, S, ds), dtype)
    C = rand(ks[4], (Bb, S, ds), dtype)
    D = jnp.ones((di,), jnp.float32)
    out = ssm_scan(dt.astype(dtype), x, A, B, C, D, chunk=chunk,
                   interpret=True)
    expect = ref.ssm_scan_ref(dt.astype(dtype), x, A, B, C, D)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_model_layout():
    """ops.flash_attention consumes the model's (B,S,H,hd) layout and matches
    the model's dense reference path."""
    from repro.kernels import ops
    from repro.models.attention import dot_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (2, 128, 4, 32), jnp.float32)
    k = rand(ks[1], (2, 128, 2, 32), jnp.float32)
    v = rand(ks[2], (2, 128, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    expect = dot_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def _bhsd(x):
    return x.transpose(0, 2, 1, 3)


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("H,Hkv,hd,layers", [
    (4, 4, 64, 0),
    (4, 2, 128, 0),                    # GQA
    (4, 2, 64, 3),                     # under vmap over a layer axis
])
def test_flash_attention_vjp_matches_dense(causal, H, Hkv, hd, layers):
    """The fused kernels' output and dQ, dK, dV match ``jax.vjp`` of
    ``dot_attention`` on the same bf16 values in float32: 256 tokens in
    2 x 2 blocks of 128, so the causal skip and the diagonal mask are
    both crossed. ``layers`` maps the call over a leading layer axis, as
    the layer-parallel solver's F-relaxation and parameter VJPs do."""
    from repro.models.attention import dot_attention
    B, S = 2, 256
    lead = (layers,) if layers else ()
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = rand(ks[0], lead + (B, S, H, hd), jnp.bfloat16)
    k = rand(ks[1], lead + (B, S, Hkv, hd), jnp.bfloat16)
    v = rand(ks[2], lead + (B, S, Hkv, hd), jnp.bfloat16)
    do = rand(ks[3], lead + (B, S, H, hd), jnp.bfloat16)

    def fused(q, k, v):
        return _bhsd(flash_attention_bhsd(
            _bhsd(q), _bhsd(k), _bhsd(v), causal=causal, q_block=128,
            k_block=128, interpret=True))

    def dense(q, k, v):
        return dot_attention(q, k, v, causal=causal)

    if layers:
        fused, dense = jax.vmap(fused), jax.vmap(dense)
    o, vjp = jax.vjp(fused, q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    o_ref, vjp_ref = jax.vjp(dense, *f32[:3])
    assert o.dtype == jnp.bfloat16
    for got, want in zip((o,) + vjp(do), (o_ref,) + vjp_ref(f32[3])):
        assert got.shape == want.shape
        scale = float(jnp.max(jnp.abs(want)))
        assert _max_err(got, want) <= 2e-2 * scale, (_max_err(got, want),
                                                      scale)


def test_attention_path_counter():
    """``attention_apply`` takes the fused kernels for self-attention with
    ``use_pallas`` over a length the blocks divide, and the dense core for a
    cached call, a cross-attention call and a length off the blocks; the
    counter in ``repro.obs.profile`` records each."""
    from repro.configs import registry
    from repro.configs.reduce import reduce_config
    from repro.models.attention import (attention_apply, init_attention,
                                        init_kv_cache)
    from repro.obs import profile

    cfg = reduce_config(registry.get_config("gpt2_nanogpt")).model
    params = init_attention(jax.random.PRNGKey(5), cfg)
    cross = init_attention(jax.random.PRNGKey(6), cfg, cross=True)

    def path_of(fn, *args):
        before = dict(profile.ATTENTION_PATHS)
        jax.eval_shape(fn, *args)
        taken = {p: n - before[p] for p, n in profile.ATTENTION_PATHS.items()
                 if n != before[p]}
        assert list(taken.values()) == [1], taken
        return next(iter(taken))

    def x(S):
        return jnp.zeros((2, S, cfg.d_model), jnp.float32)

    def self_attn(x):
        return attention_apply(params, x, cfg, causal=True,
                               positions=jnp.arange(x.shape[1]),
                               use_pallas=True)[0]

    cache = init_kv_cache(cfg, 2, 64, 1)
    cache = {"k": cache["k"][0], "v": cache["v"][0],
             "index": cache["index"]}

    def cached(x):
        return attention_apply(params, x, cfg, causal=True,
                               positions=jnp.arange(x.shape[1]),
                               cache=cache, use_pallas=True)[0]

    def cross_attn(x, xa):
        return attention_apply(cross, x, cfg, causal=False, xa=xa,
                               use_pallas=True)[0]

    assert path_of(self_attn, x(128)) == "fused"
    assert path_of(cached, x(8)) == "dense"
    assert path_of(cross_attn, x(128), x(128)) == "dense"
    assert path_of(self_attn, x(200)) == "dense"


def test_flash_attention_mode_keeps_the_dense_core_under_a_mesh(
        monkeypatch):
    """The fused kernels run in interpret mode on the CPU where
    ``use_pallas`` asks, compiled under ``REPRO_KERNEL_MODE=pallas`` (a
    compile for a described chip), and not at all under a mesh of more
    than one device, where GSPMD cannot partition a Mosaic kernel."""
    from jax.sharding import AbstractMesh

    from repro.configs.base import ShardingConfig
    from repro.kernels import ops as kops
    from repro.parallel.sharding import axis_rules

    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    assert kops.flash_attention_mode(False) is None
    assert kops.flash_attention_mode(True) == "interpret"
    one = AbstractMesh((1, 1), ("data", "model"))
    four = AbstractMesh((1, 4), ("data", "model"))
    with axis_rules(one, ShardingConfig()):
        assert kops.flash_attention_mode(True) == "interpret"
    with axis_rules(four, ShardingConfig()):
        assert kops.flash_attention_mode(True) is None
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    assert kops.flash_attention_mode(False) == "pallas"
    with axis_rules(four, ShardingConfig()):
        assert kops.flash_attention_mode(False) is None

"""Gradient correctness of the layer-parallel custom VJP.

Oracle: direct jax.grad through the exact serial scan. The serial-mode
lp_forward (fwd_iters=bwd_iters=0, i.e. the discrete adjoint) must match it
to numerical precision; MGRIT-mode gradients must converge to it as the
iteration counts grow (the paper's controllable inexactness)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.reduce import reduce_config
from repro.core import lp, mgrit
from repro.core.lp import LPStatic, lp_forward
from repro.models import transformer


def tiny_rcfg(fwd_iters, bwd_iters):
    rcfg = reduce_config(registry.get_config("deepseek_7b"))
    mg = dataclasses.replace(rcfg.mgrit, fwd_iters=fwd_iters,
                             bwd_iters=bwd_iters)
    return dataclasses.replace(rcfg, mgrit=mg)


def setup(key, rcfg):
    params = transformer.init_model(key, rcfg)
    toks = jax.random.randint(jax.random.fold_in(key, 1), (2, 8), 0,
                              rcfg.model.vocab_size)
    labels = jax.random.randint(jax.random.fold_in(key, 2), (2, 8), 0,
                                rcfg.model.vocab_size)
    return params, {"tokens": toks, "labels": labels}


def _flat(tree):
    return jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


def test_serial_adjoint_matches_direct_ad():
    """Exact adjoint (iters=0) == autodiff through the serial scan."""
    rcfg = tiny_rcfg(0, 0)
    key = jax.random.PRNGKey(0)
    params, batch = setup(key, rcfg)

    def loss_adjoint(p):
        return transformer.loss_fn(p, batch, rcfg, mode="serial")[0]

    # direct-AD oracle: same forward, but differentiate *through* the scan
    def loss_direct(p):
        static = LPStatic(cfg=rcfg.model, mgrit=rcfg.mgrit, kind="attn_mlp",
                          causal=True)
        from repro.models.layers import rope_freqs
        from repro.models.transformer import (_embed_inputs, _serial_buffer,
                                              lm_loss)
        from repro.models.layers import norm_apply, unembed
        cfg = rcfg.model
        z = _embed_inputs(p, batch, cfg)
        rope = rope_freqs(cfg.resolved_head_dim, cfg.rope_theta,
                          jnp.arange(8, dtype=jnp.int32))
        z = _serial_buffer(p.get("open"), z, cfg, kind="attn_mlp",
                           causal=True, rope=rope)
        step = lp.make_fwd_step(static, {"rope": rope})
        _, zT = mgrit.serial_solve(step, p["mid"], z, rcfg.mgrit.h)
        zT = _serial_buffer(p.get("close"), zT, cfg, kind="attn_mlp",
                            causal=True, rope=rope)
        zT = norm_apply(p["final_norm"], zT, cfg)
        return lm_loss(unembed(p["embed"], zT, cfg), batch["labels"])

    la, ga = jax.value_and_grad(loss_adjoint)(params)
    ld, gd = jax.value_and_grad(loss_direct)(params)
    np.testing.assert_allclose(float(la), float(ld), rtol=1e-5)
    # the gates are structural constants: the adjoint returns zero for them
    # by design, so zero them in the direct-AD oracle as well
    gd["mid"]["gate"] = jnp.zeros_like(gd["mid"]["gate"])
    ga["mid"]["gate"] = jnp.zeros_like(ga["mid"]["gate"])
    fa, fd = np.asarray(_flat(ga)), np.asarray(_flat(gd))
    # the adjoint reassociates reductions; in bf16 compute that leaves
    # ~1e-2 relative noise — check direction + magnitude agreement
    cos = float(np.dot(fa, fd)
                / (np.linalg.norm(fa) * np.linalg.norm(fd) + 1e-30))
    assert cos > 0.9999, f"cosine {cos}"
    np.testing.assert_allclose(np.linalg.norm(fa), np.linalg.norm(fd),
                               rtol=1e-2)
    np.testing.assert_allclose(fa, fd, rtol=5e-2, atol=5e-3)


@pytest.mark.parametrize("iters,min_cos", [(1, 0.90), (4, 0.999)])
def test_mgrit_grads_converge_to_exact(iters, min_cos):
    key = jax.random.PRNGKey(1)
    rcfg_exact = tiny_rcfg(0, 0)
    params, batch = setup(key, rcfg_exact)
    g_exact = jax.grad(
        lambda p: transformer.loss_fn(p, batch, rcfg_exact, mode="serial")[0]
    )(params)

    rcfg_lp = tiny_rcfg(iters, iters)
    g_lp = jax.grad(
        lambda p: transformer.loss_fn(p, batch, rcfg_lp, mode="lp")[0]
    )(params)

    fe, fl = _flat(g_exact), _flat(g_lp)
    cos = float(jnp.dot(fe, fl)
                / (jnp.linalg.norm(fe) * jnp.linalg.norm(fl) + 1e-30))
    assert cos > min_cos, f"cosine {cos} too low at iters={iters}"


def test_padded_layers_receive_zero_grads():
    rcfg = tiny_rcfg(1, 1)
    # force real padding: 8 mid layers padded to 12
    rcfg = dataclasses.replace(
        rcfg, mgrit=dataclasses.replace(rcfg.mgrit, pad_to=12, cf=2))
    key = jax.random.PRNGKey(2)
    params, batch = setup(key, rcfg)
    grads = jax.grad(
        lambda p: transformer.loss_fn(p, batch, rcfg, mode="lp")[0])(params)
    gate = np.asarray(params["mid"]["gate"])
    pad_idx = np.where(gate == 0.0)[0]
    assert pad_idx.size > 0
    for leaf in jax.tree.leaves(grads["mid"]["params"]):
        arr = np.asarray(leaf, np.float32)
        assert np.allclose(arr[pad_idx], 0.0), "padded layer got gradient"


def test_fwd_residual_norms_exposed():
    rcfg = tiny_rcfg(3, 1)
    key = jax.random.PRNGKey(3)
    params, batch = setup(key, rcfg)
    _, diag = transformer.loss_fn(params, batch, rcfg, mode="lp")
    norms = np.asarray(diag["fwd_norms"])
    assert norms.shape == (3,)
    assert np.all(np.isfinite(norms))


def test_lp_step_through_fused_attention():
    """The LP loss and gradients of a small GPT-2 with ``use_pallas``
    (the flash-attention kernels in interpret mode, vmapped over the trunk
    by the solver) match the dense attention core within bf16 tolerance,
    and every attention call of the step takes the fused path."""
    from repro.obs import profile
    base = reduce_config(registry.get_config("gpt2_nanogpt"), seq=64)
    base = dataclasses.replace(base, mgrit=dataclasses.replace(
        base.mgrit, fwd_iters=0, bwd_iters=1))
    fused = dataclasses.replace(base, use_pallas=True)
    key = jax.random.PRNGKey(4)
    params = transformer.init_model(key, base)
    toks = jax.random.randint(jax.random.fold_in(key, 1), (2, 64), 0,
                              base.model.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    def loss_and_grad(rcfg):
        return jax.jit(jax.value_and_grad(
            lambda p: transformer.loss_fn(p, batch, rcfg, mode="lp")[0]
        ))(params)

    before = dict(profile.ATTENTION_PATHS)
    lf, gf = loss_and_grad(fused)
    taken = {p: n - before[p] for p, n in profile.ATTENTION_PATHS.items()}
    assert taken["fused"] > 0 and taken["dense"] == 0, taken
    ld, gd = loss_and_grad(base)
    np.testing.assert_allclose(float(lf), float(ld), rtol=2e-3)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gd)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
        assert gap < 3e-2, (a.shape, gap)
    fa, fd = np.asarray(_flat(gf)), np.asarray(_flat(gd))
    cos = float(np.dot(fa, fd)
                / (np.linalg.norm(fa) * np.linalg.norm(fd) + 1e-30))
    assert cos > 0.9999, f"cosine {cos}"

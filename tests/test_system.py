"""End-to-end system tests: training with adaptive switching, serving,
elastic checkpoint restore, and sharding-rule coherence (subprocess with a
forced multi-device host platform)."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.base import (MGRITConfig, ModelConfig, OptimizerConfig,
                                RunConfig, ShapeConfig)
from repro.serve.engine import Request, ServeEngine
from repro.models import transformer
from repro.train.trainer import Trainer


def tiny_rcfg(lp=True, **mg_kw):
    model = ModelConfig(name="sys", family="decoder", n_layers=8, d_model=32,
                        n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                        act="gelu", norm="layernorm")
    mg = dict(enabled=lp, cf=2, levels=2, fwd_iters=1, bwd_iters=1,
              pad_to=8, check_every=5)
    mg.update(mg_kw)
    return RunConfig(model=model, mgrit=MGRITConfig(**mg),
                     optimizer=OptimizerConfig(name="sgd", lr=0.05,
                                               warmup_steps=2,
                                               total_steps=50),
                     shape=ShapeConfig("sys", "train", 16, 4))


def test_adaptive_switch_forced_by_threshold():
    """With threshold 0 the first probe must switch LP -> serial and the
    run must continue to train (the paper's Fig. 4 green-curve mechanism)."""
    rcfg = tiny_rcfg(switch_threshold=0.0)
    tr = Trainer(rcfg, seed=0)
    rep = tr.train(12, log_every=0, probe=True)
    assert rep.switched_at is not None
    assert rep.mode_trace[-1] == "serial"
    assert rep.mode_trace[0] == "lp"
    assert np.isfinite(rep.losses).all()


def test_serve_engine_generates():
    rcfg = tiny_rcfg()
    params = transformer.init_model(jax.random.PRNGKey(0), rcfg)
    eng = ServeEngine(rcfg, params, max_len=32)
    reqs = [Request(prompt=np.array([1, 2, 3], np.int32), max_new_tokens=4),
            Request(prompt=np.array([5, 6], np.int32), max_new_tokens=4)]
    out = eng.generate(reqs)
    for r in out:
        assert r.output.shape == (4,)
        assert ((r.output >= 0) & (r.output < 64)).all()


def test_elastic_restore_roundtrip():
    """A checkpoint written under one run restores into a fresh trainer
    (the elastic path stores logical arrays; mesh-specific placement is
    re-derived)."""
    from repro.train import checkpoint as ckpt
    rcfg = tiny_rcfg()
    tr = Trainer(rcfg, seed=0)
    tr.train(3, log_every=0, probe=False)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 3, tr.params, tr.opt_state)
        restored = ckpt.restore(d, tr.params, tr.opt_state)
        assert restored is not None
        p2, o2, step, _ = restored
        assert step == 3
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(tr.params)[0]),
            np.asarray(jax.tree.leaves(p2)[0]))


_SHARDING_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, json
    import numpy as np
    from repro.configs import registry
    from repro.launch import specs as specs_mod
    from repro.launch.mesh import make_mesh
    from repro.parallel.params import param_specs, batch_specs

    mesh = make_mesh((2, 4), ("data", "model"))
    out = {}
    for arch in ("deepseek_7b", "qwen3_moe_235b", "falcon_mamba_7b"):
        rcfg = registry.get_config(arch, "train_4k")
        params = specs_mod.params_specs(rcfg)
        ps = param_specs(params, rcfg, mesh)
        flat, _ = jax.tree_util.tree_flatten_with_path(ps)
        layer_sharded = 0
        for path, s in flat:
            spec = s.spec
            if len(spec) and spec[0] == "model":
                layer_sharded += 1
        out[arch] = layer_sharded
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_sharding_rules_subprocess():
    """param_specs shards the stacked trunk over 'model' for LP archs
    (verified on a real 8-device host mesh in a subprocess)."""
    r = subprocess.run([sys.executable, "-c", _SHARDING_SCRIPT],
                       capture_output=True, text=True, cwd=os.getcwd(),
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # every LP arch must shard a substantial number of trunk leaves on the
    # layers->model axis
    for arch, n in out.items():
        assert n >= 5, f"{arch}: only {n} layer-sharded leaves"


_MESH_TRAIN_SCRIPT = textwrap.dedent("""
    import os
    import sys
    sys.path.insert(0, "src")
    from repro.launch.hostdev import force_host_device_count
    force_host_device_count(4)        # before jax's backend comes up
    import json
    import jax
    from repro.configs import registry
    from repro.configs.reduce import reduce_config
    from repro.launch.mesh import make_mesh
    from repro.train.trainer import Trainer

    rcfg = reduce_config(registry.get_config("gpt2_nanogpt"))
    mesh = make_mesh((1, 4), ("data", "model"))
    tr = Trainer(rcfg, mesh=mesh, seed=0)
    rep = tr.train(2, log_every=0, probe=False)
    flat = jax.tree_util.tree_flatten_with_path(tr.params["mid"])[0]
    solo = Trainer(rcfg, seed=0).train(1, log_every=0, probe=False)
    print(json.dumps({
        "devices": jax.device_count(),
        "losses": rep.losses, "solo_loss": solo.losses[0],
        "mid_specs": [list(map(str, leaf.sharding.spec))
                      for _, leaf in flat],
        "opt_mid_specs": [list(map(str, leaf.sharding.spec)) for leaf in
                          jax.tree.leaves(tr.opt_state["m"]["mid"])]}))
""")


def test_trainer_places_trunk_on_model_axis_under_mesh():
    """Layer-parallel training on a (data=1, model=4) mesh of 4 host
    devices (the CPU rehearsal of ``chip_smoke.py --four-chips``): every
    stacked trunk leaf, and its optimizer moment, carries 'model' on the
    layer axis after two steps, and the first loss equals the unsharded
    trainer's from the same seed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)        # the script pins its own device count
    r = subprocess.run([sys.executable, "-c", _MESH_TRAIN_SCRIPT],
                       capture_output=True, text=True, cwd=os.getcwd(),
                       env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert np.isfinite(out["losses"]).all()
    np.testing.assert_allclose(out["losses"][0], out["solo_loss"], rtol=1e-5)
    for specs in (out["mid_specs"], out["opt_mid_specs"]):
        assert specs and all(s[0] == "model" for s in specs), specs


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_env_or_fixed_checkout_path(monkeypatch, tmp_path,
                                                  from_env):
    """JAX_COMPILATION_CACHE_DIR, where set, is left to JAX and no other
    cache is set; otherwise the cache is <checkout>/.jax_cache."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if from_env:
        assert got == str(tmp_path) and now == before
    else:
        checkout = compile_cache.CACHE_DIR.parent
        assert (checkout / "src" / "repro" / "launch").is_dir()
        assert got == now == str(checkout / ".jax_cache")


def test_train_cli_reduced():
    from repro.launch import train as train_cli
    rc = train_cli.main(["--arch", "qwen3_1p7b", "--reduced",
                         "--steps", "2"])
    assert rc == 0

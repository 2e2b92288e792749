"""Mesh-sharded serving conformance (ISSUE 5 acceptance).

On a 2-device host mesh (``--xla_force_host_platform_device_count=2``,
forced in a subprocess because the parent's jax is already initialized
single-device), temp-0 paged decode must be **token-for-token identical**
to the single-device dense serial oracle for every backend family, under
both a data-parallel split (pages/batch over 'data') and a
tensor-parallel split (weights/heads over 'model') — and sampled
requests must reproduce the oracle stream too (the sampler keys off
(seed, n_emitted) only, so placement can't change it). One spec-decode
run asserts greedy spec == plain paged decode under tp.

The engines under test are real ``ServeEngine``s built with
``mesh=make_mesh((dp, tp), ("data", "model"))`` (``repro.launch.mesh``) — the same
scheduler/allocator/trie paths as single-device serving; only the jitted
calls go SPMD (serve/cache.CacheBackend, docs/sharding.md).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.serve]

_MESH_SCRIPT = textwrap.dedent("""
    import os
    import sys
    sys.path.insert(0, "src")
    sys.path.insert(0, "tests")
    from repro.launch.hostdev import force_host_device_count
    force_host_device_count(2)        # before jax's backend comes up
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json
    import jax
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.models import transformer
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.spec import SpecConfig
    from serve_oracle import dense_decode_oracle
    from test_serve_backends import FAMILY_MODELS, MAX_LEN, family_rcfg

    FAMILIES = ("decoder", "ssm_mamba1", "hybrid")
    out = {"devices": jax.device_count(), "mismatch": []}

    def reqs():
        return [Request(prompt=np.array([5, 9, 3, 7, 2], np.int32),
                        max_new_tokens=5),
                Request(prompt=np.array([4, 2, 9], np.int32),
                        max_new_tokens=5, temperature=1.1, top_k=16,
                        top_p=0.9, seed=7)]

    for name in FAMILIES:
        rcfg = family_rcfg(name)
        params = transformer.init_model(
            jax.random.PRNGKey(sum(map(ord, name)) % 1000), rcfg)
        step = jax.jit(lambda p, c, t, _r=rcfg: transformer.decode_step(
            p, c, t, _r))
        refs = [dense_decode_oracle(rcfg, params, step, r, MAX_LEN)
                for r in reqs()]
        for dp, tp in ((2, 1), (1, 2)):
            mesh = make_mesh((dp, tp), ("data", "model"))
            eng = ServeEngine(rcfg, params, mesh=mesh, max_len=MAX_LEN,
                              max_batch=2, page_size=4)
            got = eng.generate(reqs())
            for i, (g, ref) in enumerate(zip(got, refs)):
                if not np.array_equal(g.output, ref):
                    out["mismatch"].append(
                        [name, f"dp{dp}xtp{tp}", i,
                         list(map(int, g.output)), list(map(int, ref))])
            st = eng.stats
            out[f"{name}_dp{dp}tp{tp}"] = [st["mesh_dp"], st["mesh_tp"]]
            if dp > 1:
                # the pool page axis (axis 1) must actually shard over
                # 'data' — pool_pages rounds the default size to make
                # the mapping divisible rather than silently replicate
                specs = [getattr(leaf.sharding, "spec", ())
                         for leaf in jax.tree.leaves(eng.scheduler.state)]
                out[f"{name}_pool_dp_sharded"] = any(
                    len(s) > 1 and s[1] == "data" for s in specs)

    # fused paged-decode path under the 2-device mesh (ISSUE 6): the
    # engines above already run fused by default and match the oracle;
    # here an explicit fused/gathered pair under both splits pins the
    # flag itself, so a silent fused=False regression can't hide behind
    # oracle equality
    out["fused_flag"] = []
    out["fused_mesh_mismatch"] = []
    for dp, tp in ((2, 1), (1, 2)):
        mesh = make_mesh((dp, tp), ("data", "model"))
        for name in ("decoder", "ssm_mamba1"):
            rcfg = family_rcfg(name)
            params = transformer.init_model(
                jax.random.PRNGKey(sum(map(ord, name)) % 1000), rcfg)
            kw = dict(max_len=MAX_LEN, max_batch=2, page_size=4)
            ef = ServeEngine(rcfg, params, mesh=mesh, **kw)
            eg = ServeEngine(rcfg, params, mesh=mesh, fused=False, **kw)
            out["fused_flag"].append(
                [bool(ef.scheduler.backend.fused),
                 bool(eg.scheduler.backend.fused)])
            for i, (a, b) in enumerate(zip(ef.generate(reqs()),
                                           eg.generate(reqs()))):
                if not np.array_equal(a.output, b.output):
                    out["fused_mesh_mismatch"].append(
                        [name, f"dp{dp}xtp{tp}", i,
                         list(map(int, a.output)), list(map(int, b.output))])

    # spec decode under tp: greedy spec == greedy plain, bitwise — ssm
    # covers the stacked snapshot-pool commit constraints
    # (ssm_paged_commit_step) inside the SPMD verify call, hybrid the
    # composite in-line-KV + deferred-snapshot commit path
    out["spec_drafted"] = 0
    mesh = make_mesh((1, 2), ("data", "model"))
    for name in ("decoder", "ssm_mamba1", "hybrid"):
        rcfg = family_rcfg(name)
        params = transformer.init_model(
            jax.random.PRNGKey(sum(map(ord, name)) % 1000), rcfg)
        greedy = [Request(prompt=np.array([5, 9, 3, 7, 2], np.int32),
                          max_new_tokens=6),
                  Request(prompt=np.array([4, 2, 9], np.int32),
                          max_new_tokens=6)]
        kw = dict(max_len=MAX_LEN, max_batch=2, page_size=4)
        plain = ServeEngine(rcfg, params, mesh=mesh, **kw).generate(
            [Request(prompt=r.prompt.copy(),
                     max_new_tokens=r.max_new_tokens) for r in greedy])
        spec_eng = ServeEngine(rcfg, params, mesh=mesh,
                               spec=SpecConfig(cf=2, k=3), **kw)
        spec = spec_eng.generate(greedy)
        for i, (a, b) in enumerate(zip(plain, spec)):
            if not np.array_equal(a.output, b.output):
                out["mismatch"].append(
                    [f"spec_tp2_{name}", i, list(map(int, b.output)),
                     list(map(int, a.output))])
        out["spec_drafted"] += int(spec_eng.stats["tokens_drafted"])
    print("RESULT " + json.dumps(out))
""")


def _run_mesh_subprocess():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)        # the script pins its own device count
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, cwd=os.getcwd(),
                       env=env, timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_mesh_sharded_decode_matches_dense_oracle():
    """All three backend families, dp and tp 2-device splits, greedy AND
    sampled requests, token-for-token vs the single-device dense oracle;
    plus greedy spec decode == plain decode under tp."""
    out = _run_mesh_subprocess()
    assert out["devices"] == 2
    assert out["mismatch"] == [], out["mismatch"]
    assert out["fused_mesh_mismatch"] == [], out["fused_mesh_mismatch"]
    assert all(f == [True, False] for f in out["fused_flag"])
    assert out["spec_drafted"] > 0          # spec decode actually drafted
    for name in ("decoder", "ssm_mamba1", "hybrid"):
        assert out[f"{name}_dp2tp1"] == [2, 1]
        assert out[f"{name}_dp1tp2"] == [1, 2]
        assert out[f"{name}_pool_dp_sharded"], \
            f"{name}: page pools replicated under dp2 (pool_pages?)"

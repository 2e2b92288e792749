#!/usr/bin/env python3
"""Compile each cell's step for a described (not attached) TPU v5e and
print the compiler's memory plan per device. Nothing runs on a chip, so
this says nothing about times or results; it finds what the chip's
compiler would refuse, and whether a plan fits, before any chip call.

  JAX_PLATFORMS=cpu REPRO_KERNEL_MODE=pallas python3 chipbench/rehearse.py

Training: the trainer's jitted step (layer-parallel and serial) for
gpt2_nanogpt at batch 4 and 8 x 1024 on one chip, and the layer-parallel
step on a described 2x2 mesh as (data 1, model 4). Serving: qwen3_1p7b's
paged serve step at the largest prefill bucket and at decode, for 8, 16
and 32 slots, beside the bytes of the weights and the page pool (a plan
that does not fit is reported as refused).
"""
from __future__ import annotations

import functools
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("REPRO_KERNEL_MODE", "pallas")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, Mesh, SingleDeviceSharding  # noqa: E402


def plan(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"arguments": ma.argument_size_in_bytes,
            "outputs": ma.output_size_in_bytes,
            "aliased": ma.alias_size_in_bytes,
            "temporaries": ma.temp_size_in_bytes}


def sds(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding(a) if callable(sharding)
        else sharding), tree)


def train_plans(topo):
    from chipbench.drivers.train import build_rcfg
    from repro.launch import steps as steps_mod
    from repro.models import transformer
    from repro.optim import optimizers
    cfg = json.loads((BENCH / "configs" / "gpt2_nanogpt.json").read_text())
    out = {}
    for mode, batch, chips in (("lp", 4, 1), ("serial", 4, 1), ("lp", 8, 1),
                               ("lp", 4, 4)):
        traffic = {"mode": mode, "global_batch": batch, "seq_len": 1024}
        rcfg = build_rcfg(cfg["program"], traffic)
        params = jax.eval_shape(lambda k: transformer.init_model(k, rcfg),
                                jax.random.PRNGKey(0))
        opt = jax.eval_shape(lambda p: optimizers.init_opt_state(
            rcfg.optimizer, p), params)
        batch_sds = {k: jax.ShapeDtypeStruct((batch, 1024), jnp.int32)
                     for k in ("tokens", "labels")}
        if chips == 1:
            one = SingleDeviceSharding(topo.devices[0])
            fn = jax.jit(steps_mod.make_train_fn(rcfg, None),
                         donate_argnums=(0, 1))
            args = (sds(params, one), sds(opt, one), sds(batch_sds, one))
        else:
            mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4),
                        ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))
            ps, os_, bs = steps_mod.shardings_for_train(rcfg, mesh, params,
                                                        opt, batch_sds)
            fn = jax.jit(steps_mod.make_train_fn(rcfg, mesh),
                         donate_argnums=(0, 1), in_shardings=(ps, os_, bs),
                         out_shardings=(ps, os_, None))
            args = (params, opt, batch_sds)
        compiled = fn.lower(*args).compile()
        key = f"train {mode} batch {batch}x1024 on {chips} chip(s)"
        out[key] = plan(compiled)
        if chips > 1:
            hlo = compiled.as_text()
            out[key]["collectives"] = {
                op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                for op in ("all-gather", "all-reduce", "reduce-scatter",
                           "collective-permute", "all-to-all")}
        print(key, out[key], flush=True)
    return out


def serve_plans(topo):
    from chipbench.drivers.serve import build_rcfg
    from repro.launch import steps as steps_mod
    from repro.models import transformer
    cfg = json.loads((BENCH / "configs" / "qwen3_1p7b.json").read_text())
    rcfg = build_rcfg(cfg["program"])
    dep = cfg["deployment_settings"]
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda k: transformer.init_model(k, rcfg),
                            jax.random.PRNGKey(0))
    wbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    decode = functools.partial(transformer.paged_decode_step, fused=True)
    fn = jax.jit(steps_mod.make_paged_serve_fn(rcfg, None, decode,
                                               fused=True),
                 donate_argnums=(1,))
    page, max_len = dep["page_size"], dep["max_len"]
    P = max_len // page
    out = {"weights_bytes": wbytes}
    for B in (8, 16, 32):
        n_pages = 1 + B * P
        pool = jax.eval_shape(lambda: transformer.init_paged_cache(
            rcfg, n_pages, page))
        pool_bytes = sum(a.size * a.dtype.itemsize
                         for a in jax.tree.leaves(pool))
        for S in (max_len, 1):
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
            f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,
                                                  sharding=one)
            args = (sds(params, one), sds(pool, one), i32(B, S), i32(B),
                    i32(B), i32(B, P), f32(B), i32(B), f32(B), i32(B),
                    i32(B))
            key = f"serve {B} slots, {S} tokens per slot, {P} pages"
            try:
                compiled = fn.lower(*args).compile()
            except jax.errors.JaxRuntimeError as e:   # does not fit
                out[key] = {"refused": str(e).splitlines()[0][:300]}
            else:
                out[key] = dict(plan(compiled), pool_bytes=pool_bytes)
            print(key, out[key], flush=True)
    return out


def main():
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    which = sys.argv[1:] or ["train", "serve"]
    res = {}
    if "train" in which:
        res.update(train_plans(topo))
    if "serve" in which:
        res.update(serve_plans(topo))
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()

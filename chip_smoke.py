#!/usr/bin/env python3
"""Bring-up check: the layer-parallel trainer and the paged server on a TPU.

  python chip_smoke.py               # one chip: train, then serve
  python chip_smoke.py --four-chips  # LP vs serial on a (1, 4) mesh only

Train: ``gpt2_nanogpt`` at full width (20 layers, d 768, vocab 50304) on
batches of 4 x 1024 tokens, through ``Trainer`` from one seed: serial
steps, then layer-parallel steps with the config's MGRIT (cf 4, serial
forward, one backward V-cycle, 2+2 buffer layers) and the adaptive
indicator probe. Every attention call the steps trace takes the fused
flash-attention kernels (``repro.obs.profile.ATTENTION_PATHS``; the dense
core under ``--four-chips``' mesh), every loss is finite, the first LP loss equals the first
serial loss within bf16 tolerance (with ``fwd_iters=0`` the LP forward is
the serial one), and neither run's loss rises.

Serve: ``qwen3_1p7b`` at full width (28 layers, d 2048, GQA 16/8, vocab
151936) with bf16 weights, through ``ServeEngine`` with the Pallas
kernels: 8 requests of 64-256 prompt tokens and 16 new tokens, two of
them sampled with top-k and top-p. Every request returns its tokens, the
compiled serve step holds ``tpu_custom_call``, and the paged attention
kernel agrees with a float32 jnp reference at the served shapes.

``--four-chips`` trains the same GPT-2 serial and layer-parallel on a
(data=1, model=4) mesh, checks that the trunk's layer axis is on 'model'
and that the first losses agree, and prints each device's peak bytes.
Both modes print the compiler's per-device memory plan of each step
beside the devices' allocator counters.

Weights are random, from ``--seed``. Step times are a smoke reading, not
a benchmark. Without a TPU, or when any check fails, the script prints
one line on stderr and exits 1; an exception exits non-zero as well. On
success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TRAIN_BATCH, TRAIN_SEQ = 4, 1024
TRAIN_STEPS = 4          # per mode; the probe runs at step PROBE_EVERY
PROBE_EVERY = 2
SERVE_REQUESTS, NEW_TOKENS, MAX_BATCH, PAGE_SIZE, MAX_LEN = 8, 16, 8, 16, 512
SAMPLED = (3, 6)         # request indices that sample (top-k 50, top-p 0.9)
# losses are bf16 forwards: the first LP and serial losses agree to this
LOSS_RTOL = 1e-2
# paged attention (bf16 in, bf16 out) vs a float32 reference
ATTN_ATOL = ATTN_RTOL = 2e-2
ATTN_CALLS = 100         # chained decode calls timed as one program


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


def tpu_devices():
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no devices: {e}".splitlines()[0])
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform!r}")
    return devs


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------


def gpt2_rcfg():
    """gpt2_nanogpt at full width; only the shape, the warmup (so that a
    few steps move the loss) and the probe interval are set here."""
    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    rcfg = registry.get_config("gpt2_nanogpt")
    return rcfg.replace(
        shape=ShapeConfig("smoke_1k", "train", TRAIN_SEQ, TRAIN_BATCH),
        optimizer=dataclasses.replace(rcfg.optimizer, warmup_steps=1),
        mgrit=dataclasses.replace(rcfg.mgrit, check_every=PROBE_EVERY))


def run_steps(trainer, n: int, probe: bool):
    """n single-step ``Trainer.train`` calls; each one ends on the loss
    coming back to the host, so its wall time covers the device work."""
    losses, times, modes = [], [], []
    for _ in range(n):
        t0 = time.perf_counter()
        rep = trainer.train(1, log_every=0, probe=probe)
        times.append(time.perf_counter() - t0)
        losses += rep.losses
        modes += rep.mode_trace
    return losses, times, modes


def step_memory(trainer, mode: str) -> str:
    """``memory_analysis()`` of the trainer's compiled step for ``mode``,
    lowered at the arguments it trains on: per-device bytes as the
    compiler planned them, to set beside the devices' measured peaks."""
    from repro.data.pipeline import shard_batch
    batch = shard_batch(trainer.pipeline.batch_at(trainer.step),
                        trainer.mesh, trainer.rcfg)
    t0 = time.perf_counter()
    ma = trainer._step_fn(mode).lower(
        trainer.params, trainer.opt_state, batch).compile().memory_analysis()
    return (f"arguments {ma.argument_size_in_bytes}, outputs "
            f"{ma.output_size_in_bytes}, aliased {ma.alias_size_in_bytes}, "
            f"temporaries {ma.temp_size_in_bytes}, code "
            f"{ma.generated_code_size_in_bytes} bytes (lowered and "
            f"compiled again in {time.perf_counter() - t0:.1f} s)")


def print_device_memory(devices):
    """Each device's allocator counters as the runtime reports them."""
    stats = [d.memory_stats() or {} for d in devices]
    for key in ("peak_bytes_in_use", "bytes_in_use", "bytes_limit"):
        print(f"device memory: {key} per device "
              f"{[s.get(key) for s in stats]}")
    return [s.get("peak_bytes_in_use") for s in stats]


def train_phase(rcfg, seed: int, mesh=None, steps: int = TRAIN_STEPS):
    """Serial then layer-parallel training from one seed; returns the LP
    trainer (for placement checks)."""
    from repro.obs import profile
    from repro.train.trainer import Trainer
    serial_cfg = rcfg.replace(
        mgrit=dataclasses.replace(rcfg.mgrit, enabled=False))
    runs = {}
    for name, cfg, probe in (("serial", serial_cfg, False),
                             ("lp", rcfg, True)):
        trainer = Trainer(cfg, mesh=mesh, seed=seed)
        before = dict(profile.ATTENTION_PATHS)
        losses, times, modes = run_steps(trainer, steps, probe)
        paths = {p: n - before[p] for p, n in profile.ATTENTION_PATHS.items()}
        print(f"train[{name}]: attention calls traced by path {paths}")
        # one device: every call on the fused kernels; a larger mesh keeps
        # the dense core (kernels.ops.flash_attention_mode)
        core = "fused" if mesh is None or mesh.size == 1 else "dense"
        check(paths[core] > 0 and sum(paths.values()) == paths[core],
              f"{name}: an attention call left the {core} core: {paths}")
        runs[name] = losses
        warm = times[1:]
        print(f"train[{name}]: losses {[round(x, 5) for x in losses]} "
              f"modes {modes}")
        print(f"train[{name}]: step times (s) {[round(t, 3) for t in times]}"
              f"; warm median {np.median(warm):.3f} s "
              "(smoke reading, not a benchmark; the first step compiles)")
        print(f"train[{name}]: compiled step per device: "
              f"{step_memory(trainer, modes[-1])}")
        if name == "lp":
            history = trainer.controller.state.history
            print(f"train[lp]: indicator probe (step, rho_fwd, rho_bwd) "
                  f"{history}")
            check(len(history) >= 1, "the indicator probe never ran")
            lp_trainer = trainer
        else:
            del trainer
            gc.collect()
    for name, losses in runs.items():
        check(all(math.isfinite(x) for x in losses),
              f"{name} loss not finite: {losses}")
        check(losses[-1] < losses[0],
              f"{name} loss rose: {losses[0]} -> {losses[-1]}")
    s0, l0 = runs["serial"][0], runs["lp"][0]
    print(f"train: first loss serial {s0:.6f} lp {l0:.6f} "
          f"(rel diff {abs(l0 - s0) / abs(s0):.2e}, limit {LOSS_RTOL})")
    check(abs(l0 - s0) <= LOSS_RTOL * abs(s0),
          f"first LP loss {l0} != first serial loss {s0}")
    return lp_trainer


def trunk_specs(params):
    """PartitionSpec of every leaf under the stacked ``mid`` trunk."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(params["mid"])[0]
    return {jax.tree_util.keystr(p): leaf.sharding.spec for p, leaf in flat}


def four_chip_phase(seed: int):
    import jax
    from repro.launch.mesh import make_mesh
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, have {len(jax.devices())}")
    mesh = make_mesh((1, 4), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)}")
    trainer = train_phase(gpt2_rcfg(), seed, mesh=mesh)
    specs = trunk_specs(trainer.params)
    on_model = {k: s for k, s in specs.items() if s and s[0] == "model"}
    print(f"trunk: {len(on_model)}/{len(specs)} mid leaves carry 'model' "
          f"on the layer axis, e.g. {next(iter(on_model.items()), None)}")
    check(len(on_model) == len(specs),
          f"mid leaves not on 'model': "
          f"{ {k: s for k, s in specs.items() if k not in on_model} }")
    peaks = print_device_memory(list(mesh.devices.flat))
    check(all(p is not None for p in peaks),
          "a device reports no peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------


def qwen3_rcfg():
    """qwen3_1p7b at full width, weights in bf16 as published."""
    from repro.configs import registry
    rcfg = registry.get_config("qwen3_1p7b", "decode_32k")
    return rcfg.replace(model=dataclasses.replace(rcfg.model,
                                                  param_dtype="bfloat16"))


def serve_phase(rcfg, seed: int):
    """Serve SERVE_REQUESTS requests through ServeEngine; returns it."""
    import jax
    from repro.models import transformer
    from repro.serve.engine import Request, ServeEngine
    params = transformer.init_model(jax.random.PRNGKey(seed), rcfg)
    engine = ServeEngine(rcfg, params, max_len=MAX_LEN, max_batch=MAX_BATCH,
                         page_size=PAGE_SIZE)
    rng = np.random.default_rng(seed)
    vocab = rcfg.model.vocab_size
    reqs = []
    for i in range(SERVE_REQUESTS):
        n = int(rng.integers(64, 257))
        sampled = i in SAMPLED
        reqs.append(Request(
            prompt=rng.integers(0, vocab, size=n).astype(np.int32),
            max_new_tokens=NEW_TOKENS, temperature=0.8 if sampled else 0.0,
            top_k=50 if sampled else 0, top_p=0.9 if sampled else 1.0,
            seed=i))
    t0 = time.perf_counter()
    out = engine.generate(reqs)
    wall = time.perf_counter() - t0
    for i, r in enumerate(out):
        kind = "sampled" if i in SAMPLED else "greedy"
        print(f"serve: request {i} ({kind}) prompt[{len(r.prompt)}] -> "
              f"{list(map(int, r.output))} error={r.error}")
        check(r.error is None, f"request {i} failed: {r.error}")
        check(len(r.output) == NEW_TOKENS,
              f"request {i} returned {len(r.output)} tokens")
        check(all(0 <= t < vocab for t in r.output),
              f"request {i} returned a token outside the vocab")
    thr = engine.scheduler.throughput()
    print(f"serve: {len(out)} requests in {wall:.2f} s wall, compiles "
          f"included; decode {thr['decode_tok_s']:.1f} tok/s "
          "(smoke reading, not a benchmark)")
    return engine


def serve_step_hlo(engine) -> str:
    """Compiled HLO of the engine's decode step at the served table width."""
    from repro.serve.cache import SlotBatch
    be = engine.backend
    pages = -(-MAX_LEN // PAGE_SIZE)
    table = 1 + np.arange(MAX_BATCH * pages).reshape(MAX_BATCH, pages)
    slots = SlotBatch.greedy(MAX_BATCH, table)
    state = be.init_state(be.pool_pages(1 + table.size))
    lowered = be._step_fn.lower(
        be.params, state, np.ones((MAX_BATCH, 1), np.int32), slots.lengths,
        slots.n_new, slots.page_table, slots.temps, slots.top_ks,
        slots.top_ps, slots.seeds, slots.counters)
    return lowered.compile().as_text()


def attention_agreement(rcfg, seed: int):
    """ops.paged_attention (the Pallas kernel on a TPU) against the jnp
    reference on float32 copies of the same bf16 inputs, at the served
    head layout, for a decode step and a 64-token prefill chunk."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.kernels.paged_attention import paged_attention_ref
    cfg = rcfg.model
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pages = -(-MAX_LEN // PAGE_SIZE)
    n_pages = 1 + MAX_BATCH * pages
    table = jnp.asarray(1 + np.arange(MAX_BATCH * pages).reshape(
        MAX_BATCH, pages), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool_shape = (n_pages, PAGE_SIZE, Hkv, hd)
    pk = jax.random.normal(ks[1], pool_shape, jnp.bfloat16)
    pv = jax.random.normal(ks[2], pool_shape, jnp.bfloat16)
    ref = jax.jit(paged_attention_ref)
    rng = np.random.default_rng(seed)
    for S in (1, 64):
        q = jax.random.normal(ks[0], (MAX_BATCH, S, H, hd), jnp.bfloat16)
        lengths = jnp.asarray(rng.integers(0, MAX_LEN - S, MAX_BATCH),
                              jnp.int32)
        got = np.asarray(kops.paged_attention(q, pk, pv, table, lengths),
                         np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref(q.astype(jnp.float32),
                                  pk.astype(jnp.float32),
                                  pv.astype(jnp.float32), table, lengths))
        err = np.abs(got - want)
        bound = ATTN_ATOL + ATTN_RTOL * np.abs(want)
        print(f"serve: paged attention S={S}: max |pallas - f32 ref| "
              f"{err.max():.3e} (atol {ATTN_ATOL}, rtol {ATTN_RTOL})")
        check(bool(np.all(np.isfinite(got))), "paged attention not finite")
        check(bool(np.all(err <= bound)),
              f"paged attention S={S} off the reference by {err.max()}")
    # decode-call time with every page live: ATTN_CALLS chained calls in
    # one program, each query fed by the last output so none is hoisted
    q = jax.random.normal(ks[0], (MAX_BATCH, 1, H, hd), jnp.bfloat16)
    full = jnp.full((MAX_BATCH,), MAX_LEN - 1, jnp.int32)

    @jax.jit
    def chained(q, pk, pv, table, lengths):
        return jax.lax.fori_loop(0, ATTN_CALLS, lambda _, o: (
            kops.paged_attention(q + o * 0, pk, pv, table, lengths)),
            jnp.zeros_like(q))

    chained(q, pk, pv, table, full).block_until_ready()
    t0 = time.perf_counter()
    chained(q, pk, pv, table, full).block_until_ready()
    us = (time.perf_counter() - t0) / ATTN_CALLS * 1e6
    kv_bytes = 2 * pk.dtype.itemsize * table.size * PAGE_SIZE * Hkv * hd
    print(f"serve: paged attention S=1, {table.size} (slot, page) blocks: "
          f"{us:.1f} us per call over {ATTN_CALLS} chained calls; "
          f"K/V blocks {kv_bytes} B, {kv_bytes / us / 1e3:.1f} GB/s "
          "(smoke reading, not a benchmark)")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the layer-parallel (1, 4) mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repository's src/ is not beside this script ({e})")
    cache = enable_compile_cache()
    import jax
    devs = tpu_devices()
    d0 = devs[0]
    print(f"device: {d0.device_kind} x{len(devs)}; jax {jax.__version__}, "
          f"libtpu {importlib.metadata.version('libtpu')}; "
          f"compile cache {cache}")

    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        from repro.kernels import ops as kops
        check(kops.kernel_mode() == "pallas",
              f"paged kernels in mode {kops.kernel_mode()!r}")
        train_phase(gpt2_rcfg(), args.seed)
        print_device_memory(devs)
        gc.collect()
        rcfg = qwen3_rcfg()
        engine = serve_phase(rcfg, args.seed)
        hlo = serve_step_hlo(engine)
        check("tpu_custom_call" in hlo,
              "the compiled serve step holds no tpu_custom_call")
        print(f"serve: compiled decode step names tpu_custom_call "
              f"{hlo.count('tpu_custom_call')} times")
        del engine
        gc.collect()
        attention_agreement(rcfg, args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Training cells: the program's ``Trainer`` on synthetic data from the seed.

Set-up builds one ``Trainer`` (its own input pipeline, its jitted step),
hands it the benchmark's weights from the seed, and drives it through the
first ``check_steps`` steps with the window's own call,
``Trainer.train(1)``; the first compiles. Those steps' losses, the first
gradient as the optimizer holds it (AdamW's first moment over 1 - beta1)
and every leaf's change are kept for the check. The same object then runs
the measured window: whole steps until the window closes, each ending on
its loss reaching the host. ``--trace 1`` adds a profiled segment of
``trace_steps`` steps after the window. After all that the trainer is
freed and the plain reference repeats the first steps in float32.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, flops, weights


class InputProxy:
    """Stands in for the trainer's pipeline: times every ``batch_at`` on
    the host clock, keeps the first batches for the reference, and in a
    profiled segment wraps the call in a ``TraceAnnotation``."""

    def __init__(self, inner, keep: int):
        self.inner, self.keep = inner, keep
        self.kept, self.ms = [], []
        self.annotate = False

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def batch_at(self, step):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation("input.batch_at"):
                b = self.inner.batch_at(step)
        else:
            b = self.inner.batch_at(step)
        self.ms.append((time.perf_counter() - t0) * 1e3)
        if len(self.kept) < self.keep:
            self.kept.append({k: np.array(v) for k, v in b.items()})
        return b


def build_rcfg(program: dict, traffic: dict):
    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    rcfg = registry.get_config(program["arch"])
    model = dataclasses.replace(rcfg.model, **program["model"])
    mgrit = dataclasses.replace(rcfg.mgrit, **program["mgrit"],
                                enabled=traffic["mode"] == "lp")
    opt = dataclasses.replace(rcfg.optimizer, **program["optimizer"])
    shape = ShapeConfig("bench", "train", traffic["seq_len"],
                        traffic["global_batch"])
    return rcfg.replace(model=model, mgrit=mgrit, optimizer=opt, shape=shape)


@jax.jit
def _norms(tree):
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32))))
        for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def _change_norms(new, old):
    return _norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                               - b.astype(jnp.float32), new, old))


def _floats(d):
    return {k: float(v) for k, v in d.items()}


def run(run):
    from repro.train.trainer import Trainer

    cfg, tr = run.config, run.traffic
    program = cfg["program"]
    rcfg = build_rcfg(program, tr)
    ref = common.reference(cfg["reference"])
    dims = ref.dims_from(program["model"], program["mgrit"])
    dims = dataclasses.replace(dims, **({} if tr["mode"] == "lp" else
                                        {"fwd_iters": 0, "bwd_iters": 0}))
    counter = common.CompileCounter()

    trainer = Trainer(rcfg, seed=run.seed & 0x7FFFFFFF)
    shapes = ref.param_shapes(dims)
    want = jax.tree.map(lambda s: s[0], shapes, is_leaf=ref.is_shape_leaf)
    got = jax.tree.map(lambda a: a.shape, trainer.params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise RuntimeError("the program's parameter tree no longer matches "
                           "the reference's layout")
    pdtype = jnp.dtype(program["model"]["param_dtype"])
    shard = jax.tree.map(lambda a: a.sharding, trainer.params)
    trainer.params = None
    gc.collect()

    def make_weights():
        return weights.make(run.seed, shapes, pdtype, ref.is_shape_leaf,
                            out_shardings=shard)

    trainer.params = make_weights()
    n_check = tr["check_steps"]
    proxy = InputProxy(trainer.pipeline, n_check)
    trainer.pipeline = proxy
    expect_mode = tr["mode"]
    b1 = program["optimizer"]["beta1"]

    losses = []
    first_grad = None
    for i in range(n_check):
        rep = trainer.train(1, log_every=0)
        losses += rep.losses
        if rep.mode_trace != [expect_mode]:
            raise RuntimeError(f"set-up step {i} ran {rep.mode_trace}")
        if i == 0:
            first_grad = {k: v / (1.0 - b1)
                          for k, v in _floats(_norms(
                              trainer.opt_state["m"])).items()}
    p0 = make_weights()
    change = _floats(_change_norms(trainer.params, p0))
    del p0
    rows = np.concatenate([b["tokens"] for b in proxy.kept])
    distinct = len({r.tobytes() for r in rows})

    tokens_per_step = tr["global_batch"] * tr["seq_len"]
    run.setup_done()

    # ---- measured window ----
    counter.start()
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    ends, bad = [], 0
    n_ms0 = len(proxy.ms)
    while True:
        rep = trainer.train(1, log_every=0)
        t = time.perf_counter()
        if t > deadline:
            break
        ends.append(t)
        if rep.mode_trace != [expect_mode] or not all(
                math.isfinite(x) for x in rep.losses):
            bad += 1
    compiles = counter.stop()
    window_ms = proxy.ms[n_ms0:n_ms0 + len(ends)]
    if not ends:
        raise RuntimeError(f"no step finished inside {run.seconds} s")
    tps = len(ends) * tokens_per_step / (ends[-1] - t0)
    step_ms = np.diff([t0] + ends) * 1e3
    run.note(f"window: {len(ends)} steps, step ms p10 "
             f"{np.percentile(step_ms, 10):.2f} p50 "
             f"{np.median(step_ms):.2f} p90 {np.percentile(step_ms, 90):.2f}"
             f" max {step_ms.max():.2f}, over 1.5 x p50: "
             f"{int((step_ms > 1.5 * np.median(step_ms)).sum())}; input ms "
             f"mean {np.mean(window_ms):.3f} max {np.max(window_ms):.3f}")
    run.attempted, run.failed = len(ends), bad
    fpt = flops.train_flops_per_token(program["model"], tr["seq_len"])
    run.data.update(
        kind="train", train_tokens_per_s=tps, steps=len(ends),
        tokens_per_step=tokens_per_step, flops_per_token=fpt,
        input_ms_per_step=float(np.mean(window_ms)), model=program["model"],
        batch=tr["global_batch"], seq=tr["seq_len"])

    # ---- profiled segment ----
    if run.trace:
        proxy.annotate = True
        prof = common.Profile()
        with jax.profiler.TraceAnnotation("bench.segment"):
            for _ in range(tr["trace_steps"]):
                with jax.profiler.TraceAnnotation("trainer.train"):
                    trainer.train(1, log_every=0)
        run.trace_data = prof.stop(run.devices, "bench.segment")
        proxy.annotate = False
        run.data["trace_steps"] = tr["trace_steps"]

    run.read_memory()
    del trainer, proxy.inner
    gc.collect()

    # ---- the reference, after the program's state is freed ----
    t_ref = time.perf_counter()
    dev0 = run.devices[0]
    with jax.default_device(dev0):
        params0 = weights.make(run.seed, shapes, pdtype, ref.is_shape_leaf)
        r_loss, r_grad, r_change = ref.train_readings(
            params0, proxy.kept, dims, program["optimizer"],
            lp=tr["mode"] == "lp", mode="f32")
    run.data["reference_s"] = time.perf_counter() - t_ref

    keys = common.counted_leaves(r_grad)

    def readings(loss, grad, change):
        return {"loss_gap": max(common.rel_gap(a, b, 0.0)
                                for a, b in zip(loss, r_loss)),
                "grad_gap": common.worst_leaf_gap(grad, r_grad, keys)[0],
                "update_gap": common.worst_leaf_gap(change, r_change,
                                                    keys)[0]}

    control = None
    if run.control:
        with jax.default_device(dev0):
            control = readings(*ref.train_readings(
                params0, proxy.kept, dims, program["optimizer"],
                lp=tr["mode"] == "lp", mode="fp8"))
    run.compare(readings(losses, first_grad, change), control)
    run.check("compiles_in_window", compiles, 0)
    run.check("repeated_rows", len(rows) - distinct, 0)
    run.note(f"losses {losses} reference {r_loss}; worst grad leaf "
             f"{common.worst_leaf_gap(first_grad, r_grad, keys)[1]}, worst "
             f"update leaf {common.worst_leaf_gap(change, r_change, keys)[1]}"
             f"; {len(keys)}/{len(r_grad)} leaves counted")

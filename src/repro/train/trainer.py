"""Trainer: the paper's full training procedure.

  * layer-parallel (MGRIT) steps by default, serial steps on demand;
  * adaptive inexactness control (paper §3.2.3): every ``check_every``
    steps run a doubled-iteration probe, compute the convergence factor,
    and switch LP -> serial when it crosses 1 (Fig. 4 green curves);
  * fault tolerance: periodic atomic checkpoints, resume-from-latest,
    emergency checkpoint on exception;
  * straggler watch: EWMA of step wall-time, slow steps logged.

The LP and serial steps are two separately jitted functions; switching is a
host-side decision (it happens once per run, like the paper's). Under a
mesh, params and optimizer state are placed by ``parallel/params.
param_specs`` (the paper's layout puts the stacked trunk's layer axis on
'model') and both steps keep that placement from call to call.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RunConfig
from repro.core.adaptive import AdaptiveController
from repro.core import lp as lp_mod
from repro.data.pipeline import make_pipeline, shard_batch
from repro.launch import steps as steps_mod
from repro.models import transformer
from repro.models.blocks import block_kind
from repro.optim import optimizers
from repro.train import checkpoint as ckpt_mod


@dataclasses.dataclass
class TrainReport:
    losses: List[float]
    mode_trace: List[str]
    controller_history: List
    switched_at: Optional[int]
    steps_per_sec: float


class Trainer:
    def __init__(self, rcfg: RunConfig, mesh=None, ckpt_dir: str = "",
                 seed: int = 0, data_path: str = ""):
        self.rcfg = rcfg
        self.mesh = mesh
        self.ckpt_dir = ckpt_dir
        self.controller = AdaptiveController(rcfg.mgrit)
        self.pipeline = make_pipeline(rcfg, seed, data_path)
        key = jax.random.PRNGKey(seed)
        self.params = transformer.init_model(key, rcfg)
        self.opt_state = optimizers.init_opt_state(
            rcfg.optimizer, self.params,
            moment_dtype=jnp.dtype(rcfg.optimizer.moment_dtype))
        self.step = 0
        self._shardings = None
        self._steps: Dict[str, Callable] = {}
        self._probe_fn = None
        self._ewma_dt = None

        if ckpt_dir:
            restored = ckpt_mod.restore(ckpt_dir, self.params,
                                        self.opt_state, mesh, rcfg)
            if restored is not None:
                self.params, self.opt_state, self.step, extra = restored
                if extra.get("controller_mode"):
                    self.controller.state.mode = extra["controller_mode"]
        if mesh is not None:
            self._shardings = steps_mod.shardings_for_train(
                rcfg, mesh, self.params, self.opt_state,
                self.pipeline.batch_at(0))
            ps, os_, _ = self._shardings
            self.params = jax.device_put(self.params, ps)
            self.opt_state = jax.device_put(self.opt_state, os_)

    # -- jitted steps (built lazily, cached per mode) --
    def _step_fn(self, mode: str):
        if mode not in self._steps:
            rcfg = self.rcfg
            if mode == "serial":
                rcfg = rcfg.replace(
                    mgrit=dataclasses.replace(rcfg.mgrit, enabled=False))
            fn = steps_mod.make_train_fn(rcfg, self.mesh)
            if self._shardings is None:
                self._steps[mode] = jax.jit(fn, donate_argnums=(0, 1))
            else:
                ps, os_, bs = self._shardings
                self._steps[mode] = jax.jit(
                    fn, donate_argnums=(0, 1), in_shardings=(ps, os_, bs),
                    out_shardings=(ps, os_, None))
        return self._steps[mode]

    def _probe(self, batch):
        """Paper's indicator probe: doubled iterations, measure rho."""
        fwd_it, bwd_it = self.controller.probe_iters()
        rcfg = self.rcfg
        cfg = rcfg.model
        kind = block_kind(cfg)
        if cfg.family in ("hybrid",):
            return None  # LP inapplicable; controller never probes anyway

        static = lp_mod.LPStatic(
            cfg=cfg,
            mgrit=dataclasses.replace(rcfg.mgrit, fwd_iters=fwd_it,
                                      bwd_iters=bwd_it),
            kind=kind, causal=cfg.family != "encoder")

        from repro.models.layers import rope_freqs
        from repro.models.transformer import _embed_inputs, _serial_buffer

        def run(params, batch):
            z = _embed_inputs(params, batch, cfg)
            rope = None if kind in ("mamba1", "mamba2") else rope_freqs(
                cfg.resolved_head_dim, cfg.rope_theta,
                jnp.arange(z.shape[1], dtype=jnp.int32))
            z = _serial_buffer(params.get("open"), z, cfg, kind=kind,
                               causal=static.causal, rope=rope)
            extra = {"rope": rope} if rope is not None else {}
            return lp_mod.lp_diagnose(
                static, params["mid"], z, extra,
                seed_ct=lambda zT: jnp.ones_like(zT)
                / jnp.asarray(zT.size, zT.dtype),
                fwd_iters=fwd_it, bwd_iters=bwd_it)

        if self._probe_fn is None:
            self._probe_fn = jax.jit(run)
        return self._probe_fn(self.params, batch)

    def train(self, num_steps: int, ckpt_every: int = 0,
              log_every: int = 50, probe: bool = True) -> TrainReport:
        losses, modes = [], []
        t_start = time.time()
        try:
            for _ in range(num_steps):
                batch = shard_batch(self.pipeline.batch_at(self.step),
                                    self.mesh, self.rcfg)
                mode = self.controller.state.mode
                t0 = time.time()

                if probe and self.controller.should_probe(self.step):
                    res = self._probe(batch)
                    if res is not None:
                        fwd_norms, bwd_norms = res
                        action = self.controller.observe(
                            self.step, np.asarray(fwd_norms),
                            np.asarray(bwd_norms))
                        if action == "switched":
                            mode = "serial"

                fn = self._step_fn(mode)
                self.params, self.opt_state, metrics = fn(
                    self.params, self.opt_state, batch)
                dt = time.time() - t0
                self._ewma_dt = dt if self._ewma_dt is None else \
                    0.9 * self._ewma_dt + 0.1 * dt
                if dt > 3.0 * self._ewma_dt:
                    print(f"[straggler] step {self.step} took {dt:.2f}s "
                          f"(ewma {self._ewma_dt:.2f}s)")
                losses.append(float(metrics["loss"]))
                modes.append(mode)
                self.step += 1
                if ckpt_every and self.step % ckpt_every == 0:
                    self._save()
                if log_every and self.step % log_every == 0:
                    print(f"step {self.step} [{mode}] "
                          f"loss={losses[-1]:.4f}")
        except Exception:
            if self.ckpt_dir:
                self._save(tag="emergency")
            raise
        dt_total = time.time() - t_start
        return TrainReport(
            losses=losses, mode_trace=modes,
            controller_history=list(self.controller.state.history),
            switched_at=self.controller.state.step_of_switch,
            steps_per_sec=len(losses) / max(dt_total, 1e-9))

    def _save(self, tag: str = ""):
        ckpt_mod.save(self.ckpt_dir, self.step, self.params, self.opt_state,
                      extra={"controller_mode": self.controller.state.mode,
                             "tag": tag})

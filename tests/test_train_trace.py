"""The training step traced from inside: the solver's phase scopes in the
compiled step's op metadata, the trainer's host spans, its compile
counter and its compile-free step clock (docs/observability.md,
"Training")."""
import re
import tempfile
import time

import jax
import pytest

from repro.configs.base import (MGRITConfig, ModelConfig, OptimizerConfig,
                                RunConfig, ShapeConfig)
from repro.train.trainer import Trainer

PHASES = ("trunk_forward", "trunk_adjoint", "mgrit_coarse",
          "trunk_param_grads", "optimizer_update")
STEP_SPANS = ["train.step", "train.input", "train.h2d", "train.dispatch",
              "train.wait"]


def rcfg_for(lp=True, check_every=1000):
    """A 1 + 4 + 1-layer decoder: buffers around an MGRIT trunk (cf 2,
    two levels) with one forward and one adjoint V-cycle, so every phase
    of the layer-parallel step is present."""
    model = ModelConfig(name="t", family="decoder", n_layers=6, d_model=32,
                        n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64)
    return RunConfig(
        model=model,
        mgrit=MGRITConfig(enabled=lp, cf=2, levels=2, fwd_iters=1,
                          bwd_iters=1, n_open=1, n_close=1, pad_to=4,
                          check_every=check_every, switch_threshold=1e9),
        optimizer=OptimizerConfig(name="adamw", lr=1e-3, warmup_steps=2,
                                  total_steps=30),
        shape=ShapeConfig("t", "train", 16, 4))


def op_names(trainer, mode):
    batch = trainer.pipeline.batch_at(0)
    hlo = trainer._step_fn(mode).lower(trainer.params, trainer.opt_state,
                                       batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def holds(names, scope):
    pat = re.compile(rf"(^|/|\(){re.escape(scope)}($|/|\))")
    return [n for n in names if pat.search(n)]


class Recorder:
    """Stands in for the trainer's span callable: logs enters and exits."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        rec = self

        class Span:
            def __enter__(self):
                rec.log.append(("enter", name))

            def __exit__(self, *exc):
                rec.log.append(("exit", name))
        return Span()


def steps_of(log):
    """The entered span names, one list per ``train.step``."""
    out = []
    for kind, name in log:
        if kind == "enter":
            if name == "train.step":
                out.append([])
            out[-1].append(name)
    return out


@pytest.fixture(scope="module")
def run():
    """One layer-parallel trainer through four calls, each call's report,
    spans and compile counts kept; then the compiled LP and serial steps'
    op names (their lowering counts as traces, so it comes last)."""
    out = {}
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(rcfg_for(lp=True, check_every=2), ckpt_dir=d, seed=0)
        watch = tr._watch

        def watched(dt):
            out["watched"][-1].append(tr.step - 1)
            watch(dt)
        tr._watch = watched
        out["watched"] = []
        calls = (
            ("first", dict(num_steps=1, probe=False)),     # compiles lp
            ("probe", dict(num_steps=3, probe=True, ckpt_every=2)),
            ("warm", dict(num_steps=3, probe=False)),
            ("serial", dict(num_steps=2, probe=False)),    # compiles serial
        )
        for name, kw in calls:
            if name == "serial":
                tr.controller.state.mode = "serial"
            rec = Recorder()
            tr.span = rec
            out["watched"].append([])
            t0 = time.perf_counter()
            rep = tr.train(log_every=0, **kw)
            out[name] = {"report": rep, "log": rec.log,
                         "wall_s": time.perf_counter() - t0,
                         "counts": dict(tr.compile_counts),
                         "ewma": tr._ewma_dt}
        out["lp_names"] = op_names(tr, "lp")
        out["serial_names"] = op_names(tr, "serial")
        hlo = tr._probe_fn.lower(tr.params, tr.pipeline.batch_at(0)) \
            .compile().as_text()
        out["probe_names"] = set(re.findall(r'op_name="([^"]*)"', hlo))
    return out


@pytest.mark.parametrize("scope", PHASES)
def test_lp_step_names_each_phase(run, scope):
    assert holds(run["lp_names"], scope), f"no compiled op under {scope!r}"


def test_compiled_steps_keep_their_name(run):
    """The compile counter's wrapper leaves the jitted program named after
    the step function, as it was without the counter."""
    for names in (run["lp_names"], run["serial_names"]):
        jits = {n.split("/")[0] for n in names if n.startswith("jit(")}
        assert jits == {"jit(train_step)"}


def test_adjoint_scopes_reach_the_custom_vjp_backward(run):
    """The adjoint runs in ``_lp_bwd``, the custom VJP's backward rule:
    its ops, the coarse ones inside the scan bodies among them, carry
    both scopes under the transpose."""
    names = run["lp_names"]
    bwd = [n for n in holds(names, "trunk_adjoint")
           if "transpose(jvp(trunk_adjoint))" in n]
    assert bwd
    assert [n for n in bwd if "/mgrit_coarse/" in n and "/while/" in n]
    # the forward solve's own coarse start lies outside the adjoint
    assert [n for n in holds(names, "mgrit_coarse")
            if "trunk_forward" in n and "trunk_adjoint" not in n]
    # the parameter VJPs follow the adjoint, they do not nest in it
    assert not holds(holds(names, "trunk_param_grads"), "trunk_adjoint")


def test_serial_step_bypasses_the_mgrit_scopes(run):
    names = run["serial_names"]
    assert not holds(names, "trunk_adjoint")
    assert not holds(names, "mgrit_coarse")
    for scope in ("trunk_forward", "trunk_param_grads", "optimizer_update"):
        assert holds(names, scope), scope


def test_spans_in_order_and_nested(run):
    log = run["warm"]["log"]
    assert steps_of(log) == [STEP_SPANS] * 3
    n = 2 * len(STEP_SPANS)
    for i in range(0, len(log), n):
        step = log[i:i + n]
        # each inner span opens and closes inside the step's own span
        assert step[0] == ("enter", "train.step")
        assert step[-1] == ("exit", "train.step")
        assert step[1:-1:2] == [("enter", s) for s in STEP_SPANS[1:]]
        assert step[2:-1:2] == [("exit", s) for s in STEP_SPANS[1:]]


def test_probe_and_ckpt_spans_only_when_they_run(run):
    got = steps_of(run["probe"]["log"])
    plain = STEP_SPANS
    probed = STEP_SPANS[:3] + ["train.probe"] + STEP_SPANS[3:]
    # steps 1, 2, 3: steps 1 and 3 bring the count to 2 and 4, each a
    # checkpoint; step 2 is a probe step (every 2nd from the 2nd)
    assert got == [plain + ["train.ckpt"], probed, plain + ["train.ckpt"]]
    assert steps_of(run["first"]["log"]) == [plain]


def test_probe_program_carries_its_own_scope(run):
    """The probe's doubled-iteration solves run under ``mgrit_probe``; the
    phase scopes nest inside it, so a probe that lands in a traced segment
    counts in the phase readings, and its ops can be told apart."""
    names = run["probe_names"]
    assert names and holds(names, "mgrit_probe")
    for scope in ("trunk_forward", "trunk_adjoint"):
        inside = holds(names, scope)
        assert inside and all(holds([n], "mgrit_probe") for n in inside)
    assert not holds(run["lp_names"], "mgrit_probe")


def test_straggler_watch_skips_probe_and_ckpt_steps(run):
    """Steps 1 and 3 of the probe call checkpoint, step 2 probes (and
    compiles the probe): none of them feeds the EWMA."""
    first, probe, warm, serial = run["watched"]
    assert first == []                             # it compiled
    assert probe == []
    assert warm == [4, 5, 6]
    assert serial == [8]                           # its first step compiled


def test_compile_counts_once_per_mode(run):
    assert run["first"]["counts"] == {"lp": 1, "serial": 0, "probe": 0}
    assert run["first"]["report"].compiles == 1
    # the probe's program compiles on its first step, the LP step not again
    assert run["probe"]["counts"] == {"lp": 1, "serial": 0, "probe": 1}
    assert run["probe"]["report"].compiles == 1
    assert run["warm"]["counts"] == run["probe"]["counts"]
    assert run["warm"]["report"].compiles == 0


def test_step_clock_leaves_compiles_out(run):
    first, warm = run["first"], run["warm"]
    assert first["report"].steps_per_sec == 0.0    # no compile-free step
    assert first["ewma"] is None                   # a compile seeds no EWMA
    assert warm["ewma"] is not None
    # the rate is over the compile-free steps alone
    assert warm["report"].steps_per_sec > 1.0 / first["wall_s"]
    assert warm["report"].steps_per_sec >= 3 / warm["wall_s"]


def test_mode_switch_counts_its_compile(run):
    serial = run["serial"]
    assert serial["counts"] == {"lp": 1, "serial": 1, "probe": 1}
    assert serial["report"].compiles == 1
    assert serial["report"].mode_trace == ["serial", "serial"]
    assert serial["report"].steps_per_sec > 0      # its warm second step


def test_spans_are_profiler_annotations_by_default():
    tr = Trainer(rcfg_for(lp=True), seed=0)
    assert isinstance(tr.span("train.step"), jax.profiler.TraceAnnotation)


def test_declared_names_are_what_the_step_emits(run):
    """``repro.obs.profile`` declares the training step's scopes and spans
    (the benchmark's readers look for them): each declared scope is in the
    compiled LP step or probe, and the spans entered are the declared ones,
    in the declared order."""
    from repro.obs import profile
    names = run["lp_names"] | run["probe_names"]
    assert set(PHASES) < set(profile.TRAIN_SCOPES)
    for scope in profile.TRAIN_SCOPES:
        assert holds(names, scope), scope
    entered = [step for call in ("first", "probe", "warm", "serial")
               for step in steps_of(run[call]["log"])]
    assert {s for step in entered for s in step} == set(profile.TRAIN_SPANS)
    for step in entered:
        assert step == [s for s in profile.TRAIN_SPANS if s in step]

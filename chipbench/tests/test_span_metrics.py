"""The readers of the trainer's own scopes and spans, on a small trace whose
answers are counted by hand below."""
import sizes  # noqa: F401

import pytest

from chipbench import run as run_mod
from chipbench.trace import Trace

NEW = ("trunk_fwd_ms_per_step.train", "adjoint_ms_per_step.train",
       "coarse_ms_per_step.train", "param_grad_ms_per_step.train",
       "optimizer_ms_per_step.train", "input_wait_ms_per_step.train")

# two devices, window 0..1000 ns, two steps
#   device 0: forward 50..150, adjoint 150..300 (inside it mgrit_coarse),
#             adjoint 300..400, parameter VJPs 400..450, optimizer 450..480;
#             the second step's forward 560..700 (inside it mgrit_coarse)
#   device 1: a coarse adjoint op 0..500, an unscoped op 520..1000
S = "jit(train_step)/"
OPS = {
    0: [(50, 150, "fusion.1", S + "jvp(trunk_forward)/attn_core/dot"),
        (150, 300, "fusion.2",
         S + "transpose(jvp(trunk_adjoint))/mgrit_coarse/while/body/dot"),
        (300, 400, "fusion.3", S + "transpose(jvp(trunk_adjoint))/add"),
        (400, 450, "fusion.4", S + "trunk_param_grads/vmap/dot"),
        (450, 480, "fusion.5", S + "optimizer_update/mul"),
        (560, 700, "fusion.6",
         S + "jvp(trunk_forward)/mgrit_coarse/while/body/dot")],
    1: [(0, 500, "fusion.7",
         S + "transpose(jvp(trunk_adjoint))/mgrit_coarse/dot"),
        (520, 1000, "fusion.8", S + "while/body/add")],
}
# the program's spans leave 490..510 uncovered; the benchmark's do not
SPANS = [(0, 1000, "bench.segment"), (0, 500, "trainer.train"),
         (500, 1000, "trainer.train"),
         (0, 490, "train.step"), (0, 60, "train.input"),
         (60, 80, "train.h2d"), (80, 100, "train.dispatch"),
         (100, 490, "train.wait"),
         (510, 1000, "train.step"), (510, 540, "train.input"),
         (540, 560, "train.h2d"), (560, 600, "train.dispatch"),
         (600, 1000, "train.wait")]


class FakeRun:
    def __init__(self, spans, ops=OPS):
        self.trace_data = Trace(ops=ops, spans=spans, window=(0, 1000))
        self.data = {"trace_steps": 2}


def read(fake):
    bench = run_mod.load_bench()
    cell = run_mod.find_cell(bench, "gpt2.train_lp")
    entries = [m for m in run_mod.metrics_for(bench, cell, True)
               if m["name"] in NEW]
    assert sorted(m["name"] for m in entries) == sorted(NEW)
    return {k: v["value"] for k, v in
            run_mod.read_metrics(fake, entries).items()}


def test_hand_counted_phases_and_input_wait():
    got = read(FakeRun(SPANS))
    ns_per_step = {
        # summed over the devices, over 2 steps
        "trunk_fwd_ms_per_step.train": (100 + 140) / 2,
        "adjoint_ms_per_step.train": (150 + 100 + 500) / 2,
        # nested in trunk_adjoint or trunk_forward, counted in both
        "coarse_ms_per_step.train": (150 + 140 + 500) / 2,
        "param_grad_ms_per_step.train": 50 / 2,
        "optimizer_ms_per_step.train": 30 / 2,
        # device 0's holes: 0..50 all under train.input (50); 480..560:
        # 480..490 under train.wait (not counted), 490..510 under no
        # program span, 510..540 train.input and 540..560 train.h2d (50);
        # 700..1000 under train.wait (not counted). Device 1's hole
        # 500..520: 510..520 under train.input (10). Mean over devices.
        "input_wait_ms_per_step.train": (100 + 10) / 2 / 2,
    }
    assert got == pytest.approx({k: v * 1e-6 for k, v in
                                 ns_per_step.items()})


def test_a_missing_scope_or_span_finds_nothing():
    """A phase with no op, or no input span, reads None: the run then
    fails (nothing to read)."""
    no_opt = {d: [op for op in ops if "optimizer_update" not in op[3]]
              for d, ops in OPS.items()}
    got = read(FakeRun(SPANS, no_opt))
    assert "optimizer_ms_per_step.train" not in got
    assert len(got) == len(NEW) - 1
    no_input = [s for s in SPANS if s[2] not in ("train.input",
                                                 "train.h2d")]
    got = read(FakeRun(no_input))
    assert "input_wait_ms_per_step.train" not in got


def test_a_program_that_names_no_step_reads_zero(monkeypatch):
    """A program from before ``repro.obs.profile`` declared the training
    step's scopes and spans spends no time under them: each of the six
    reads 0.0, so that its traced run still gives a result."""
    from chipbench import step_trace
    from repro.obs import profile
    assert step_trace.program_names_its_step()
    monkeypatch.delattr(profile, "TRAIN_SCOPES")
    assert not step_trace.program_names_its_step()
    bench_only = [s for s in SPANS if not s[2].startswith("train.")]
    assert read(FakeRun(bench_only)) == {name: 0.0 for name in NEW}


def test_a_trace_without_the_programs_spans_finds_nothing():
    """A program that declares these scopes and spans but emits none of
    them gets no reading of any of the six, and its traced run fails,
    rather than a 0.0 that would read as the best possible time."""
    bench_only = [s for s in SPANS if not s[2].startswith("train.")]
    plain = {d: [(s, e, n, sc.replace("trunk_", "x_").replace(
        "mgrit_coarse", "x").replace("optimizer_update", "x"))
        for s, e, n, sc in ops] for d, ops in OPS.items()}
    assert read(FakeRun(bench_only, plain)) == {}

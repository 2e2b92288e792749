"""The reduction from a trace to metrics, on a small trace whose answers
are counted by hand below."""
import sizes  # noqa: F401

import gzip
import json
import re
from pathlib import Path

import pytest

from chipbench.trace import Trace, label, load

# two devices, window 100..200 ns
#   device 0: a matmul under mlp_core 90..130 (40 in window: 100..130),
#             attention core 120..150 (overlaps: union 100..150),
#             an all-reduce-start 160..170, its -done 170..175
#   device 1: a fusion under attn_core 100..110, a collective-permute
#             150..190, an op after the window 210..220
OPS = {
    0: [(90, 130, "fusion.12", "jit(step)/jit(main)/mlp_core/dot_general"),
        (120, 150, "fusion.3",
         "jit(step)/transpose(jvp(attn_core))/dot_general"),
        (160, 170, "all-reduce-start.1", ""),
        (170, 175, "all-reduce-done.1", "")],
    1: [(100, 110, "fusion.7", "jit(step)/attn_core/softmax"),
        (150, 190, "collective-permute.2", "jit(step)/mgrit/shift"),
        (210, 220, "copy.1", "")],
}
SPANS = [(95, 205, "bench.segment"), (100, 180, "trainer.train"),
         (150, 160, "input.batch_at")]


@pytest.fixture
def tr():
    return Trace(ops=OPS, spans=SPANS, window=(100, 200))


def test_busy_and_idle(tr):
    # device 0: 100..150 + 160..175 = 65; device 1: 10 + 40 = 50
    assert tr.busy_s() == pytest.approx((65 + 50) / 2 * 1e-9)
    assert tr.window_s() == pytest.approx(100e-9)
    assert tr.idle_share() == pytest.approx(1 - 57.5 / 100)


def test_scope_attribution(tr):
    assert tr.scope_s("mlp_core") == pytest.approx(30e-9)      # clipped
    # the transposed (backward) op and device 1's softmax both count
    assert tr.scope_s("attn_core") == pytest.approx((30 + 10) * 1e-9)
    assert tr.scope_s("attn") == 0.0           # whole path elements only
    assert label("fusion.3", OPS[0][1][3]) == "attn_core"
    assert label("all-reduce-start.1", "") == "all-reduce-start"


def test_collective_time(tr):
    assert tr.collective_s() == pytest.approx((10 + 5 + 40) * 1e-9)


def test_top_ops_and_idle_gaps(tr):
    top = dict(tr.top_ops(10))
    assert top["attn_core"] == pytest.approx(40e-9 / 2)
    assert top["collective-permute"] == pytest.approx(40e-9 / 2)
    gaps = tr.idle_gaps(3)
    # device 1: 110..150 (40, around 130: trainer.train), 190..200 (10);
    # device 0: 150..160 (10, input.batch_at), 175..200 (25)
    assert gaps[0] == ["trainer.train", pytest.approx(40e-9)]
    assert gaps[1] == ["bench.segment", pytest.approx(25e-9)]
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 25e-9, 10e-9])



RECORDED = Path(__file__).parent / "data" / "v5e_train_lp.trace.json.gz"


def test_recorded_v5e_trace():
    """A cut of a trace that ``jax.profiler`` recorded on a TPU v5e in a
    ``gpt2.train_lp`` step (1.5 ms; its ``bench.segment`` span cut to the
    same 1.5 ms): the loader finds the device's ``XLA Ops`` and the host's
    spans, names ops by their ``tf_op`` name stack, and leaves out the
    while loop that holds the other ops."""
    with gzip.open(RECORDED, "rt") as f:
        events = json.load(f)["traceEvents"]
    tr = load(str(RECORDED), [0], "bench.segment")
    seg = next(e for e in events if e["name"] == "bench.segment")
    lo, hi = seg["ts"] * 1e3, (seg["ts"] + seg["dur"]) * 1e3
    ops = [e for e in events if e.get("ph") == "X" and e["pid"] != seg["pid"]
           and e["args"].get("hlo_category") != "while"]
    assert ops and len(tr.ops[0]) == len(ops) == 61

    def clipped(e):
        return max(0.0, min(hi, (e["ts"] + e["dur"]) * 1e3)
                   - max(lo, e["ts"] * 1e3)) * 1e-9
    for scope in ("mlp_core", "attn_core"):
        want = sum(clipped(e) for e in ops
                   if re.search(rf"[/(]{scope}[/)]",
                                e["args"].get("tf_op", "")))
        assert want > 0
        assert tr.scope_s(scope) == pytest.approx(want, abs=1e-8)  # ns
    assert tr.window_s() == pytest.approx(seg["dur"] * 1e-6)
    assert max(clipped(e) for e in ops) <= tr.busy_s() <= tr.window_s()
    assert {"mlp_core", "attn_core"} <= set(dict(tr.top_ops(10)))
    assert tr.idle_gaps(1)[0][0] != "(no host span)"

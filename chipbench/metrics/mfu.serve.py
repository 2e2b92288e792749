"""Forward FLOPs that the profiled segment's serve-step calls required
(every slot's new tokens, causal attention over cached and new keys, one
row of logits per active slot) over the host time spent inside
``Scheduler.step`` in that segment, over the cell's chips times the
bf16 peak."""
from chipbench import flops

META = {"layer": "step / solver", "unit": "%", "source": "host_clock",
        "moves": "tpot_p90_ms"}


def read(run):
    d = run.data
    if not d.get("calls") or not d.get("step_s"):
        return None
    work = sum(flops.serve_call_flops(d["model"], ln, nn)
               for ln, nn in d["calls"])
    return (100.0 * work / d["step_s"]
            / (run.chips * run.peak["bf16_flops_per_s"]))

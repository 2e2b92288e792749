"""Share of its roofline that the attention core (scores, softmax,
weighted values; the projections are outside it) reaches in a training
step: the least time for its required operations and bytes (causal,
forward and backward, from shapes) over the device time of all ops
under the ``attn_core`` scope per step, summed over the chips."""
from chipbench import flops

META = {"layer": "model / kernels", "unit": "%", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    t = run.trace_data
    if t is None:
        return None
    spent = t.scope_s("attn_core") / run.data["trace_steps"]
    if spent <= 0:
        return None
    need, _ = flops.roofline_s(*flops.train_attn_core_step(
        run.data["model"], run.data["batch"], run.data["seq"]), run.peak)
    return 100.0 * need / spent

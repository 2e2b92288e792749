"""Share of its roofline that the MLP reaches in a training step: the
least time the chip needs for the operations and bytes every layer's MLP
requires (forward and backward, from shapes), over the device time of
all ops under the ``mlp_core`` scope per step, summed over the chips."""
from chipbench import flops

META = {"layer": "model / kernels", "unit": "%", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    t = run.trace_data
    if t is None:
        return None
    spent = t.scope_s("mlp_core") / run.data["trace_steps"]
    if spent <= 0:
        return None
    need, _ = flops.roofline_s(*flops.train_mlp_step(
        run.data["model"], run.data["tokens_per_step"]), run.peak)
    return 100.0 * need / spent

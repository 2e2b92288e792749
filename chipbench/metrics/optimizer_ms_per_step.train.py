"""Device ms per training step of the ops under the ``optimizer_update``
scope: structural masking, clipping, the gradient norm and AdamW, summed
over the chips."""
from chipbench import step_trace

META = {"layer": "step / solver", "unit": "ms", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    return step_trace.scope_ms_per_step(run, "optimizer_update")

"""Device ms per training step of the ops under the ``trunk_param_grads``
scope: the per-layer parameter VJPs of the trunk, vmapped over its
layers, summed over the chips."""
from chipbench import step_trace

META = {"layer": "step / solver", "unit": "ms", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    return step_trace.scope_ms_per_step(run, "trunk_param_grads")

"""Device ms per training step of the ops under the ``trunk_forward`` scope:
the trunk's forward solve (serial at ``fwd_iters=0``, MGRIT otherwise),
summed over the chips."""
from chipbench import step_trace

META = {"layer": "step / solver", "unit": "ms", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    return step_trace.scope_ms_per_step(run, "trunk_forward")

"""Device ms per training step of the ops under the ``trunk_adjoint`` scope:
the layer-parallel step's adjoint solve (the MGRIT start and V-cycles, or
a serial sweep), summed over the chips. Holds ``mgrit_coarse``'s share."""
from chipbench import step_trace

META = {"layer": "step / solver", "unit": "ms", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    return step_trace.scope_ms_per_step(run, "trunk_adjoint")

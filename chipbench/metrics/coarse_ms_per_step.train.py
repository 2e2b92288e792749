"""Device ms per training step of the ops under the ``mgrit_coarse`` scope:
MGRIT's serial coarse part (the coarse start sweep, restriction, the
coarse propagator, the FAS right-hand side and the coarsest solve), inside
the forward or the adjoint solve, summed over the chips."""
from chipbench import step_trace

META = {"layer": "step / solver", "unit": "ms", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    return step_trace.scope_ms_per_step(run, "mgrit_coarse")

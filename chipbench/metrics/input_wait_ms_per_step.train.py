"""Device-idle ms per training step while the host builds or places the
batch: the holes in each chip's busy union, inside the profiled window,
that fall under the trainer's own ``train.input`` or ``train.h2d`` spans,
mean over the chips."""
from chipbench import step_trace

META = {"layer": "trainer", "unit": "ms", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    return step_trace.idle_under_ms_per_step(run, ("train.input",
                                                   "train.h2d"))

"""Set-up time: process start to window start (import, devices, weights
from the seed, programs loaded from the compile cache, warm-up)."""
META = {"unit": "s", "source": "host_clock"}


def read(run):
    return run.setup_s

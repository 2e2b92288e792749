"""90th percentile over the requests due in the window of (done - first
token) / (output tokens - 1)."""
META = {"unit": "ms", "source": "host_clock"}


def read(run):
    return run.data.get("tpot_p90_ms")

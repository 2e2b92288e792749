"""90th percentile over the requests due in the window of first token
minus the time the request was due on the arrival schedule."""
META = {"unit": "ms", "source": "host_clock"}


def read(run):
    return run.data.get("ttft_p90_ms")

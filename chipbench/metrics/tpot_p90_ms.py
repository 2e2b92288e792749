"""90th percentile over every inter-token gap of the requests due in the
window (``drivers/serve.TokenGaps``): each of a few thousand gaps counts,
so no one request's place in the order of arrivals moves it."""
META = {"unit": "ms", "source": "host_clock"}


def read(run):
    return run.data.get("tpot_p90_ms")

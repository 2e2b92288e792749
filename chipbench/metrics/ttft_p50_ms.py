"""Median over the requests due in the window of first token minus the
time the request was due on the arrival schedule. A window holds too few
requests for a tail: a percentile with fewer than ten requests beyond it
follows one request's place in the order of arrivals."""
META = {"unit": "ms", "source": "host_clock"}


def read(run):
    return run.data.get("ttft_p50_ms")

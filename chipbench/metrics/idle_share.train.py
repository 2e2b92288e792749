"""1 - (union of device-op intervals / traced window), mean over the
cell's chips, in a profiled segment of training steps."""
META = {"layer": "device", "unit": "%", "source": "device_trace",
        "moves": "train_tokens_per_s"}


def read(run):
    t = run.trace_data
    if t is None or t.window_s() <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s())

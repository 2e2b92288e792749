"""1 - (union of device-op intervals / traced window), mean over the
cell's chips, in a profiled segment in the middle of the open-loop
window (the scheduler's own dispatch spans share the device clock)."""
META = {"layer": "device", "unit": "%", "source": "device_trace",
        "moves": "tpot_p90_ms"}


def read(run):
    t = run.trace_data
    if t is None or t.window_s() <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s())

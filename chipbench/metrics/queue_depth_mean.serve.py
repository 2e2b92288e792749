"""Time-weighted mean of the scheduler's queue length over the window,
sampled by the benchmark's open-loop driver around every submission and
``Scheduler.step``."""
META = {"layer": "scheduler", "unit": "requests", "source": "host_clock",
        "moves": "ttft_p50_ms"}


def read(run):
    return run.data.get("queue_depth_mean")

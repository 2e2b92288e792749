"""Host time of the trainer's ``pipeline.batch_at`` per step over the
window, timed by the benchmark's proxy around the call."""
META = {"layer": "trainer", "unit": "ms", "source": "host_clock",
        "moves": "train_tokens_per_s"}


def read(run):
    return run.data.get("input_ms_per_step")
